"""Work of paged attention in a serving step of a stack with FULL and
WINDOW layers, as the mathematics requires it whatever the kernel walks:
a full layer reads the K and V of every active row's context once, a
window layer those of the keys its window still shows, and every layer
writes the step's new positions.  ``events`` are the program's own counts,
one ``serve_kv_read`` event a step, reckoned by the scheduler from the
plan: ``full_keys`` = the active rows' contexts summed, ``window_keys`` =
``min(context, window)`` summed, ``positions`` = new positions written.
With one query a row this is bound by memory bandwidth."""

from __future__ import annotations

from benchmark import manifest as mf

# ``PagedKVView.attend`` names the Pallas call after the block group it
# stands in; XLA:TPU names a Mosaic custom call after the innermost
# component of its scope path
EVENTS = r"^paged_decode_(full|window)(\.\d+)?$"
OPCODE = "custom-call"
# one call a layer of the layer scan's BODY in each step program, and the
# body is a period of the stack: one full and three window layers
NAMES_PER_PROGRAM = 4


def keys_read(cfg, events) -> int:
    """(layer, key) pairs the step's layers had to read."""
    z = mf.family(cfg).sizes(cfg)
    return sum(z["n_full"] * int(e["full_keys"])
               + z["n_window"] * int(e["window_keys"]) for e in events)


def work(cfg, events):
    """(FLOPs, bytes) over the steps whose events are given."""
    family = mf.family(cfg)
    z = family.sizes(cfg)
    read = keys_read(cfg, events)
    written = z["L"] * sum(int(e["positions"]) for e in events)
    bytes_ = family.kv_bytes_per_token_layer(cfg) * (read + written)
    # a decode row's one query against each key it reads: QK^T and PV (a
    # chunk's later queries see more; its rows are few)
    flops = 4 * z["Hq"] * z["D"] * read
    return flops, bytes_
