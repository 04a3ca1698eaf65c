"""Work of paged attention in the serving steps of a stack with FULL and
WINDOW layers whose steps carry prefill chunks, as the mathematics
requires it whatever the kernels walk or pad: every (query, key) pair a
layer of each kind had to compute costs QK^T and PV at that kind's query
heads, a layer reads the K and V of the keys its rows see once, and every
layer writes the step's new positions.  ``events`` are the program's own
counts, one ``serve_kv_read`` event a step, reckoned by the scheduler from
the plan: ``full_pairs`` / ``window_pairs`` = Σ over rows and their new
positions of the keys each one sees, ``full_keys`` / ``window_keys`` = the
keys a layer of the kind reads, ``positions`` = new positions written.
The query heads and layer counts of each kind are the family's
(``reference/<model_type>.py``: ``sizes``)."""

from __future__ import annotations

from benchmark import manifest as mf

# ``PagedKVView.attend`` names the Pallas call after the block group it
# stands in; XLA:TPU names a Mosaic custom call after the innermost
# component of its scope path
EVENTS = r"^paged_decode_(full|window)(\.\d+)?$"
OPCODE = "custom-call"
# one call a layer of a scan body in each step program: the leading dense
# layer's (full) and a period's (three window, one full)
NAMES_PER_PROGRAM = 5


def work(cfg, events):
    """(FLOPs, bytes) over the steps whose events are given."""
    family = mf.family(cfg)
    z = family.sizes(cfg)
    flops = sum(4 * z["D"] * (z["Hq_full"] * z["n_full"] * int(e["full_pairs"])
                              + z["Hq_window"] * z["n_window"]
                              * int(e["window_pairs"])) for e in events)
    read = sum(z["n_full"] * int(e["full_keys"])
               + z["n_window"] * int(e["window_keys"]) for e in events)
    written = z["L"] * sum(int(e["positions"]) for e in events)
    return flops, family.kv_bytes_per_token_layer(cfg) * (read + written)
