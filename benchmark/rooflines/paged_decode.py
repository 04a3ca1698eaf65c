"""Work of paged attention in a serving step: the K and V of every active
row's real context are read once, the step's new rows written.  With one
query position per row and a context of hundreds to thousands of keys this
is bound by memory bandwidth, not by compute."""

from __future__ import annotations

from benchmark.rooflines import step
from benchmark.weights import head_dim

# the program's pallas_call carries no name of its own: in a serving step
# the kernel is the only Mosaic custom call and appears as `closed_call.<n>`
# (one per step width); PERF.md asks the tracing PR for a stable name
EVENTS = r"^closed_call(\.\d+)?$"
OPCODE = "custom-call"      # a Pallas kernel, not another closed call
NAMES_PER_PROGRAM = 1       # one kernel in each step program


def kv_bytes_per_token(cfg, dtype_bytes: int = 2) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * dtype_bytes)


def work(cfg, steps):
    """(FLOPs, bytes) over the engine steps given (runners/serve.py's step
    records: summed context, new positions and attended pairs)."""
    per = kv_bytes_per_token(cfg)
    bytes_ = sum(per * (s["context"] + s["positions"]) for s in steps)
    flops = sum(step.attention_pair_flops(cfg) * s["attended"] for s in steps)
    return flops, bytes_
