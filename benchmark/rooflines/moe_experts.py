"""Work of the routed experts in a serving step, as the mathematics
requires it: every expert that got a token has its three matrices read
once, and every (token, expert) assignment costs the three products of a
SwiGLU.  ``events`` are the program's own counts, one ``serve_experts``
event per step: ``hit`` = experts with a token summed over the expert
layers, ``assignments`` = tokens they got.  Under a few dozen decode rows
an expert sees one or two tokens: its cost is its weights' read."""

from __future__ import annotations


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def work(cfg, events, dtype_bytes: int = 2):
    """(FLOPs, bytes) over the steps whose events are given."""
    per = expert_params(cfg)
    flops = sum(2 * per * int(e["assignments"]) for e in events)
    bytes_ = sum(dtype_bytes * per * int(e["hit"]) for e in events)
    return flops, bytes_
