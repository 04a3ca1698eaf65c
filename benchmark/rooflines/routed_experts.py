"""Work of the routed experts in a serving step, as the mathematics
requires it, for ANY family whose reference states one expert's size
(``matmul_params(cfg)["expert"]``): every expert that got a token has its
three matrices read once, and every (token, expert) assignment costs the
three products of its gated FFN.  ``events`` are the program's own counts,
one ``serve_experts`` event per step: ``hit`` = experts with a token summed
over the expert layers, ``assignments`` = tokens they got.
(``rooflines/moe_experts.py`` is the same arithmetic with the size read
from ``cfg["moe_intermediate_size"]``, which only one family's files have.)
"""

from __future__ import annotations

from benchmark import manifest as mf


def work(cfg, events, dtype_bytes: int = 2):
    """(FLOPs, bytes) over the steps whose events are given."""
    per = mf.family(cfg).matmul_params(cfg)["expert"]
    flops = sum(2 * per * int(e["assignments"]) for e in events)
    bytes_ = sum(dtype_bytes * per * int(e["hit"]) for e in events)
    return flops, bytes_
