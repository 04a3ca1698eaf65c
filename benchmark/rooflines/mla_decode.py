"""Work of latent (MLA) paged attention in a serving step, as the
mathematics requires it whatever implements it: ONE row of ``kv_lora_rank +
qk_rope_head_dim`` values per cached token and layer is read once and
serves every head (the step's new rows are written), and each (query, key)
pair costs every head's score against that row and its value over the
row's ``kv_lora_rank``.  At Kimi-K2's widths that is 1,152 bytes a token a
layer and 139,264 FLOPs a pair a layer: 121 FLOPs a byte under 64 decode
rows, half the v5e's ridge, so bandwidth bounds it, but not by much."""

from __future__ import annotations

from benchmark import manifest as mf

# XLA:TPU names a Mosaic custom call after the innermost component of its
# scope path; the rung wraps its pallas_call in ``mla_decode``
EVENTS = r"^mla_decode(\.\d+)?$"
OPCODE = "custom-call"
# one call in each of the model's two layer stacks (the dense layers' scan
# and the expert layers'), in each step program
NAMES_PER_PROGRAM = 2


def work(cfg, steps):
    """(FLOPs, bytes) over the engine steps given (runners/serve.py's step
    records: summed context, new positions and attended pairs)."""
    family = mf.family(cfg)
    per = family.latent_bytes_per_token(cfg)
    bytes_ = sum(per * (s["context"] + s["positions"]) for s in steps)
    flops = sum(family.attention_pair_flops(cfg) * s["attended"]
                for s in steps)
    return flops, bytes_
