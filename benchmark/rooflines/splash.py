"""Work of training attention (forward and backward) over packed rows:
within-document causal pairs, whatever kernel implements it.  The backward
pass needs four matrix products per pair against the forward's two; the
score recomputation inside a flash backward is not credited.  Compute-bound
at head size 128 and documents of hundreds of tokens."""

from __future__ import annotations

from benchmark.rooflines import step
from benchmark.weights import head_dim

# device operations of the kernel in a trace (PERF.md section 3)
EVENTS = r"^splash_"
OPCODE = "custom-call"
NAMES_PER_PROGRAM = 4       # forward, its re-forward, dkv and dq at most


def work(cfg, steps):
    """(FLOPs, bytes) of all layers' attention over the given steps."""
    pairs = sum(step.causal_pairs(segs) for segs in steps)
    tokens = sum(int((s != 0).sum()) for segs in steps for s in segs)
    flops = 3 * step.attention_pair_flops(cfg) * pairs
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    # writes dq, dk, dv: twelve [tokens, heads, D] bf16 arrays a layer
    width = (cfg["num_attention_heads"] * 8
             + cfg["num_key_value_heads"] * 4) * head_dim(cfg)
    return flops, 2 * width * tokens * cfg["num_hidden_layers"]
