"""Device milliseconds per step COVERED by operations under the full
(NoPE) layers' attention scope, ``attn_full`` (projections, ``kv_write``,
the kernel under ``attn_core``, the output projection)."""
from benchmark.metrics import _latent_moe as lm


def read(ctx):
    return lm.scope_ms_per_step(ctx, lambda part: part == "attn_full")
