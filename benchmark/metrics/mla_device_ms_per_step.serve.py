"""Device milliseconds per step under the latent attention's scopes:
``mla_latent_write``, ``mla_absorb_q``, ``attn_core`` (the kernel, under
``mla_decode``) and ``mla_out``."""
from benchmark.metrics import _latent_moe as lm


def read(ctx):
    return lm.scope_ms_per_step(ctx, lm.MLA)
