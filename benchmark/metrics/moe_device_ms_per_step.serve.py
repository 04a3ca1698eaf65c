"""Device milliseconds per step under the expert layers' scopes:
``moe_router``, ``moe_experts`` and ``moe_shared``."""
from benchmark.metrics import _latent_moe as lm


def read(ctx):
    return lm.scope_ms_per_step(ctx, lm.MOE)
