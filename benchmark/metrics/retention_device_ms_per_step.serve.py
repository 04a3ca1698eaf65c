"""Device milliseconds per step COVERED by operations under the retention
core's scopes: ``attn_core`` (which holds ``state_reset`` and the kernel
under ``retention_decode`` or ``retention_chunk``) and any ``retention_*``
(the gate, the output projection)."""
from benchmark.metrics import _latent_moe as lm

RETENTION = lambda part: (part in ("attn_core", "state_reset")
                          or part.startswith("retention_"))


def read(ctx):
    return lm.scope_ms_per_step(ctx, RETENTION)
