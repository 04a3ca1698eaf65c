"""The latent (MLA) paged attention kernel against its roofline."""
from benchmark.metrics import _kernel
from benchmark.rooflines import mla_decode


def read(ctx):
    return _kernel.roofline_share(ctx, mla_decode, ctx["window"]["steps"])
