"""The paged attention kernel against its (bandwidth) roofline."""
from benchmark.metrics import _kernel
from benchmark.rooflines import paged_decode


def read(ctx):
    return _kernel.roofline_share(ctx, paged_decode, ctx["window"]["steps"])
