"""Splash attention (forward and backward) against its roofline."""
from benchmark.metrics import _kernel
from benchmark.rooflines import splash


def read(ctx):
    return _kernel.roofline_share(ctx, splash, ctx["window"]["segments"])
