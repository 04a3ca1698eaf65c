"""The power-retention kernels (decode and chunk) against their roofline."""
from benchmark.metrics import _kernel
from benchmark.rooflines import retention


def read(ctx):
    return _kernel.roofline_share(ctx, retention, ctx["window"]["steps"])
