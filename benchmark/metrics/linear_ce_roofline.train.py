"""The fused linear cross-entropy kernel against its roofline."""
from benchmark.metrics import _kernel
from benchmark.rooflines import linear_ce


def read(ctx):
    return _kernel.roofline_share(ctx, linear_ce, ctx["window"]["segments"])
