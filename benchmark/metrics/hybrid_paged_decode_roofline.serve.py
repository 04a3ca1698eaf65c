"""The two paged attention kernels of a stack with full and window layers
(``paged_decode_full``, ``paged_decode_window``) against their (bandwidth)
roofline: the keys each kind of layer HAD to read (the program's
``serve_kv_read`` events), whatever the kernels walked."""
from benchmark.metrics import _kernel, _kv_read
from benchmark.rooflines import hybrid_paged_decode


def read(ctx):
    events = _kv_read.events(ctx)
    if not events:
        return None
    return _kernel.roofline_share(ctx, hybrid_paged_decode, events)
