"""The two paged attention kernels of a stack with full and window layers
(``paged_decode_full``, ``paged_decode_window``), decode steps and steps
that carry prefill chunks alike, against their roofline: the (query, key)
pairs each kind of layer HAD to compute and the keys it had to read (the
program's ``serve_kv_read`` events, ``rooflines/chunked_paged_attention.py``),
whatever the kernels walked or padded.  A program whose events carry no
pair counts gives nothing to read."""
from benchmark.metrics import _kernel, _kv_read
from benchmark.rooflines import chunked_paged_attention


def read(ctx):
    events = [e for e in _kv_read.events(ctx) or ()
              if "full_pairs" in e and "window_pairs" in e]
    if not events:
        return None
    return _kernel.roofline_share(ctx, chunked_paged_attention, events)
