"""Keys a window layer had to read over keys a full layer had to read, %,
over the traced steps (the program's ``serve_kv_read`` events): how much of
the context the window still showed.  It says whether a run's contexts
were the cell's."""
from benchmark.metrics import _kv_read


def read(ctx):
    events = _kv_read.events(ctx)
    if not events:
        return None
    full = sum(int(e["full_keys"]) for e in events)
    return 100.0 * sum(int(e["window_keys"]) for e in events) / full \
        if full else None
