"""Due time to the start of the step that first schedules the request,
95th percentile over the requests due in the window that were scheduled."""
from benchmark import common


def read(ctx):
    waits = [r["scheduled"] - r["due"] for r in ctx["window"]["due_in"]
             if r["scheduled"] is not None]
    return 1e3 * common.quantile(waits, 0.95) if waits else None
