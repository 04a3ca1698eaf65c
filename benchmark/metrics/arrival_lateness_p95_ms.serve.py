"""How late the load generator ran: submit time minus due time, 95th
percentile over the requests due in the window."""
from benchmark import common


def read(ctx):
    late = [r["submitted"] - r["due"] for r in ctx["window"]["due_in"]]
    return 1e3 * common.quantile(late, 0.95) if late else None
