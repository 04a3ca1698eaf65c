"""Host milliseconds per engine step: the harness's span around
engine.step() minus the device-busy time inside it, on the trace's clock."""
from benchmark import trace_reduce as tr


def read(ctx):
    r = ctx["reduced"]
    steps = tr.union([(s, e) for n, s, e in r["host_spans"]
                      if n == "engine_step"])
    n = sum(1 for name, _, _ in r["host_spans"] if name == "engine_step")
    if not n:
        return None
    inside = tr.overlap(steps, r["busy_intervals"])
    return 1e-6 * (tr.length(steps) - inside) / n
