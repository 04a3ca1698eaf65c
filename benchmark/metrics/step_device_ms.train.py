"""Device-busy milliseconds per optimizer step: the union of device-op
intervals in the traced window over its steps."""


def read(ctx):
    return 1e3 * ctx["reduced"]["busy_s"] / ctx["window"]["steps"]
