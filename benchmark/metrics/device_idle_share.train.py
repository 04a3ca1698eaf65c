"""1 - busy / window on the fullest chip, from the trace."""


def read(ctx):
    r = ctx["reduced"]
    return 100.0 * (1.0 - r["busy_fullest_s"] / r["window_s"])
