"""Share of the window the loop waited for input: the recipe's own
data_wait + data_staging timers (host clock).  It is not device idle."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * w["input_wait_s"] / w["seconds"]
