"""Mean active rows of the window's steps over max_num_seqs."""
from benchmark import common


def read(ctx):
    w = ctx["window"]
    if not w["steps"]:
        return None
    mixed = sum(1 for s in w["steps"] if s["width"] > 1)
    common.say("mixed_steps/steps %d/%d" % (mixed, len(w["steps"])))
    return (100.0 * sum(s["rows"] for s in w["steps"])
            / (len(w["steps"]) * w["max_num_seqs"]))
