"""Held experts that got a token, over the held experts of all expert
layers, mean over the steps, % (the program's ``serve_experts`` events)."""
from benchmark import manifest as mf
from benchmark.metrics import _latent_moe as lm


def read(ctx):
    events = lm.expert_events(ctx)
    if not events:
        return None
    z = mf.family(ctx["config"]).sizes(ctx["config"])
    return (100.0 * sum(int(e["hit"]) for e in events)
            / (len(events) * z["n_moe"] * z["held"]))
