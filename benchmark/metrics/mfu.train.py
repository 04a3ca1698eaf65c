"""The whole train step's share of the chip's peak: required FLOPs of the
window's non-padding tokens (benchmark/rooflines/step.py) over its seconds,
chips and the bf16 peak of the device kind."""
from benchmark.rooflines import step


def read(ctx):
    w = ctx["window"]
    flops = step.train_flops(ctx["config"], w["segments"])
    return 100.0 * flops / (w["seconds"] * ctx["device"]["count"]
                            * ctx["peaks"]["bf16_flops_per_s"])
