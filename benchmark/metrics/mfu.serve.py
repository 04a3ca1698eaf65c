"""The whole engine step's share of the chip's peak: required FLOPs of
the positions the window's steps had to process (benchmark/rooflines/
step.py; padding rows and columns of the step buffer not credited)."""
from benchmark.rooflines import step


def read(ctx):
    w = ctx["window"]
    flops = step.serve_flops(ctx["config"], w["steps"])
    return 100.0 * flops / (w["seconds"] * ctx["device"]["count"]
                            * ctx["peaks"]["bf16_flops_per_s"])
