"""Of the device intervals of every step matched in the window, the share
that mixed steps took, %: where the chip's time goes by kind of step, which
the per-step averages of the scopes' readers cannot tell apart."""
from benchmark.metrics import _steps


def read(ctx):
    return _steps.mixed_share(ctx)
