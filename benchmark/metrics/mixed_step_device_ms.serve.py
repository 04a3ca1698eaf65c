"""Mean device interval of the window's mixed steps (a dispatch with
``prefill_rows > 0``: the wide program that carries a prefill chunk),
joined as ``decode_step_device_ms.serve``; left out under three of them."""
from benchmark.metrics import _steps


def read(ctx):
    return _steps.mean_ms(ctx, "mixed", least=3)
