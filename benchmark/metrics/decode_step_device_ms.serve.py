"""Mean device interval of the window's decode steps: each execution of the
step program, joined to the ``serve_dispatch`` step that launched it
(``_steps.py``), whose dispatch had no prefilling row; its ``XLA Modules``
event is what the chip spent on that step, gaps inside it included."""
from benchmark.metrics import _steps


def read(ctx):
    return _steps.mean_ms(ctx, "decode")
