"""Of the device time covered under the ``moe_experts`` scope, the share
that is the expert kernel's own operations: the Mosaic calls named
``moe_decode`` (XLA:TPU names a custom call after the innermost component
of its scope path; ``ops/moe_decode_kernel.py`` gives it that one).  The rest
of the scope is what surrounds the kernel: the combine matrix, the list of
hit experts, the wide step's loop.  A program with no such operation (the
parent of the PR that brought the kernel; a model without routed experts)
reads nothing and is left out."""
import re

from benchmark import common, program_trace
from benchmark import trace_reduce as tr
from benchmark.metrics import _latent_moe as lm

KERNEL = re.compile(r"^moe_decode(\.\d+)?$")


def read(ctx):
    pt = program_trace.load(ctx)
    spans = [(s, s + d) for name, s, d, at in pt["ops"]
             if at >= 0 and KERNEL.match(name)
             and "moe_experts" in program_trace.names_in(pt["scopes"][at])]
    scope = lm.scope_ns(ctx, lm.MOE_EXPERTS)
    if not spans or not scope:
        return None
    kernel = tr.length(tr.union(spans))
    common.say(f"moe_decode: {len(spans)} kernel calls, {1e-9 * kernel:.4f} s "
               f"of {1e-9 * scope:.4f} s covered under moe_experts")
    return 100.0 * kernel / scope
