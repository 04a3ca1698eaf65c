"""Of the blocks of splash attention's static (causal) mask, the share the
per-row block map made the kernel run: sum of ``attn_blocks_run`` over sum
of ``attn_blocks_static`` on the window's ``dispatch`` spans (the train
loop counts both from the rows it hands the step, by the rule the step
applies on the device).  A program whose spans carry no such stats (the
parent of the PR that brought them) reads nothing and is left out."""
from benchmark import common, program_trace


def read(ctx):
    lo, hi = ctx["reduced"]["lo_ns"], ctx["reduced"]["hi_ns"]
    stats = [st for s, _, _, st in program_trace.spans(
        program_trace.load(ctx), "dispatch")
        if lo <= s < hi and "attn_blocks_static" in st]
    static = sum(int(st["attn_blocks_static"]) for st in stats)
    if not static:
        return None
    run = sum(int(st["attn_blocks_run"]) for st in stats)
    common.say(f"splash block map: {run} of {static} static blocks run over "
               f"{len(stats)} dispatch spans")
    return 100.0 * run / static
