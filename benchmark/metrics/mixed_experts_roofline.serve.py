"""The routed experts of the steps that carry a prefill chunk (the wide
step's expert path), against their roofline: ``rooflines/routed_experts.py``
over those steps' ``serve_experts`` events, over the device time covered
under the ``moe_experts`` scope inside those steps' executions (the step
split of ``metrics/_steps.py``).  Nothing to read where no mixed step or
no expert event was found."""
import bisect

from benchmark import common, program_trace
from benchmark import trace_reduce as tr
from benchmark.metrics import _latent_moe as lm, _steps
from benchmark.rooflines import routed_experts


def read(ctx):
    found, events = _steps.steps(ctx), lm.expert_events(ctx)
    if not found or not events:
        return None
    mixed = sorted((s for s in found if s["kind"] == "mixed"),
                   key=lambda s: s["start"])
    numbers = {s["step"] for s in mixed}
    events = [e for e in events if e.get("step") in numbers]
    if not mixed or not events:
        return None
    pt = program_trace.load(ctx)
    starts = [s["start"] for s in mixed]
    spans = []
    for _, start, dur, at in pt["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if (at >= 0 and i >= 0 and start < mixed[i]["end"]
                and "moe_experts" in program_trace.names_in(pt["scopes"][at])):
            spans.append((start, start + dur))
    if not spans:
        return None
    seconds = 1e-9 * tr.length(tr.union(spans))
    flops, bytes_ = routed_experts.work(ctx["config"], events)
    chips, peaks = ctx["device"]["count"], ctx["peaks"]
    t_flops = flops / chips / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / chips / peaks["hbm_bytes_per_s"]
    common.say(f"mixed_experts: {len(events)} mixed steps' events, device "
               f"{seconds:.4f} s under moe_experts; least "
               f"{max(t_flops, t_bytes):.4f} s, bound by "
               f"{'compute' if t_flops >= t_bytes else 'bandwidth'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
