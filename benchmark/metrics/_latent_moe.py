"""Shared by the readers of the latent-attention and routed-expert layers:
device time by the scope names those layers give their operations, and the
``serve_experts`` events the engine stamps after each step of a model with
routed layers.  A trace of a program without them gives nothing to read:
every function returns ``None`` and nothing raises."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from benchmark import common, program_trace
from benchmark import trace_reduce as tr

MLA = lambda part: part == "attn_core" or part.startswith("mla_")
MOE = lambda part: part.startswith("moe_")
MOE_EXPERTS = lambda part: part == "moe_experts"


def steps_run(ctx) -> int:
    return len(program_trace.spans(program_trace.load(ctx),
                                   "serve_dispatch"))


def scope_ns(ctx: Dict[str, Any], wanted: Callable[[str], bool]
             ) -> Optional[int]:
    """Device nanoseconds covered by the operations whose scope path holds
    a name that ``wanted`` accepts; None where no operation does.  Covered,
    not summed: a ``cond`` or a ``while`` that the trace lists beside the
    operations of its own body (``cond.1.clone.7`` round an expert's three
    products) would count its time twice."""
    pt = program_trace.load(ctx)
    hit = [bool(any(wanted(p) for p in program_trace.names_in(s)))
           for s in pt["scopes"]]
    spans = [(s, s + d) for _, s, d, at in pt["ops"] if at >= 0 and hit[at]]
    return tr.length(tr.union(spans)) if spans else None


def scope_ms_per_step(ctx, wanted) -> Optional[float]:
    ns, n = scope_ns(ctx, wanted), steps_run(ctx)
    if ns is None or not n:
        return None
    return 1e-6 * ns / n


def expert_events(ctx) -> Optional[List[Dict[str, int]]]:
    """The stats of the trace's ``serve_experts`` events."""
    pt = program_trace.load(ctx)
    events = [st for _, _, _, st in program_trace.spans(pt, "serve_experts")]
    if not events:
        return None
    common.say(f"serve_experts: {len(events)} events, "
               f"{sum(int(e['assignments']) for e in events)} assignments, "
               f"{sum(int(e['hit']) for e in events)} experts hit")
    return events
