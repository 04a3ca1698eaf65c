"""Shared by the readers of the engine step by kind: each device execution
of the step program (``jit_paged_step_w<width>`` on the ``XLA Modules``
line), joined to the ``serve_dispatch`` span that launched it, so that the
chip's time is read per step and split into decode and mixed steps.

How the join holds (my chip runs, PR 37; PERF.md section 3): the engine's
``serve_dispatch`` is a step annotation (``step_num`` = the engine's step
number), but a raw ``.xplane.pb`` carries no ``Steps`` line and no step
number on a device event: xprof's viewer groups them later.  What it does
carry is the runtime's own link from host to device: the host event
``DoEnqueueProgram`` (stat ``_p``) that put an execution on the chip's
queue, and the ``XLA Modules`` event (stat ``_c``, the same number) of that
execution.  The enqueue lands on the host's clock after the dispatch span
opened and before the next one opens, so each execution belongs to the
LAST dispatch span opened before its enqueue; the width in the program's
name must agree with the span's ``width`` stat, and each span takes one
execution at most.

A step is mixed when its dispatch has ``prefill_rows > 0`` (the engine's
own rule in ``_dispatch``), decode otherwise.  Executions cut by the
window's edges (the harness's ``bench:`` spans) are left out, and so is
one queued before the session began: no enqueue of it is in the trace.  A trace
without dispatch spans or without the link gives nothing: ``None``, and
nothing raises.  Imports nothing of the program.

The plain form (what ``load_xplane`` returns, and the fixture holds)::

    [[width, start_ns, dur_ns, enqueue_ns or None]...]   (by start)
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Optional

from benchmark import common, program_trace
from benchmark import trace_reduce as tr

STEP_PROGRAM = re.compile(r"^jit_paged_step_w(\d+)\(")
ENQUEUE = "DoEnqueueProgram"
KINDS = ("decode", "mixed")
# serve_table's partition, innermost first, then the named parts of it that
# the next perf_opt asks for by kind
TOP = ("sample", "lm_head", "kv_write", "attn_core", "attn", "mlp", "embed",
       "final_norm", "cow_copy", "layers")
PARTS = ("moe_experts", "moe_router", "moe_shared", "attn_window",
         "attn_full", "retention_chunk", "retention_decode", "mla_decode")


def load_xplane(path: str) -> List[List[Any]]:
    """The step program's executions on the first chip, each with the host
    time of the enqueue that the runtime links to it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, enqueued = None, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE:
                        link = dict(e.stats).get("_p")
                        if link is not None:
                            enqueued[link] = int(e.start_ns)
        elif plane.name.startswith("/device:TPU:") and (
                device is None or plane.name < device.name):
            device = plane
    out = []
    for line in device.lines if device is not None else ():
        if line.name != tr.MODULES_LINE:
            continue
        for e in line.events:
            m = STEP_PROGRAM.match(e.name)
            if m:
                out.append([int(m.group(1)), int(e.start_ns),
                            int(e.duration_ns),
                            enqueued.get(dict(e.stats).get("_c"))])
    return sorted(out, key=lambda x: x[1])


def _window(ctx):
    host = ctx["reduced"].get("host_spans") or []
    if host:
        return min(s for _, s, _ in host), max(e for _, _, e in host)
    return ctx["reduced"]["lo_ns"], ctx["reduced"]["hi_ns"]


def steps(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's steps whose execution was found: step number, the
    dispatch's stats, kind and device interval; said aloud with what could
    not be matched.  None where nothing matched."""
    if "engine_steps" in ctx:
        return ctx["engine_steps"]
    ctx["engine_steps"] = None
    dispatch = sorted(program_trace.spans(program_trace.load(ctx),
                                          "serve_dispatch"))
    if not dispatch:
        return None
    if "step_executions" not in ctx:
        ctx["step_executions"] = load_xplane(ctx["xplane"])
    lo, hi = _window(ctx)
    opened = [s for s, _, _, _ in dispatch]
    out, cut, unmatched, taken = [], 0, [], set()
    linked = [x[1] for x in ctx["step_executions"] if x[3] is not None]
    for width, start, dur, enq in ctx["step_executions"]:
        # queued before the session began (no enqueue in the trace), run
        # after the window opened: the left edge's, as one that starts
        # before it or ends past the close
        if (start < lo or start + dur > hi
                or enq is None and linked and start < linked[0]):
            cut += 1
            continue
        i = -1 if enq is None else bisect.bisect_right(opened, enq) - 1
        stats = dispatch[i][3] if i >= 0 else {}
        why = ("no enqueue linked" if enq is None
               else "enqueued before any dispatch" if i < 0
               else f"width {width} against the span's "
                    f"{stats.get('width')}" if stats.get("width") != width
               else "a second execution of one dispatch" if i in taken
               else None)
        if why:
            unmatched.append(f"w{width} at {start} ns ({why})")
            continue
        taken.add(i)
        out.append({"step": stats.get("step_num", stats.get("step")),
                    "stats": stats, "start": start, "end": start + dur,
                    "kind": "mixed" if int(stats.get("prefill_rows", 0))
                    else "decode"})
    numbers = [s["step"] for s in out]
    engine = ctx.get("window", {}).get("steps")
    common.say(
        f"engine steps on the device: {len(out)} step-program executions "
        f"in the window matched to their dispatch, {len(unmatched)} not "
        f"{unmatched[:4]}, {cut} cut by the window's edges; "
        f"{len(dispatch) - len(out)} of {len(dispatch)} dispatch spans "
        f"without an execution in the window; step numbers "
        f"{numbers[0] if numbers else None}..{numbers[-1] if numbers else None}"
        f"{' in order' if numbers == sorted(numbers) else ' OUT OF ORDER'}"
        + (f"; the harness counted {len(engine)} steps in the window"
           if isinstance(engine, list) else ""))
    if not out or "prefill_rows" not in out[0]["stats"]:
        return None
    ctx["engine_steps"] = out
    _say_table(ctx, out)
    return out


def _ms(ns, n):
    return 1e-6 * ns / n if n else float("nan")


def _say_table(ctx, found) -> None:
    """What the next perf_opt and the stall want, printed, not kept."""
    busy = ctx["reduced"]["busy_intervals"]
    by = {k: [s for s in found if s["kind"] == k] for k in KINDS}
    n = {k: len(v) for k, v in by.items()}
    length = {k: sum(s["end"] - s["start"] for s in v)
              for k, v in by.items()}
    idle = {k: length[k] - tr.overlap([(s["start"], s["end"]) for s in v],
                                      busy) for k, v in by.items()}
    lo, hi = _window(ctx)
    in_window = tr.length(tr.clip(busy, lo, hi))
    common.say(
        f"  by kind: " + "; ".join(
            f"{k} {n[k]} steps, device {_ms(length[k], n[k]):.3f} ms a step "
            f"(idle inside {_ms(idle[k], n[k]):.4f})" for k in KINDS)
        + f"; matched intervals {1e-9 * sum(length.values()):.4f} s against "
        f"the window's device-busy {1e-9 * in_window:.4f} s")
    # operations by step: each lies inside the execution it started in
    pt = program_trace.load(ctx)
    starts = [s["start"] for s in found]
    spans: Dict[tuple, list] = {}
    for name, start, dur, at in pt["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= found[i]["end"]:
            continue
        parts = program_trace.names_in(pt["scopes"][at]) if at >= 0 else []
        top = next((p for p in TOP if p in parts),
                   "scoped_other" if parts else "unscoped")
        kind = found[i]["kind"]
        for key in [top] + [p for p in PARTS if p in parts]:
            spans.setdefault((kind, key), []).append((start, start + dur))
    covered = {k: tr.length(tr.union(v)) for k, v in spans.items()}
    keys = sorted({key for _, key in covered},
                  key=lambda key: (key in PARTS, -covered.get(
                      ("decode", key), 0) - covered.get(("mixed", key), 0)))
    common.say("  device ms a step by scope, decode / mixed (covered; "
               + "named parts last): " + ", ".join(
                   f"{key} {_ms(covered.get(('decode', key), 0), n['decode']):.3f}"
                   f" / {_ms(covered.get(('mixed', key), 0), n['mixed']):.3f}"
                   for key in keys))
    if n["mixed"] and n["decode"]:
        prefill = sum(int(s["stats"].get("positions", 0))
                      - int(s["stats"].get("rows", 0))
                      + int(s["stats"].get("prefill_rows", 0))
                      for s in by["mixed"]) / n["mixed"]
        extra = _ms(length["mixed"], n["mixed"]) - _ms(length["decode"],
                                                       n["decode"])
        common.say(f"  a mixed step: {prefill:.1f} prefill positions, "
                   f"{extra:.3f} ms over a decode step, "
                   f"{1e3 * extra / max(prefill, 1):.3f} us a prefill "
                   "position")
    longest = max(found, key=lambda s: s["end"] - s["start"])
    serve = [sp for sp in program_trace.spans(pt, "serve_step")
             if lo <= sp[0] and sp[1] <= hi]
    say = (f"  longest device step: {longest['step']} ({longest['kind']}) "
           f"{_ms(longest['end'] - longest['start'], 1):.3f} ms")
    if serve:
        s, e = max(serve, key=lambda sp: sp[1] - sp[0])[:2]
        during = [f"{x['step']} {_ms(x['end'] - x['start'], 1):.3f}"
                  for x in found if x["start"] < e and x["end"] > s]
        say += (f"; longest host serve_step {_ms(e - s, 1):.3f} ms, the "
                f"chip idle {_ms(e - s - tr.overlap([(s, e)], busy), 1):.3f}"
                f" ms of it, steps on the chip during it (ms): {during}")
    common.say(say)


def mean_ms(ctx, kind: str, least: int = 1) -> Optional[float]:
    """Mean device interval of the window's steps of that kind; None under
    ``least`` of them."""
    found = steps(ctx)
    if not found:
        return None
    of = [s["end"] - s["start"] for s in found if s["kind"] == kind]
    return 1e-6 * sum(of) / len(of) if len(of) >= least else None


def mixed_share(ctx) -> Optional[float]:
    """Σ mixed intervals over Σ all matched intervals, %."""
    found = steps(ctx)
    if not found:
        return None
    mixed = sum(s["end"] - s["start"] for s in found if s["kind"] == "mixed")
    return 100.0 * mixed / sum(s["end"] - s["start"] for s in found)
