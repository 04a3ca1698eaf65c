"""From a profiler trace to numbers.  ``load_xplane`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` into a plain dict (device operations per
chip, XLA modules per chip, the harness's ``bench:`` host spans);
``reduce`` turns that dict into busy time, idle gaps laid against the host
spans, time per operation name, and collective time with its exposed part.
``benchmark/tests`` holds one recorded trace in the plain form and the
numbers it must reduce to, so that no later PR moves the yardstick unseen.

What the planes of a v5e trace look like (seen in this PR's first trace,
PERF.md section 3): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per executed program) and ``XLA Ops`` (one event
per executed HLO operation; control-flow operations span their bodies, so
time per name leaves out the containers and busy time is a union), and one
``/host:CPU`` plane whose thread lines hold the TraceAnnotations.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

SPAN_PREFIX = "bench:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# operations that only contain other operations
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)*$")
COLLECTIVES = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")


def short_name(text: str) -> str:
    """An operation's event carries its whole HLO line, ``%fusion.12 =
    bf16[...] fusion(...)``: keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


OPCODE = re.compile(r" = (?:\(.*?\)|\S+) ([a-z][a-z\-]*)\(")


def opcode(text: str) -> str:
    """The HLO opcode of an operation's event (``fusion``, ``copy``,
    ``custom-call``: a Pallas kernel is a custom call), or ``""``."""
    m = OPCODE.search(text)
    return m.group(1) if m else ""


def load_xplane(path: str) -> Dict[str, Any]:
    """{"devices": {plane: {"ops": [[name, start_ns, dur_ns, opcode]...],
    "modules": [[name, start_ns, dur_ns]...]}}, "host": [[name, start_ns,
    dur_ns]...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [[short_name(e.name), int(e.start_ns),
                             int(e.duration_ns)]
                            + ([opcode(e.name)] if key == "ops" else [])
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host"].append(
                            [e.name[len(SPAN_PREFIX):], int(e.start_ns),
                             int(e.duration_ns)])
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(cover: List[Tuple[int, int]], lo: int, hi: int):
    """The gaps of a disjoint sorted cover inside [lo, hi]."""
    gaps, at = [], lo
    for a, b in cover:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def overlap(intervals, cover) -> int:
    """Length of ``intervals`` (disjoint) that ``cover`` (disjoint) covers."""
    total, j = 0, 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def innermost(spans: List[Tuple[str, int, int]]):
    """{name: disjoint sorted intervals during which a span of that name is
    the innermost one open}.  Spans of one thread nest or follow each other."""
    out: Dict[str, List[Tuple[int, int]]] = {}
    stack: List[List[Any]] = []        # [name, end, resume_at]

    def emit(name, a, b):
        if b > a:
            out.setdefault(name, []).append((a, b))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end, at = stack.pop()
            emit(n, at, end)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)
        if stack:
            emit(stack[-1][0], stack[-1][2], s)
        stack.append([name, e, s])
    while stack:
        n, end, at = stack.pop()
        emit(n, at, end)
        if stack:
            stack[-1][2] = max(stack[-1][2], end)
    return {n: union(v) for n, v in out.items()}


def reduce(raw: Dict[str, Any]) -> Dict[str, Any]:
    devices = {n: d for n, d in sorted(raw["devices"].items()) if d["ops"]}
    if not devices:
        raise ValueError("the trace holds no device operation")
    host = [(n, s, s + d) for n, s, d in raw["host"]]
    op_end = max(op[1] + op[2] for dev in devices.values()
                 for op in dev["ops"])
    lo = min([s for _, s, _ in host]
             or [op[1] for dev in devices.values() for op in dev["ops"]])
    hi = max([e for _, _, e in host] + [op_end])
    window = hi - lo

    per_device = {}
    for name, dev in devices.items():
        leaves = [(op[0], op[1], op[1] + op[2]) for op in dev["ops"]
                  if not CONTAINERS.match(op[0])]
        busy = clip(union([(s, e) for _, s, e in leaves]), lo, hi)
        coll = union([(s, e) for n, s, e in leaves if COLLECTIVES.match(n)])
        comp = union([(s, e) for n, s, e in leaves
                      if not COLLECTIVES.match(n)])
        by_name: Dict[str, int] = {}
        for n, s, e in leaves:
            by_name[n] = by_name.get(n, 0) + (e - s)
        modules: Dict[str, int] = {}
        for n, _, _ in dev["modules"]:
            modules[n] = modules.get(n, 0) + 1
        per_device[name] = {
            "busy": busy, "busy_ns": length(busy), "by_name": by_name,
            "modules": modules, "collective_ns": length(clip(coll, lo, hi)),
            "exposed_collective_ns": length(clip(coll, lo, hi))
            - overlap(clip(coll, lo, hi), comp)}

    fullest = max(per_device, key=lambda n: per_device[n]["busy_ns"])
    first = per_device[next(iter(per_device))]
    # idle gaps of the first chip, laid against the innermost host span
    # open at each moment; what no span covers is "no_span"
    gaps = subtract(first["busy"], lo, hi)
    gaps_by: Dict[str, int] = {}
    covered = 0
    for label, segs in innermost(host).items():
        t = overlap(segs, gaps)
        if t:
            gaps_by[label] = t
            covered += t
    if length(gaps) > covered:
        gaps_by["no_span"] = length(gaps) - covered
    by_name_all: Dict[str, int] = {}
    for d in per_device.values():
        for n, t in d["by_name"].items():
            by_name_all[n] = by_name_all.get(n, 0) + t
    chips = len(per_device)
    top = sorted(by_name_all.items(), key=lambda kv: -kv[1])[:10]
    busy_s = sum(d["busy_ns"] for d in per_device.values()) / chips / 1e9
    return {
        "window_s": window / 1e9, "lo_ns": lo, "hi_ns": hi,
        "busy_s": busy_s,
        "busy_fullest_s": per_device[fullest]["busy_ns"] / 1e9,
        "chips": chips,
        "op_seconds": {n: t / chips / 1e9 for n, t in by_name_all.items()},
        # opcode per operation name, where the trace gave one
        "opcodes": {op[0]: op[3] for dev in devices.values()
                    for op in dev["ops"] if len(op) > 3},
        "modules": first["modules"],
        "collective_s": first["collective_ns"] / 1e9,
        "exposed_collective_s": first["exposed_collective_ns"] / 1e9,
        "busy_intervals": first["busy"],
        "host_spans": host,
        "breakdown": {
            "device_ops": [[n, t / chips / 1e9] for n, t in top],
            "idle_gaps": [[n, t / 1e9] for n, t in sorted(
                gaps_by.items(), key=lambda kv: -kv[1])[:10]]},
        "summary": {"window_s": round(window / 1e9, 4),
                    "busy_s": round(busy_s, 4), "chips": chips,
                    "ops": sum(len(d["ops"]) for d in devices.values()),
                    "host_spans": len(host)},
    }


def names_matching(reduced: Dict[str, Any], pattern: str,
                   opcode: str = None) -> List[str]:
    """Names of device operations that match ``pattern`` and, where the
    trace gave opcodes and one is asked for, have that opcode."""
    rx, known = re.compile(pattern), reduced.get("opcodes") or {}
    return sorted(n for n in reduced["op_seconds"] if rx.search(n)
                  and (opcode is None or known.get(n) in (None, "", opcode)))


def seconds_matching(reduced: Dict[str, Any], pattern: str,
                     opcode: str = None) -> float:
    """Device seconds (averaged over chips) of those operations."""
    return sum(reduced["op_seconds"][n]
               for n in names_matching(reduced, pattern, opcode))


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce(load_xplane(path))
