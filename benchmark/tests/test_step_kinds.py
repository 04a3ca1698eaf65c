"""The engine step on the device by kind (``metrics/_steps.py`` and the three
readers over it): each execution of the step program joined to the
``serve_dispatch`` step annotation that launched it, on a recorded traced
run of olmo2-1b.serve-rollout-saturated (TPU v5 lite, PR 37) and on
made-up traces.  The numbers are pinned: a PR that moves them has changed
the yardstick."""
import ast
import gzip
import json
import os

import pytest

from benchmark import manifest as mf
from benchmark import trace_reduce as tr
from benchmark.metrics import _steps

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ["decode_step_device_ms.serve", "mixed_step_device_ms.serve",
           "mixed_step_device_share.serve"]
SERVING = ["olmo2-1b.serve-chat-steady", "olmo2-1b.serve-rollout-saturated",
           "kimi-k2.6.serve-reasoning-saturated",
           "brumby-14b.serve-reasoning-8k-saturated",
           "smallthinker-21b-a3b.serve-reasoning-16k-saturated"]


def _recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return json.load(f)


def _ctx(program_trace, executions, host_spans, harness_steps=None):
    """What run.py hands a reader: the window is the harness's spans, the
    busy time the union of the operations."""
    busy = tr.union([(s, s + d) for _, s, d, _ in program_trace["ops"]])
    host = [tuple(h) for h in host_spans]
    return {"program_trace": program_trace, "step_executions": executions,
            "reduced": {"host_spans": host, "busy_intervals": busy,
                        "lo_ns": min(s for _, s, _ in host),
                        "hi_ns": max(e for _, _, e in host)},
            "window": {"steps": [{}] * (harness_steps or 0)}}


def _read(ctx):
    return {n: mf.load_by_name("metrics", n).read(ctx) for n in READERS}


def test_readers_on_the_recorded_rollout(capsys):
    rec = _recorded("steps-rollout-short.json.gz")
    ctx = _ctx(rec["program_trace"], rec["executions"], rec["host_spans"],
               rec["harness_steps"])
    assert _read(ctx) == pytest.approx(PINNED, abs=1e-5)
    found = _steps.steps(ctx)
    lo, hi = _steps._window(ctx)
    inside = [x for x in rec["executions"]
              if lo <= x[1] and x[1] + x[2] <= hi]
    # every execution inside the window is one step's, widths agreeing
    assert len(found) == len(inside)
    assert [s["stats"]["width"] for s in found] == [x[0] for x in inside]
    assert len({s["step"] for s in found}) == len(found)
    assert [s["step"] for s in found] == sorted(s["step"] for s in found)
    assert abs(len(found) - rec["harness_steps"]) <= 2
    kinds = [s["kind"] for s in found]
    assert kinds.count("mixed") >= 2 and kinds.count("decode") >= 2
    assert all((s["kind"] == "mixed") == (s["stats"]["prefill_rows"] > 0)
               for s in found)
    # the matched intervals are the window's device-busy time, to 2 %
    matched = sum(s["end"] - s["start"] for s in found)
    busy = tr.length(tr.clip(ctx["reduced"]["busy_intervals"], lo, hi))
    assert matched == pytest.approx(busy, rel=0.02)
    said = capsys.readouterr().out
    assert f"{len(found)} step-program executions" in said
    assert "0 not []" in said and "in order" in said
    assert "decode / mixed" in said and "attn_core" in said
    assert "us a prefill position" in said and "longest device step" in said


PINNED = {"decode_step_device_ms.serve": 31.517333,
          "mixed_step_device_ms.serve": 80.887111,
          "mixed_step_device_share.serve": 41.174261}


def _span(step, start, width, prefill_rows, rows=4):
    return ["serve_dispatch", start, 10, 0,
            {"step_num": step, "width": width, "rows": rows,
             "positions": rows + (width - 1) * prefill_rows,
             "prefill_rows": prefill_rows}]


def _made_up(executions, dispatch=None):
    """Four steps, launched at 100, 200, 300, 400 ns; the third mixed."""
    dispatch = dispatch or [_span(0, 100, 1, 0), _span(1, 200, 1, 0),
                            _span(2, 300, 8, 1), _span(3, 400, 1, 0)]
    ops = [["fusion.1", x[1], x[2], 0] for x in executions]
    pt = {"host": dispatch, "scopes": ["jit(paged_step)/layers/attn"],
          "ops": ops}
    return _ctx(pt, executions, [["engine_step", 50, 1000]], 4)


def test_join_rules_on_made_up_traces(capsys):
    good = [[1, 150, 90, 120], [1, 240, 60, 215], [8, 300, 300, 320],
            [1, 600, 100, 410]]
    ctx = _made_up(good)
    assert [(s["step"], s["kind"]) for s in _steps.steps(ctx)] == [
        (0, "decode"), (1, "decode"), (2, "mixed"), (3, "decode")]
    assert _read(ctx) == pytest.approx({
        "decode_step_device_ms.serve": 250e-6 / 3,
        "mixed_step_device_ms.serve": None,        # under three mixed steps
        "mixed_step_device_share.serve": 100.0 * 300 / 550})
    assert _steps.mean_ms(ctx, "mixed") == pytest.approx(300e-6)
    # cut by the window's edges (one queued before the session, so with no
    # enqueue in the trace, among them), a width that disagrees, a second
    # execution after one dispatch, one enqueued before any dispatch, one
    # without its enqueue: none is a step
    bad = [[1, 20, 90, None], [1, 60, 30, None], [1, 150, 90, 120],
           [1, 240, 60, 130], [1, 300, 100, 320], [1, 400, 40, 90],
           [1, 450, 40, None], [1, 990, 100, 410]]
    capsys.readouterr()
    found = _steps.steps(_made_up(bad))
    assert [s["step"] for s in found] == [0]
    said = capsys.readouterr().out
    assert "1 step-program executions" in said and "4 not" in said
    assert "3 cut by the window's edges" in said
    assert "width 1 against the span's 8" in said
    # a parent's dispatch span: ``step``, not ``step_num``; read the same
    parent = [_span(0, 100, 1, 0)]
    parent[0][4]["step"] = parent[0][4].pop("step_num")
    assert [s["step"] for s in _steps.steps(_made_up(good[:1], parent))] == [0]


def test_readers_find_nothing_without_the_markers():
    """No dispatch span (a training trace), or no enqueue linked to the
    step program's executions: every reader returns None, none raises."""
    train = _recorded("program-train-2steps.json.gz")
    host = [["step", train["ops"][0][1], train["ops"][-1][1]]]
    assert _read(_ctx(train, [], host)) == dict.fromkeys(READERS)
    unlinked = [[1, 150, 90, None], [8, 300, 300, None]]
    assert _read(_made_up(unlinked)) == dict.fromkeys(READERS)
    # a dispatch without ``prefill_rows`` cannot be told decode or mixed
    old = [_span(0, 100, 1, 0)]
    del old[0][4]["prefill_rows"]
    assert _read(_made_up([[1, 150, 90, 120]], old)) == dict.fromkeys(
        READERS)


def test_listed_for_the_serving_cells():
    listed = {m["name"]: m for m in mf.load()["per_layer"]}
    for name in READERS:
        m = listed[name]
        assert (m["source"], m["layer"], m["workloads"]) == (
            "device_trace", "engine step", SERVING), name
    assert listed[READERS[0]]["moves"] == "itl_p95_ms"
    assert {listed[n]["moves"] for n in READERS[1:]} == {"serve_tok_s"}


def test_step_readers_import_nothing_of_the_program():
    files = [os.path.join(mf.HERE, "metrics", n + ".py")
             for n in ["_steps"] + READERS]
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.split(".")[0] == "automodel_tpu"
                           for n in names), path


def test_a_trace_without_a_chip_holds_no_step_program(tmp_path):
    """A CPU trace has no TPU plane: nothing to join, nothing raised."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.StepTraceAnnotation("automodel/serve_dispatch",
                                              step_num=0, width=1):
            jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert _steps.load_xplane(path) == []
