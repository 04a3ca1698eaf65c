"""The reduction from trace to numbers, on one recorded trace (two optimizer
steps of olmo2-1b.sft-packed-4k on a TPU v5 lite, kept in the plain form
that ``load_xplane`` gives) and on a hand-made one.  The numbers are pinned:
a PR that moves them has changed the yardstick."""
import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.rooflines import linear_ce, splash

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "trace-train-2steps.json.gz"),
                   "rt") as f:
        return tr.reduce(json.load(f))


def test_recorded_trace_reduces_to_known_numbers(recorded):
    r = recorded
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.682585164, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.682543471, abs=1e-9)
    assert r["collective_s"] == 0.0
    assert r["modules"] == {
        "jit_train_step(10163448403357496297)": 2,
        "jit_convert_element_type(15388027131515875373)": 2}
    top = r["breakdown"]["device_ops"]
    assert top[0][0] == "splash_mqa_dkv_segmented_no_residuals.14"
    assert top[0][1] == pytest.approx(0.041136868, abs=1e-9)
    assert len(top) == 10 and not any(n.startswith("while") for n, _ in top)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["train_step"] == pytest.approx(2.6423e-05, abs=1e-9)
    assert gaps["no_span"] == pytest.approx(1.527e-05, abs=1e-9)


def test_kernels_are_found_by_name(recorded):
    assert tr.seconds_matching(recorded, splash.EVENTS) == pytest.approx(
        0.079783731, abs=1e-9)
    assert tr.seconds_matching(recorded, linear_ce.EVENTS) == pytest.approx(
        0.091328257, abs=1e-9)
    assert tr.seconds_matching(recorded, r"^paged") == 0


def test_hand_made_trace():
    raw = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 5, 10], ["while.3", 20, 40], ["fusion.2", 22, 8],
                ["all-gather.1", 28, 10], ["fusion.3", 35, 20]],
        "modules": [["jit_f(1)", 5, 50]]}},
        "host": [["outer", 0, 100], ["inner", 10, 30]]}
    r = tr.reduce(raw)
    assert r["window_s"] == pytest.approx(100e-9)
    # 5-15 and 22-55: the while is a container, not work of its own
    assert r["busy_s"] == pytest.approx(43e-9)
    assert r["collective_s"] == pytest.approx(10e-9)
    # 28-38 less what compute covers (22-30 and 35-55): 30-35
    assert r["exposed_collective_s"] == pytest.approx(5e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # idle 0-5, 15-22, 55-100; "inner" is innermost from 10 to 40
    assert gaps["inner"] == pytest.approx(7e-9)
    assert gaps["outer"] == pytest.approx(50e-9)
    assert "while.3" not in r["op_seconds"]


def test_innermost_span_wins():
    spans = [("a", 0, 100), ("b", 10, 30), ("c", 15, 20), ("b", 40, 50)]
    assert tr.innermost(spans) == {
        "a": [(0, 10), (30, 40), (50, 100)],
        "b": [(10, 15), (20, 30), (40, 50)], "c": [(15, 20)]}


def test_short_name():
    assert tr.short_name("%fusion.12 = bf16[4,8]{1,0} fusion(bf16[4] %p)") \
        == "fusion.12"
    assert tr.short_name("jit_train_step(123)") == "jit_train_step(123)"


@pytest.mark.parametrize("text,code", [
    ("%closed_call.9 = bf16[64,16,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
     "custom-call(s32[64,128]{1,0:T(8,128)S(1)} %copy-done.10)",
     "custom-call"),
    ("%splash_mqa_fwd.2 = (f32[16,1024,128]{2,1,0:T(8,128)}, "
     "bf16[16,1024,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[1] %p)",
     "custom-call"),
    ("%fusion.154 = (f32[64,32]{1,0:T(8,128)S(1)}, bf16[64,32,2048]{2,1,0})"
     " fusion(bf16[16,8192,2048]{2,1,0:T(8,128)(2,1)} %gte)", "fusion"),
    ("%copy.62 = bf16[16,2560]{1,0} copy(bf16[16,2560]{1,0} %gte.507)",
     "copy"),
    ("jit_train_step(123)", "")])
def test_opcode(text, code):
    assert tr.opcode(text) == code


def test_a_kernel_is_a_custom_call_of_its_name_and_nothing_else():
    """Another operation that XLA happens to name like the kernel is not
    summed in, and more names than the programs can hold kernels silence
    the metric instead of inflating it."""
    from benchmark.metrics import _kernel
    from benchmark.rooflines import paged_decode

    ops = [["closed_call.8", 0, 10, "custom-call"],
           ["closed_call.9", 10, 30, "custom-call"],
           ["closed_call.3", 40, 50, "call"], ["fusion.1", 90, 10, "fusion"]]
    raw = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_a(1)", 0, 50], ["jit_b(2)", 50, 50]]}},
        "host": []}
    r = tr.reduce(raw)
    assert tr.names_matching(r, paged_decode.EVENTS, "custom-call") == [
        "closed_call.8", "closed_call.9"]
    assert tr.seconds_matching(r, paged_decode.EVENTS, "custom-call") \
        == pytest.approx(40e-9)
    cfg = {"num_hidden_layers": 1, "num_key_value_heads": 1, "head_dim": 128,
           "num_attention_heads": 1, "hidden_size": 128, "model_type": "olmo2"}
    ctx = {"reduced": r, "config": cfg, "device": {"count": 1},
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    steps = [{"context": 10, "positions": 1, "attended": 10}]
    assert _kernel.roofline_share(ctx, paged_decode, steps) > 0
    # a third kernel of that name in two programs: nothing sound to read
    raw["devices"]["/device:TPU:0"]["ops"] = ops + [
        ["closed_call.10", 100, 5, "custom-call"]]
    ctx["reduced"] = tr.reduce(raw)
    assert _kernel.roofline_share(ctx, paged_decode, steps) is None
    # and none at all: left out, never 0
    raw["devices"]["/device:TPU:0"]["ops"] = [["fusion.1", 90, 10, "fusion"]]
    ctx["reduced"] = tr.reduce(raw)
    assert _kernel.roofline_share(ctx, paged_decode, steps) is None
