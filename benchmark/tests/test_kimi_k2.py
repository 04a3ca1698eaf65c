"""The family ``kimi_k2`` and its cell: what the manifest's self-check asks
of them, the two new rooflines against hand counts, the readers of the new
per-layer metrics on a made-up trace, and ``correct`` at a size a test can
hold: sound passes, the program's int8 control and a planted fault (the
cached rope key left unrotated) do not."""
import importlib
import json
import os

import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.rooflines import mla_decode, moe_experts, step
from benchmark.tests import tiny

CELL = "kimi-k2.6.serve-reasoning-saturated"
# Readings at the toy's size on the CPU, served_token_gap (PR 29), over 16
# finished requests (180-200 served tokens: a toy request serves a dozen
# tokens of which one or two are tied, fewer than the reference's rule wants
# for a percentile, so here the tied tokens are left out).  Seeds 3 (which
# the tests run), 4, 5, 6: sound 0.0053, 0.0127, 0.0026, 0.0035; the int8
# control 0.0411, 0.0313, 0.0335, 0.0449; the rope key left unrotated 0.539,
# 0.298, 0.395, 0.308.  The cell's limit is not set from the toy (PERF.md
# section 2 has the chip's readings).
TOY_LIMIT = {"served_token_gap": 0.02}


@pytest.fixture(scope="module")
def kimi():
    return mf.config_of(mf.load(), "kimi-k2.6")


def toy_config():
    with open(os.path.join(tiny.DATA, "tiny-kimi-k2.json")) as f:
        return json.load(f)


def _run(monkeypatch, seed=3, **kw):
    tiny.patch(monkeypatch)
    ctx = tiny.ctx(CELL, seed=seed, seconds=2.0, **kw)
    ctx["config"] = toy_config()
    ctx["cell_file"]["limits"] = TOY_LIMIT
    ctx["cell_file"]["serving"]["max_model_len"] = 512
    ctx["cell_file"]["check_sample"] = 16
    importlib.import_module("benchmark.runners.serve").run(ctx)
    return ctx["compared"]


def test_the_configuration_holds_every_published_width(kimi):
    published = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.827,
        "rope_theta": 50000, "max_position_embeddings": 262144}
    assert {k: kimi[k] for k in published} == published
    assert kimi["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert kimi["published"] == {"num_hidden_layers": 61,
                                 "n_routed_experts": 384,
                                 "vocab_size": 163840}
    program = mf.family(kimi).model_config(kimi)
    assert program["n_routed_experts"] == 384       # the router's width
    assert program["held_experts"] == [0, 12]
    assert program["model_type"] == "kimi_k2"


def test_sizes_of_the_cut(kimi):
    """ISSUE 29's arithmetic: 4.850 B parameters, 9.70 GB in bfloat16."""
    assert step.total_params(kimi) == pytest.approx(4.850e9, rel=2e-3)
    mm = step.matmul_params(kimi)
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 64 * 128 * 7168)
    expert = 3 * 7168 * 2048
    assert mm["expert"] == expert
    assert mm["layers"] == (7 * attn + 3 * 7168 * 18432
                            + 6 * (7168 * 384 + expert + 8 * 12 * expert // 384))
    assert mm["head"] == 7168 * 20480
    assert step.attention_pair_flops(kimi) == 7 * 139264
    assert mf.family(kimi).latent_bytes_per_token(kimi) == 7 * 1152


def test_mla_decode_work_counts_the_latent_once(kimi):
    steps = [{"context": 300000, "positions": 64, "attended": 300000},
             {"context": 5000, "positions": 4096, "attended": 123456}]
    flops, bytes_ = mla_decode.work(kimi, steps)
    assert bytes_ == 7 * 1152 * (300064 + 9096)
    assert flops == 7 * 139264 * (300000 + 123456)
    # 121 FLOPs a byte under 64 decode rows: bandwidth-bound on the v5e
    assert 64 * 139264 / 1152 / 64 == pytest.approx(120.9, abs=0.1)


def test_moe_experts_work_counts_hits_and_assignments(kimi):
    events = [{"assignments": 15, "hit": 9, "step": 0},
              {"assignments": 1024, "hit": 72, "step": 1}]
    flops, bytes_ = moe_experts.work(kimi, events)
    assert bytes_ == (9 + 72) * 3 * 7168 * 2048 * 2
    assert flops == (15 + 1024) * 6 * 7168 * 2048


def test_tied_tokens_count_through_their_95th_percentile(kimi):
    """The rule of ``served_token_gaps`` on made-up gaps: a tie that fell
    the other way in 2 % of the tied tokens (what sound runs read on the
    chip) leaves the reading with the untied; a fault that moves a quarter
    of them (what the int8 control does) shows; a handful of tied tokens
    has no percentile and is left out."""
    ref = mf.family(kimi)
    rng = np.random.default_rng(0)
    tied = np.arange(1000) % 5 < 2                      # 400 of 1000
    gaps = rng.uniform(0, 0.05, 1000)
    sound = gaps.copy()
    sound[np.flatnonzero(tied)[:8]] = 0.9
    out, among = ref.gaps_by_the_rule(sound, tied)
    assert among < 0.05 and out.max() < 0.05
    np.testing.assert_array_equal(out[~tied], sound[~tied])
    faulty = gaps.copy()
    faulty[np.flatnonzero(tied)[:100]] = 0.9
    assert ref.gaps_by_the_rule(faulty, tied)[0].max() == pytest.approx(0.9)
    few = np.arange(1000) < ref.MIN_TIED - 1
    out, among = ref.gaps_by_the_rule(np.where(few, 0.9, gaps), few)
    assert among == 0.0 and out.max() < 0.05


def _trace(ops, host, scopes):
    return {"ops": ops, "host": host, "scopes": scopes}


def test_readers_of_the_new_metrics_on_a_made_up_trace(kimi):
    scopes = ["jit(paged_step_w1)/layers/while/body/attn/attn_core/mla_decode",
              "jit(paged_step_w1)/layers/while/body/attn/mla_out/dot_general",
              "jit(paged_step_w1)/layers/while/body/mlp/moe_experts/while",
              "jit(paged_step_w1)/layers/while/body/mlp/moe_shared/dot",
              "jit(paged_step_w1)/layers/while/body/mlp/dense_mlp/dot",
              "jit(paged_step_w1)/sample/argmax"]
    # a cond is listed beside the products of its own body: covered once
    ops = [["mla_decode.9", 0, 4_000_000, 0],
           ["fusion.1", 4_000_000, 1_000_000, 1],
           ["cond.1", 5_000_000, 6_000_000, 2],
           ["fusion.2", 5_000_000, 2_500_000, 2],
           ["fusion.6", 7_500_000, 3_000_000, 2],
           ["fusion.3", 11_000_000, 500_000, 3],
           ["fusion.4", 11_500_000, 900_000, 4],
           ["fusion.5", 12_400_000, 700_000, 5],
           ["copy.1", 13_100_000, 300_000, -1]]
    host = [["serve_dispatch", 0, 10, 0, {"width": 1}],
            ["serve_experts", 11, 0, 0, {"assignments": 15, "hit": 50}],
            ["serve_dispatch", 20, 10, 0, {"width": 1}],
            ["serve_experts", 31, 0, 0, {"assignments": 17, "hit": 58}]]
    ctx = {"program_trace": _trace(ops, host, scopes), "config": kimi,
           "device": {"count": 1},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: mf.load_by_name("metrics", name).read(ctx)
    assert read("mla_device_ms_per_step.serve") == pytest.approx(2.5)
    assert read("moe_device_ms_per_step.serve") == pytest.approx(3.25)
    assert read("experts_hit_share.serve") == pytest.approx(
        100 * 108 / (2 * 6 * 12))
    least = 108 * 3 * 7168 * 2048 * 2 / 819e9
    assert read("moe_experts_roofline.serve") == pytest.approx(
        100 * least / 6e-3)
    # a program without the scopes and the events: nothing to read, no raise
    bare = dict(ctx, program_trace=_trace(
        [["fusion.9", 0, 5, -1]], [["serve_dispatch", 0, 10, 0, {}]], []))
    for name in ("mla_device_ms_per_step.serve", "moe_device_ms_per_step.serve",
                 "experts_hit_share.serve", "moe_experts_roofline.serve"):
        assert mf.load_by_name("metrics", name).read(bare) is None


def test_sound_serving_of_the_toy_is_correct(monkeypatch):
    compared = _run(monkeypatch)
    assert compared.correct, compared.rows


def test_the_int8_control_is_not_correct(monkeypatch):
    compared = _run(monkeypatch, control=True)
    assert not compared.correct, compared.rows


def test_an_unrotated_rope_key_is_not_correct(monkeypatch):
    """The planted fault: the one rope key all heads share goes into the
    latent cache as projected, not rotated; the queries are rotated."""
    from automodel_tpu.models import deepseek_v3

    real = deepseek_v3.apply_rope

    def key_left_unrotated(q, k, *args, **kwargs):
        return real(q, k, *args, **kwargs)[0], k

    monkeypatch.setattr(deepseek_v3, "apply_rope", key_left_unrotated)
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows
