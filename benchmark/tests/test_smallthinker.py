"""The family ``smallthinker`` and its cell: what the manifest's self-check
asks of them, the configuration against the catalog row and the sizes of
the cut, the two new work functions against hand counts, the readers of
the new per-layer metrics on a made-up trace, and ``correct`` at a size a
test can hold: sound passes, and three planted faults do not (a window
layer that attends everything, RoPE applied on a full layer, the router
fed the post-attention stream)."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf, weights
from benchmark.rooflines import hybrid_paged_decode, routed_experts, step
from benchmark.tests import tiny

CELL = "smallthinker-21b-a3b.serve-reasoning-16k-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Readings at the toy's size on the CPU, served_token_gap (PR 35), over 16
# finished requests of 4-24 served tokens, ``assumed.qk_gain`` 6 (so that a
# toy's attention is as peaked as the published widths') and
# ``assumed.tie`` 0.01 (the cell's band, 0.03, would call nearly every token
# of a toy tied).  A toy request has
# too few tied tokens for the reference's percentile: they are left out, and
# the untied are compared.  Seeds 3 (which the tests run), 4, 5, 6: sound
# 0.0015, 0.0018, 0.0014, 0; a window layer that attends everything 0.66,
# 0.66, 0.81, 0.90; RoPE on the full layers 0.21, 0.14, 0.14, 0.089; the
# router fed the post-attention stream 0.023, 0.014, 0.018, 0.012; the int8
# control 0.0032, 0.015, 0.011, 0.0029 (a toy's logits are small: its lowest
# seeds overlap the sound ones, as the other toys' do, so no test holds it).
# The cell's limit is not set from the toy (PERF.md section 2 has the
# chip's readings).
TOY_LIMIT = {"served_token_gap": 0.005}


@pytest.fixture(scope="module")
def small():
    return mf.config_of(mf.load(), "smallthinker-21b-a3b")


def toy_config():
    with open(os.path.join(tiny.DATA, "tiny-smallthinker.json")) as f:
        return json.load(f)


def _run(monkeypatch, seed=3, sample=16, seconds=2.0, **kw):
    tiny.patch(monkeypatch)
    ctx = tiny.ctx(CELL, seed=seed, seconds=seconds, **kw)
    ctx["config"] = toy_config()
    ctx["cell_file"]["limits"] = TOY_LIMIT
    # blocks of 4 under a window of 16: a toy request of 12-144 tokens
    # passes the window by many blocks, and its prompt crosses the edge in
    # chunks of 8
    ctx["cell_file"]["serving"].update(max_model_len=512, kv_block_size=4,
                                       num_kv_blocks=256)
    ctx["cell_file"]["check_sample"] = sample
    importlib.import_module("benchmark.runners.serve").run(ctx)
    return ctx["compared"]


def test_the_configuration_is_the_catalog_row_key_for_key(small):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert small["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if small.get(k, "-") != v}
    assert differs == set(small["reduced"]) == {
        "num_hidden_layers", "rope_layout", "sliding_window_layout"}
    assert small["published"] == {k: row["config"][k] for k in differs}
    assert small["num_hidden_layers"] == 8
    for key in ("rope_layout", "sliding_window_layout"):
        assert small[key] == row["config"][key][:8] == [0, 1, 1, 1] * 2
    dep = small["deployment"]
    assert (dep["stages"], dep["periods_per_stage"],
            dep["chips_per_layer"]) == (6, [2, 2, 2, 2, 2, 3], 1)
    assert sum(dep["periods_per_stage"]) * 4 == 52
    for key in ("attention", "rope", "window", "router_input", "routing",
                "experts", "parameter_names", "not_in_this_config", "init"):
        assert key in small["assumed"], key
    program = mf.family(small).model_config(small)
    assert program["model_type"] == "smallthinker"
    assert "reduced" not in program and "assumed" not in program


def test_the_configuration_holds_every_published_width(small):
    published = {
        "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
        "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
        "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6,
        "vocab_size": 151936, "max_position_embeddings": 16384,
        "sliding_window_size": 4096, "rope_theta": 1500000,
        "tie_word_embeddings": False}
    assert {k: small[k] for k in published} == published


def test_sizes_of_the_cut(small):
    """ISSUE 35's arithmetic: attention 20,971,520, router 163,840, two
    norms 5,120, one expert 5,898,240 and 64 of them 377,487,360: a layer
    is 398,627,840; 8 layers + embedding + head + final norm =
    3,966,937,600 parameters = 7.93 GB in bfloat16."""
    ref = mf.family(small)
    attn = 2560 * (3584 + 512 + 512) + 3584 * 2560
    assert attn == 20_971_520 and ref.expert_params(small) == 5_898_240
    layer = attn + 163_840 + 5_120 + 64 * 5_898_240
    assert layer == 398_627_840
    total = step.total_params(small)
    assert total == 8 * layer + 2 * 388_956_160 + 2560 == 3_966_937_600
    assert round(2 * total / 1e9, 2) == 7.93
    mm = step.matmul_params(small)
    assert mm["expert"] == 5_898_240
    assert mm["layer"] == attn + 163_840 + 6 * 5_898_240     # 6 ACTIVE experts
    assert mm["layers"] == 8 * mm["layer"] and mm["head"] == 388_956_160
    assert step.attention_pair_flops(small) == 4 * 28 * 128 * 8
    assert ref.kv_bytes_per_token_layer(small) == 2048
    z = ref.sizes(small)
    assert (z["n_full"], z["n_window"], z["n_moe"], z["held"], z["W"]) == (
        2, 6, 8, 64, 4096)


def test_the_cell_is_what_the_issue_asked_for():
    man = mf.load()
    cell = mf.cell_of(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "reasoning-16k-saturated", 1)
    t = mf.read_json("traffic", "reasoning-16k-saturated.json")
    assert (t["kind"], t["clients"], t["rounds"]) == ("closed_loop", 48, 4)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.6, "min": 32, "max": 512}
    assert t["output_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.3, "min": 4096, "max": 15872}
    assert t["prompt_len"]["max"] + t["output_len"]["max"] == 16384
    c = mf.read_json("workloads", CELL + ".json")
    s = c["serving"]
    assert (s["max_num_seqs"], s["max_model_len"], s["prefix_caching"],
            s["speculative"]) == (48, 16384, "off", "off")
    assert s["prefill_chunk"] in (32, 64)
    assert set(s["num_kv_blocks"]) == {"full", "window"}
    # the window group can hold every row at the window's span: it never
    # preempts; the full group decides
    span = -(-(4096 + s["prefill_chunk"] - 2) // s["kv_block_size"]) + 1
    assert s["num_kv_blocks"]["window"] >= 48 * span + 1
    assert c["expected_rungs"] == ["attention.paged_decode"]
    assert c["forbidden_rungs"] == ["attention.paged_gather"]
    assert c["check_sample"] == 4
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["serve_tok_s", "itl_p95_ms", "setup_s"]
    per = [m["name"] for m in mf.metrics_of(man, "per_layer", CELL)]
    assert per == ["batch_occupancy.serve", "host_ms_per_step.serve",
                   "mfu.serve", "device_idle_share.serve",
                   "moe_device_ms_per_step.serve", "experts_hit_share.serve",
                   "hybrid_paged_decode_roofline.serve",
                   "window_attn_device_ms_per_step.serve",
                   "full_attn_device_ms_per_step.serve",
                   "window_keys_share.serve",
                   "routed_experts_roofline.serve"]
    assert mf.self_check(man) == []


def test_hybrid_paged_decode_work_counts_what_each_kind_must_read(small):
    events = [{"rows": 48, "positions": 48, "full_keys": 232_800,
               "window_keys": 153_600},
              {"rows": 3, "positions": 3 + 31, "full_keys": 9_000,
               "window_keys": 8_192}]
    read = 2 * (232_800 + 9_000) + 6 * (153_600 + 8_192)
    assert hybrid_paged_decode.keys_read(small, events) == read
    flops, bytes_ = hybrid_paged_decode.work(small, events)
    assert bytes_ == 2048 * (read + 8 * (48 + 34))
    assert flops == 4 * 28 * 128 * read
    # a decode step of 48 rows at the cell's mix: 0.95 GB for the 2 full
    # layers, 1.89 GB for the 6 window layers; 7 FLOPs a byte: bandwidth
    _, one = hybrid_paged_decode.work(small, events[:1])
    assert round(2048 * 2 * 232_800 / 1e9, 2) == 0.95
    assert round(2048 * 6 * 153_600 / 1e9, 2) == 1.89
    assert round(one / 1e9, 2) == 2.84
    assert flops / bytes_ == pytest.approx(7.0, abs=0.01)


def test_routed_experts_work_takes_the_size_from_the_family(small):
    events = [{"assignments": 48 * 6 * 8, "hit": 507, "step": 0},
              {"assignments": 1200, "hit": 512, "step": 1}]
    flops, bytes_ = routed_experts.work(small, events)
    assert bytes_ == (507 + 512) * 5_898_240 * 2
    assert flops == (2304 + 1200) * 2 * 5_898_240
    # 507 reads of 11.8 MB: 5.98 GB a step, 7.3 ms at 819 GB/s
    assert round(507 * 5_898_240 * 2 / 1e9, 2) == 5.98
    # the same arithmetic as the older reader's on the family it serves
    from benchmark.rooflines import moe_experts

    kimi = mf.config_of(mf.load(), "kimi-k2.6")
    assert routed_experts.work(kimi, events) == moe_experts.work(kimi, events)


def test_tied_tokens_count_through_their_95th_percentile(small):
    ref = mf.family(small)
    rng = np.random.default_rng(0)
    tied = np.arange(1000) % 5 < 4                      # 800 of 1000
    gaps = rng.uniform(0, 0.05, 1000)
    sound = gaps.copy()
    sound[np.flatnonzero(tied)[:16]] = 0.9
    out, among = ref.gaps_by_the_rule(sound, tied)
    assert among < 0.05 and out.max() < 0.05
    np.testing.assert_array_equal(out[~tied], sound[~tied])
    faulty = gaps.copy()
    faulty[np.flatnonzero(tied)[:200]] = 0.9
    assert ref.gaps_by_the_rule(faulty, tied)[0].max() == pytest.approx(0.9)
    few = np.arange(1000) < 10
    assert ref.gaps_by_the_rule(faulty, few)[1] == 0.0


def test_layer_0_ties_by_token_id_and_deeper_layers_by_position():
    """Why the program's router reads the norm's float32 result: layer 0's
    margins are a function of the token id alone, so a token that ties
    there ties at every position that holds it (one such token took a
    request of seed 38214120 to 0.148 on the chip); a deeper layer's differ
    from position to position."""
    toy = toy_config()
    ref = mf.family(toy)
    flat = jax.jit(lambda w: ref.make(toy, w))(weights.seed_words(5))
    ids = jnp.asarray([3, 9, 3, 3, 27, 9, 3, 41] * 4, jnp.int32)
    _, margins = ref.hidden_states(flat, toy, ids, by_layer=True)
    margins = np.asarray(margins)
    same = np.flatnonzero(np.asarray(ids) == 3)
    np.testing.assert_allclose(margins[0, same], margins[0, same[0]],
                               rtol=1e-5)
    assert np.ptp(margins[1:, same], axis=1).min() > 1e-3 * margins[0, same[0]]


def _trace(ops, host, scopes):
    return {"ops": ops, "host": host, "scopes": scopes}


def test_readers_of_the_new_metrics_on_a_made_up_trace(small):
    body = "jit(paged_step_w1)/layers/while/body/"
    scopes = [body + "attn/attn_full/attn_core/paged_decode/paged_decode_full",
              body + "attn/attn_full/dot_general",
              body + "attn/attn_window/attn_core/paged_decode/"
              "paged_decode_window",
              body + "attn/attn_window/kv_write/scatter",
              body + "mlp/moe_experts/while/body/cond/dot_general",
              body + "mlp/moe_router/top_k",
              "jit(paged_step_w1)/sample/argmax"]
    ops = [["paged_decode_full.3", 0, 2_000_000, 0],
           ["fusion.1", 2_000_000, 1_000_000, 1],
           ["paged_decode_window.4", 3_000_000, 5_000_000, 2],
           ["fusion.2", 8_000_000, 1_000_000, 3],
           ["fusion.3", 9_000_000, 16_000_000, 4],
           ["fusion.4", 25_000_000, 1_000_000, 5],
           ["fusion.5", 26_000_000, 2_000_000, 6]]
    kv = {"rows": 48, "positions": 48, "full_keys": 232_800,
          "window_keys": 153_600}
    ex = {"assignments": 2304, "hit": 507}
    host = [["serve_dispatch", 0, 10, 0, {"width": 1}],
            ["serve_kv_read", 11, 0, 0, dict(kv, step=0)],
            ["serve_experts", 12, 0, 0, dict(ex, step=0)],
            ["serve_dispatch", 20, 10, 0, {"width": 1}],
            ["serve_kv_read", 31, 0, 0, dict(kv, step=1)],
            ["serve_experts", 32, 0, 0, dict(ex, step=1)]]
    # one full and three window calls a program (a period is the scan's
    # body), two programs
    kernels = {"paged_decode_full.3": 0.003, "paged_decode_full.9": 0.001,
               "paged_decode_window.4": 0.003, "paged_decode_window.5": 0.003,
               "paged_decode_window.6": 0.003, "paged_decode_window.10": 0.001}
    reduced = {"modules": {"jit_paged_step_w1(7)": 2,
                           "jit_paged_step_w64(9)": 1},
               "op_seconds": dict(kernels, **{"fusion.7": 1.0}),
               "opcodes": {k: "custom-call" for k in kernels}}
    ctx = {"program_trace": _trace(ops, host, scopes), "config": small,
           "reduced": reduced, "device": {"count": 1},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name, c=ctx: mf.load_by_name("metrics", name).read(c)
    assert read("full_attn_device_ms_per_step.serve") == pytest.approx(1.5)
    assert read("window_attn_device_ms_per_step.serve") == pytest.approx(3.0)
    assert read("window_keys_share.serve") == pytest.approx(
        100 * 153_600 / 232_800)
    least = 2 * 2048 * (2 * 232_800 + 6 * 153_600 + 8 * 48) / 819e9
    assert read("hybrid_paged_decode_roofline.serve") == pytest.approx(
        100 * least / 0.014)
    least = 2 * 507 * 5_898_240 * 2 / 819e9
    assert read("routed_experts_roofline.serve") == pytest.approx(
        100 * least / 0.016)
    # a program without the scopes, the events or the kernels' names (the
    # parent's, any other family's): nothing to read, and no raise
    bare = dict(ctx, program_trace=_trace(
        [["fusion.9", 0, 5, -1]], [["serve_dispatch", 0, 10, 0, {}]], []))
    for name in ("full_attn_device_ms_per_step.serve",
                 "window_attn_device_ms_per_step.serve",
                 "window_keys_share.serve",
                 "hybrid_paged_decode_roofline.serve",
                 "routed_experts_roofline.serve"):
        assert read(name, bare) is None, name
    unnamed = dict(ctx, reduced=dict(reduced, op_seconds={"fusion.7": 1.0}))
    assert read("hybrid_paged_decode_roofline.serve", unnamed) is None
    # more names than two programs of four kernels can hold: left out
    crowded = dict(reduced, op_seconds=dict(
        reduced["op_seconds"], **{f"paged_decode_window.{i}": 0.001
                                  for i in (20, 21, 22)}))
    assert read("hybrid_paged_decode_roofline.serve",
                dict(ctx, reduced=crowded)) is None


def test_sound_serving_of_the_toy_is_correct(monkeypatch):
    compared = _run(monkeypatch)
    assert compared.correct, compared.rows


def test_a_window_layer_that_attends_everything_is_not_correct(monkeypatch):
    """The planted fault: the window never reaches the attention core."""
    from automodel_tpu.models.smallthinker import SmallThinkerForCausalLM

    real = SmallThinkerForCausalLM._attention_core
    monkeypatch.setattr(
        SmallThinkerForCausalLM, "_attention_core",
        lambda self, *a, local_window_size=None: real(self, *a))
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows


def test_rope_on_a_full_layer_is_not_correct(monkeypatch):
    """The planted fault: every layer rotates, the NoPE ones too."""
    from automodel_tpu.models.smallthinker import SmallThinkerConfig

    real = SmallThinkerConfig.layer_kinds
    monkeypatch.setattr(
        SmallThinkerConfig, "layer_kinds",
        lambda self: tuple((True, w) for _, w in real(self)))
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows


def test_the_router_fed_the_post_attention_stream_is_not_correct(monkeypatch):
    """The planted fault: the router reads ``m``, as every other family's
    does, and not ``u``."""
    from automodel_tpu.models.smallthinker import SmallThinkerForCausalLM

    real = SmallThinkerForCausalLM._experts_block
    monkeypatch.setattr(
        SmallThinkerForCausalLM, "_experts_block",
        lambda self, u, m, *a: real(self, m, m, *a))
    # the mildest of the three faults at a toy's size (0.012-0.023 against
    # a limit of 0.005): which requests finish in the window is the CPU's
    # speed to decide, so a longer window and a wider sample than the others
    compared = _run(monkeypatch, sample=96, seconds=5.0)
    assert not compared.correct, compared.rows
