"""The family ``laguna`` and its cell: what the manifest's self-check asks of
them, the configuration against the catalog row and the sizes of the cut,
the new work function against hand counts, the readers of the new
per-layer metrics on made-up traces, and ``correct`` at a size a test can
hold: sound passes, and three planted faults do not (the per-head gate
left out, a window layer that attends everything, YaRN turning every
channel of a full layer's head)."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import trace_reduce as tr
from benchmark.rooflines import chunked_paged_attention, routed_experts, step
from benchmark.tests import tiny

CELL = "laguna-s-2.1.serve-agent-8k-saturated"
# the catalog of public architectures (the model-configs guide's
# ``architectures.jsonl``), where the machine that runs the tests has one
CATALOG = os.environ.get("MODEL_CONFIGS_CATALOG", "")
LISTS = ("layer_types", "mlp_layer_types", "gating_types",
         "num_attention_heads_per_layer")
# Readings at the toy's size on the CPU, served_token_gap, over 16 finished
# requests of 4-24 served tokens, ``assumed.qk_gain`` 6 (so that a toy's
# attention is as peaked as the published widths') and ``assumed.tie`` 0.01
# (the cell's band, 0.03, would call nearly every token of a toy tied).
# Seeds 3 (which the tests run), 5, 7: sound 0, 0.0026-0.0052 (which
# requests finish in the window is the CPU's speed to decide), 0; the gate
# left out 0.47, 0.40, 0.37; a window layer that attends everything 0.81,
# 0.62, 0.64; YaRN over the whole head 0.28, 0.36, 0.25.  The limit lies
# between, with room on both sides.  The cell's limit is not set from the
# toy (PERF.md section 2 has the chip's readings).
TOY_LIMIT = {"served_token_gap": 0.02}


@pytest.fixture(scope="module")
def laguna():
    return mf.config_of(mf.load(), "laguna-s-2.1")


def toy_config():
    with open(os.path.join(tiny.DATA, "tiny-laguna.json")) as f:
        return json.load(f)


def _run(monkeypatch, seed=3, sample=16, seconds=2.0):
    tiny.patch(monkeypatch)
    ctx = tiny.ctx(CELL, seed=seed, seconds=seconds)
    ctx["config"] = toy_config()
    ctx["cell_file"]["limits"] = TOY_LIMIT
    # blocks of 4 under a window of 8: a toy request of 12-144 tokens passes
    # the window by many blocks, and its prompt crosses the edge in chunks
    ctx["cell_file"]["serving"].update(max_model_len=512, kv_block_size=4,
                                       num_kv_blocks=256)
    ctx["cell_file"]["check_sample"] = sample
    importlib.import_module("benchmark.runners.serve").run(ctx)
    return ctx["compared"]


def test_the_configuration_is_the_catalog_row_key_for_key(laguna):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog named by MODEL_CONFIGS_CATALOG")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert laguna["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if laguna.get(k, "-") != v}
    assert differs == set(laguna["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", *LISTS}
    assert laguna["published"] == {k: row["config"][k] for k in differs}
    assert (laguna["num_hidden_layers"], laguna["num_experts"],
            laguna["vocab_size"]) == (5, 128, 50176)
    for key in LISTS:
        assert laguna[key] == row["config"][key][:5]
    assert laguna["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert laguna["held_experts"] == [0, 128]
    assert laguna["deployment"]["chips_per_layer"] == 2
    for key in ("gate", "router", "shared_expert", "activations", "qk_norm",
                "rope", "window", "parameter_names", "init"):
        assert key in laguna["assumed"], key
    program = mf.family(laguna).model_config(laguna)
    assert (program["model_type"], program["num_experts"],
            program["held_experts"]) == ("laguna", 256, [0, 128])
    assert "reduced" not in program and "assumed" not in program


def test_the_configuration_holds_every_published_width(laguna):
    published = {
        "hidden_size": 3072, "head_dim": 128, "num_attention_heads": 48,
        "num_key_value_heads": 8, "intermediate_size": 12288,
        "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
        "num_experts_per_tok": 10, "sliding_window": 512,
        "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False}
    assert {k: laguna[k] for k in published} == published
    z = mf.family(laguna).sizes(laguna)
    assert (z["Hq_full"], z["Hq_window"], z["E"], z["held"], z["k"]) == (
        48, 72, 256, 128, 10)


def test_sizes_of_the_cut(laguna):
    """The deployment's arithmetic: attention 44,187,648 full and 63,135,744
    window, one expert 9,437,184, router 786,432; dense layer 0
    157,433,856, a window expert layer 1,281,318,912, the full one
    1,262,370,816, embedding + head 308,281,344: 5,572,042,752 matrix
    parameters = 11.14 GB in bfloat16, the norms 35,072 more."""
    ref = mf.family(laguna)
    full, window = (ref.attention_params(laguna, k) for k in ("full",
                                                              "window"))
    assert (full, window) == (44_187_648, 63_135_744)
    expert = ref.expert_params(laguna)
    assert expert == 9_437_184
    routed = 786_432 + expert + 128 * expert
    assert full + 3 * 3072 * 12288 == 157_433_856
    assert window + routed == 1_281_318_912
    assert full + routed == 1_262_370_816
    shapes = ref.leaf_shapes(laguna)
    matrices = sum(int(np.prod(s)) for s, kind in shapes.values()
                   if kind == "matrix")
    assert matrices == (157_433_856 + 3 * 1_281_318_912 + 1_262_370_816
                        + 308_281_344) == 5_572_042_752
    assert step.total_params(laguna) == matrices + 35_072
    assert round(2 * matrices / 1e9, 2) == 11.14
    mm = step.matmul_params(laguna)
    # a position runs 10 of 256 experts, 128 of them held here: 5 on average
    assert mm["layers"] == (2 * full + 3 * window + 3 * 3072 * 12288
                            + 4 * (786_432 + expert + 10 * 128 * expert
                                   // 256))
    assert mm["head"] == 3072 * 50176 and mm["expert"] == expert
    # pairs are credited to the two full layers only (the reference says why)
    assert step.attention_pair_flops(laguna) == 4 * 48 * 128 * 2
    assert ref.kv_bytes_per_token_layer(laguna) == 4096
    z = ref.sizes(laguna)
    assert (z["kd"], z["n_full"], z["n_window"], z["routed_full"],
            z["routed_window"], z["W"]) == (1, 2, 3, 1, 3, 512)


def test_the_cell_is_what_the_issue_asked_for():
    man = mf.load()
    cell = mf.cell_of(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", "agent-8k-saturated", 1)
    t = mf.read_json("traffic", "agent-8k-saturated.json")
    assert (t["kind"], t["schedule_seed"], t["clients"], t["rounds"]) == (
        "closed_loop", 2041, 24, 8)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 7168,
                               "sigma": 0.6, "min": 1024, "max": 28672}
    assert t["output_len"] == {"dist": "lognormal", "median": 150,
                               "sigma": 0.6, "min": 32, "max": 1024}
    c = mf.read_json("workloads", CELL + ".json")
    s = c["serving"]
    assert (s["max_num_seqs"], s["max_model_len"], s["kv_block_size"],
            s["prefill_chunk"], s["prefix_caching"], s["speculative"]) == (
        24, 32768, 128, 128, "off", "off")
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 32768
    # the window group holds every row at the widest step's span: it never
    # preempts; the full group decides
    span = -(-(512 + s["prefill_chunk"] - 2) // s["kv_block_size"]) + 1
    assert s["num_kv_blocks"]["window"] >= 24 * span + 1
    assert c["expected_rungs"] == ["attention.paged_decode"]
    assert c["forbidden_rungs"] == ["attention.paged_gather"]
    assert c["limits"] == {"served_token_gap": 0.05}
    e2e = [m["name"] for m in mf.metrics_of(man, "end_to_end", CELL)]
    assert e2e == ["serve_tok_s", "itl_p95_ms", "setup_s"]
    per = [m["name"] for m in mf.metrics_of(man, "per_layer", CELL)]
    assert per == ["batch_occupancy.serve", "host_ms_per_step.serve",
                   "mfu.serve", "device_idle_share.serve",
                   "schedule_ms_per_step.serve",
                   "assemble_ms_per_step.serve",
                   "finish_ms_per_step.serve",
                   "device_roundtrip_ms_per_step.serve",
                   "sample_device_ms_per_step.serve",
                   "step_fill.serve", "moe_device_ms_per_step.serve",
                   "experts_hit_share.serve",
                   "window_attn_device_ms_per_step.serve",
                   "full_attn_device_ms_per_step.serve",
                   "window_keys_share.serve",
                   "routed_experts_roofline.serve",
                   "mixed_step_device_ms.serve",
                   "mixed_step_device_share.serve",
                   "chunked_paged_roofline.serve",
                   "mixed_experts_roofline.serve"]
    assert mf.self_check(man) == []


def test_chunked_paged_work_counts_pairs_by_kind(laguna):
    """A mixed step: row A decodes at context 9,000 (one query), row B
    prefills positions 1,000-1,127 (128 queries); the window is 512."""
    full_pairs = 9_000 + (1_001 + 1_128) * 128 // 2
    window_pairs = 512 + 512 * 128
    events = [{"positions": 129, "full_keys": 9_000 + 1_128,
               "window_keys": 512 + 512, "full_pairs": full_pairs,
               "window_pairs": window_pairs}]
    flops, bytes_ = chunked_paged_attention.work(laguna, events)
    assert flops == 4 * 128 * (48 * 2 * full_pairs + 72 * 3 * window_pairs)
    assert bytes_ == 4096 * (2 * 10_128 + 3 * 1_024 + 5 * 129)


def _span(step, start, width, prefill_rows, rows=2):
    return ["serve_dispatch", start, 10, 0,
            {"step_num": step, "width": width, "rows": rows,
             "positions": rows + (width - 1) * prefill_rows,
             "prefill_rows": prefill_rows}]


def test_readers_of_the_new_metrics_on_a_made_up_trace(laguna):
    """Three steps (a decode step, then two that carry a chunk), their
    executions on the device, the operations inside them by scope and the
    program's events."""
    ms = 1_000_000
    scopes = ["jit(paged_step_w8)/layers/while/body/mlp/moe_experts/while",
              "jit(paged_step_w8)/layers/while/body/attn/attn_window/"
              "attn_core/paged_decode/paged_decode_window",
              "jit(paged_step_w8)/layers/while/body/attn/attn_full/"
              "attn_core/paged_decode/paged_decode_full"]
    executions = [[1, 10 * ms, 20 * ms, 5 * ms],
                  [8, 40 * ms, 100 * ms, 35 * ms],
                  [8, 150 * ms, 100 * ms, 145 * ms]]
    ops = [["fusion.1", 12 * ms, 5 * ms, 0],                # decode experts
           ["fusion.1", 45 * ms, 30 * ms, 0],               # mixed experts
           ["paged_decode_window.6", 80 * ms, 10 * ms, 1],
           ["paged_decode_full.4", 95 * ms, 20 * ms, 2],
           ["fusion.1", 160 * ms, 40 * ms, 0],
           ["fusion.1", 195 * ms, 10 * ms, 0]]              # overlaps: covered
    kv = lambda s: {"step": s, "rows": 2, "positions": 9, "full_keys": 9_000,
                    "window_keys": 1_024, "full_pairs": 50_000,
                    "window_pairs": 4_000}
    host = [_span(0, 0, 1, 0), ["serve_kv_read", 1, 0, 0, kv(0)],
            _span(1, 30 * ms, 8, 1), ["serve_kv_read", 31 * ms, 0, 0, kv(1)],
            _span(2, 140 * ms, 8, 1),
            ["serve_kv_read", 141 * ms, 0, 0, kv(2)],
            ["serve_experts", 30 * ms, 0, 0,
             {"step": 0, "assignments": 10, "hit": 10}],
            ["serve_experts", 140 * ms, 0, 0,
             {"step": 1, "assignments": 1_200, "hit": 500}],
            ["serve_experts", 250 * ms, 0, 0,
             {"step": 2, "assignments": 1_000, "hit": 400}]]
    pt = {"host": host, "scopes": scopes, "ops": ops}
    kernels = {"paged_decode_window.6": 0.010, "paged_decode_full.4": 0.020}
    ctx = {"program_trace": pt, "step_executions": executions,
           "config": laguna, "device": {"count": 1},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "reduced": {"host_spans": [("engine_step", 0, 300 * ms)],
                       "busy_intervals": tr.union(
                           [(s, s + d) for _, s, d, _ in ops]),
                       "lo_ns": 0, "hi_ns": 300 * ms,
                       "modules": {"jit_paged_step_w8(3)": 2},
                       "op_seconds": dict(kernels, **{"fusion.1": 0.085}),
                       "opcodes": {k: "custom-call" for k in kernels}},
           "window": {"steps": [{}] * 3}}
    read = lambda name, c=ctx: mf.load_by_name("metrics", name).read(c)
    # the two mixed steps' events over their 30 + 45 ms under moe_experts
    flops, bytes_ = routed_experts.work(laguna, [{"assignments": 2_200,
                                                  "hit": 900}])
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read("mixed_experts_roofline.serve") == pytest.approx(
        100 * least / 0.075)
    flops, bytes_ = chunked_paged_attention.work(laguna, [kv(s)
                                                          for s in range(3)])
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read("chunked_paged_roofline.serve") == pytest.approx(
        100 * least / 0.030)
    # a program without the pair counts or the step split (the parent's,
    # any other family's): nothing to read, and no raise
    plain = [h if h[0] != "serve_kv_read" else
             [*h[:4], {k: v for k, v in h[4].items() if "pairs" not in k}]
             for h in host]
    fresh = lambda **kw: dict({k: v for k, v in ctx.items()
                               if k != "engine_steps"}, **kw)
    bare = fresh(program_trace=dict(pt, host=plain))
    assert read("chunked_paged_roofline.serve", bare) is None
    decode_only = [h for h in host if h[0] != "serve_dispatch"] + [
        _span(0, 0, 1, 0), _span(1, 30 * ms, 8, 0), _span(2, 140 * ms, 8, 0)]
    assert read("mixed_experts_roofline.serve", fresh(
        program_trace=dict(pt, host=decode_only))) is None
    assert read("mixed_experts_roofline.serve", fresh(
        program_trace={"host": [], "scopes": [], "ops": []},
        step_executions=[])) is None


def test_tied_tokens_count_through_their_percentile_and_flipped_share(
        laguna):
    """300 tied tokens of 400.  Sound: 5 % of the tied flipped by round-off
    (0.12, as a flip of one expert moves the logits), the rest under 0.01.
    A fault in a few tokens past round-off's share shows in the 90th
    percentile; a fault that moves a quarter of them by 0.03, under the
    limit each, only in the share over ``FLIP_GAP``, and then the widest
    tied gap stands for them all."""
    ref = mf.family(laguna)
    rng = np.random.default_rng(0)
    tied = np.arange(400) % 4 < 3
    at = np.flatnonzero(tied)
    sound = rng.uniform(0, 0.01, 400)
    sound[at[::20]] = 0.12
    out, among, flipped = ref.gaps_by_the_rule(sound, tied)
    assert flipped == pytest.approx(0.05)
    assert among < 0.01 and out.max() < 0.05
    few = sound.copy()
    few[at[1::20]] = few[at[2::20]] = 0.3             # 10 % more, widely
    out, among, flipped = ref.gaps_by_the_rule(few, tied)
    assert flipped == pytest.approx(0.15) and out.max() > 0.05
    bulk = sound.copy()
    bulk[at[3::4]] = 0.03                             # 25 % more, slightly
    out, among, flipped = ref.gaps_by_the_rule(bulk, tied)
    assert np.quantile(bulk[tied], ref.TIED_QUANTILE) < 0.05
    assert flipped > ref.FLIP_SHARE and among == pytest.approx(0.12)
    assert out.max() == pytest.approx(0.12)
    # fewer than MIN_TIED: left out, whatever they read
    some = np.arange(400) < ref.MIN_TIED - 1
    out, among, flipped = ref.gaps_by_the_rule(np.where(some, 0.9, 0.0),
                                               some)
    assert out.max() == among == 0.0 and flipped == 1.0


def test_eight_rounds_never_run_dry():
    """A client's row advances one chunk of its prompt or one token a step,
    so its 8 requests last at least as many steps as they hold chunks and
    tokens.  The fewest any client holds, 1,291 steps, is past the ~725
    steps a run makes (ramp and window over a step of ~112 ms: PERF.md
    section 4), and past what 100 ms steps would make."""
    from benchmark.traffic import closed_loop

    t = mf.read_json("traffic", "agent-8k-saturated.json")
    chunk = mf.read_json("workloads", CELL + ".json")["serving"][
        "prefill_chunk"]
    least = min(sum(-(-len(r["prompt"]) // chunk) + r["max_new_tokens"]
                    for r in client)
                for client in closed_loop.generate(t, 2**31 + 5, 50_176))
    assert least == 1_291
    seconds = mf.load()["run_seconds"]
    assert least > (t["ramp_s"] + seconds) / 0.100


def test_held_margin_looks_at_held_experts_only(laguna):
    """The boundary is midway between the 2nd and 3rd logit; only the
    held experts' distances from it count."""
    ref = mf.family(laguna)
    r = jnp.asarray([[4.0, 3.0, 2.9, 1.0, 0.0, -1.0]])
    rms = float(jnp.sqrt(jnp.mean(r * r)))
    assert float(ref.held_margin(r, 2, 0, 3)[0]) == pytest.approx(
        0.05 / rms, rel=1e-5)
    assert float(ref.held_margin(r, 2, 3, 3)[0]) == pytest.approx(
        1.95 / rms, rel=1e-5)


def test_sound_serving_of_the_toy_is_correct(monkeypatch):
    compared = _run(monkeypatch)
    assert compared.correct, compared.rows


def test_the_gate_left_out_is_not_correct(monkeypatch):
    """The planted fault: the attention output reaches ``o_proj`` ungated."""
    from automodel_tpu.models.laguna import LagunaConfig

    from automodel_tpu.models.laguna import LagunaForCausalLM

    monkeypatch.setattr(LagunaConfig, "gated", property(lambda self: False))

    init = LagunaForCausalLM._attn_params

    def attn_params(self, dense, n, hq):
        out = init(self, dense, n, hq)
        out["attn_gate"] = {"kernel": dense((self.config.hidden_size, hq),
                                            n)}
        return out

    # the tree keeps its gate kernels (the benchmark makes them); the
    # forward no longer reads them
    monkeypatch.setattr(LagunaForCausalLM, "_attn_params", attn_params)
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows


def test_a_window_layer_that_attends_everything_is_not_correct(monkeypatch):
    from automodel_tpu.models.laguna import LagunaForCausalLM

    real = LagunaForCausalLM._attention_core
    monkeypatch.setattr(
        LagunaForCausalLM, "_attention_core",
        lambda self, *a, local_window_size=None: real(self, *a))
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows


def test_a_lost_write_at_a_block_edge_is_not_correct(monkeypatch):
    """The planted fault: in the window group, the k/v of a block's last
    slot lands in the null page, so a window layer reads that key as
    whatever the slot held before (zeros, or a freed request's); the full
    layers, the other slots and the routing are sound."""
    import dataclasses

    from automodel_tpu.serving.kv_cache import PagedKVView

    real = PagedKVView.write

    def write(self, k, v):
        if self.group != "window":
            return real(self, k, v)
        slots = self.slot_mapping["window"]
        edge = slots % self.block_size == self.block_size - 1
        lost = dict(self.slot_mapping, window=jnp.where(edge, 0, slots))
        return real(dataclasses.replace(self, slot_mapping=lost), k, v)

    monkeypatch.setattr(PagedKVView, "write", write)
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows


def test_yarn_over_the_whole_head_is_not_correct(monkeypatch):
    """The planted fault: the full layers' table turns all 16 channels
    (``partial_rotary_factor`` read as 1)."""
    from automodel_tpu.models.laguna import LagunaForCausalLM
    from automodel_tpu.ops.rotary import rope_parameters

    real = LagunaForCausalLM.__init__

    def init(self, config, **kw):
        real(self, config, **kw)
        _, theta, scaling = config.rope("full")
        D = config.head_dim
        inv, scale = rope_parameters(D, theta, scaling)
        self._rope["full"] = (inv, scale, D)

    monkeypatch.setattr(LagunaForCausalLM, "__init__", init)
    compared = _run(monkeypatch)
    assert not compared.correct, compared.rows
