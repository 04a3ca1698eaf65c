"""Laguna (``model_type: laguna``): gated attention layers of two kinds that
differ in shape (query heads, rotary table, window) in stacks by kind, a
leading dense layer, routed experts beside a shared one, one expert-parallel
share of them held.  The cache-less forward and the decode engine (chunked
prefill across the window's edge, then decode) against the benchmark's
plain float32 reference (``benchmark/reference/laguna.py``, which imports
nothing of the program); the share test; the parameter count at the
published widths; one compile a step width.  Small sizes that keep what
matters: 4 query heads on full layers and 6 on window layers over 2
key/value heads (G = 2 and 3), YaRN over half the head on full layers, a
window of 8 SMALLER than the contexts, a dense layer then two periods of
window, window, window, full, 16 experts top-4 of which 8 are held."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.analysis.jaxpr_audit import compile_cache_size
from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models.auto_model import build_model
from automodel_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
from automodel_tpu.serving import DecodeEngine, ServingConfig
from automodel_tpu.serving.kv_cache import cache_groups
from benchmark import weights as bench_weights
from benchmark.reference import laguna as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WINDOW, BS = 8, 4
KINDS = (["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
         + ["sliding_attention"] * 3 + ["full_attention"])
CFG = {
    "model_type": "laguna", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 9,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": WINDOW,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": KINDS,
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "gating_types": ["per_head"] * 9,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6, 4],
    "moe_router_logit_softcapping": 0,
    "held_experts": [0, 8], "published": {"num_experts": 16},
    # a toy's scores are 0.03 wide at these weights: attention would be
    # uniform and no comparison could see what a query sees or how it turns
    "assumed": {"qk_gain": 6.0},
}


def _world(cfg, seed=11):
    model = build_model(config=ref.model_config(cfg),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(cfg, w))(bench_weights.seed_words(seed))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    return model, params, flat


@pytest.fixture(scope="module")
def world():
    return _world(CFG)


def _ref_logits(flat, ids, cfg=CFG, pad_to=64):
    """The reference's logits at ``ids``' positions, its forward run over
    them padded on the right to ``pad_to`` (causal: the pad changes nothing
    before it), so that one program serves every length."""
    padded = np.zeros((max(pad_to, len(ids)),), np.int32)
    padded[:len(ids)] = ids
    hidden, _ = ref.hidden_states(flat, cfg, jnp.asarray(padded))
    return np.asarray(ref.logits_of(flat, hidden))[:len(ids)]


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG["vocab_size"], n).tolist() for n in lens]


def _engine(world, **kw):
    model, params, _ = world
    cfg = dict(max_num_seqs=2, max_model_len=64, kv_block_size=BS,
               prefill_chunk=4)
    cfg.update(kw)
    return DecodeEngine(
        model, params, ServingConfig(**cfg),
        generation=GenerationConfig(max_new_tokens=64, do_sample=False,
                                    eos_token_id=None))


# ---------------------------------------------------------------------------
# The family: config, stacks by kind, the cache's groups
# ---------------------------------------------------------------------------
def test_config_reads_the_published_keys_into_stacks_by_kind(world):
    model = world[0]
    assert isinstance(model, LagunaForCausalLM)
    cfg = model.config
    assert isinstance(cfg, LagunaConfig)
    assert (cfg.dense_layers(), cfg.period(), cfg.heads("full"),
            cfg.heads("window"), cfg.n_held_experts) == (1, 4, 4, 6, 8)
    assert cfg.qk_norm and cfg.gated
    assert model.stack_counts() == {"dense_layers": 1, "layers_full": 2,
                                    "layers_window": 6}
    shapes = jax.tree.map(lambda a: a.shape, model.abstract_params())
    assert shapes["layers_full"]["self_attn"]["q_proj"]["kernel"] == (
        2, 64, 64)
    assert shapes["layers_window"]["self_attn"]["q_proj"]["kernel"] == (
        6, 64, 96)
    assert shapes["layers_window"]["self_attn"]["attn_gate"]["kernel"] == (
        6, 64, 6)
    assert shapes["layers_full"]["mlp"]["experts"]["gate_proj"]["kernel"] \
        == (2, 8, 64, 32)
    # two rotary tables: YaRN over 8 of 16 channels, default over 16
    (_, yarn_scale, yarn_dim), (_, scale, dim) = (model._rope["full"],
                                                  model._rope["window"])
    assert (yarn_dim, dim, scale) == (8, 16, 1.0)
    assert yarn_scale == pytest.approx(1.4852030263919618)
    groups = cache_groups(model.paged_cache_planes(), 9)
    assert [(g.name, g.layers, g.window) for g in groups] == [
        ("full", 3, None), ("window", 6, WINDOW)]
    assert model.cache_group_of()[:5] == (
        ("full", 0), ("window", 0), ("window", 1), ("window", 2), ("full", 1))


def test_reference_tree_is_the_program_tree(world):
    model, params, flat = world
    want = jax.tree.map(lambda a: a.shape, model.abstract_params())
    assert jax.tree.map(lambda a: a.shape, params) == want
    back = ref.from_program_tree(ref.to_program_tree(flat))
    assert all(back[k] is flat[k] for k in flat)


@pytest.mark.parametrize("change, match", [
    (dict(mlp_layer_types=["dense", "sparse", "dense"] + ["sparse"] * 6),
     "leading sub-stack"),
    (dict(num_attention_heads_per_layer=[4, 6, 6, 2, 4, 6, 6, 6, 4]),
     "share one stack"),
    (dict(moe_router_logit_softcapping=30.0), "published routing"),
    (dict(gating_types=["per_head"] * 8 + [None]), "every layer"),
])
def test_what_the_stacks_by_kind_cannot_hold_is_refused(change, match):
    keys = {f.name for f in dataclasses.fields(LagunaConfig)}
    cfg = {k: v for k, v in dict(CFG, **change).items() if k in keys}
    with pytest.raises((NotImplementedError, ValueError), match=match):
        LagunaConfig(**cfg)


# ---------------------------------------------------------------------------
# Against the reference: the cache-less forward, the engine, the shares
# ---------------------------------------------------------------------------
def test_forward_without_a_cache_matches_the_reference(world):
    """Float32 both sides: only the order of additions differs (3e-7 of
    logits of ~0.7 measured); a bfloat16 product anywhere would be off by
    ~1e-2."""
    model, params, flat = world
    ids = np.asarray(_prompts([40, 40], seed=1), np.int32)
    got = np.asarray(jax.jit(lambda p, i: model(p, i)["logits"])(params, ids))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(flat, ids[b]),
                                   atol=2e-5, rtol=2e-5)


def test_engine_logits_match_the_reference_across_window_and_chunks(world):
    """Prefill in chunks of 4 across the window's edge (8), then decode
    several blocks past it: the last column's logits of EVERY step are the
    reference's full forward at that position; the served tokens are the
    reference's greedy choices.  One compiled program a step width."""
    _, _, flat = world
    eng = _engine(world)
    prompts = _prompts([23, 6], seed=4)
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    seen = []                   # (rid, context after the step, logits)
    while eng.scheduler.has_work():
        plan = eng.scheduler.schedule()
        args = eng._assemble(plan)
        greedy, last, eng.pools, _ = eng.step_fn(plan.step_width)(
            eng.params, eng.pools, *args)
        greedy, logits = np.asarray(greedy), np.asarray(last.logits)
        seen += [(w.req.rid, w.start_pos + len(w.tokens), logits[w.req.slot])
                 for w in plan.active]
        eng.scheduler.finish_step(plan, {
            w.req.slot: [int(greedy[w.req.slot, len(w.tokens) - 1])]
            for w in plan.active if w.samples_next})
    assert len(seen) > 20
    want = {}
    for rid, prompt in zip(rids, prompts):
        served = eng.requests[rid].out_tokens
        assert len(served) == 10 and len(prompt) + 10 > WINDOW + BS
        want[rid] = _ref_logits(flat, prompt + served)
        assert served == np.argmax(want[rid], -1)[len(prompt) - 1:-1].tolist()
    for rid, n, got in seen:
        # float32 both sides, the paged walk's order of additions
        np.testing.assert_allclose(got, want[rid][n - 1], atol=5e-5,
                                   rtol=5e-5)
    assert eng.all_free
    assert sorted(eng.stats()["compiled_widths"]) == [1, 4]
    for width in (1, 4):
        assert compile_cache_size(eng.step_fn(width)) in (None, 1)


def test_a_stack_that_ends_inside_a_period_matches_the_reference():
    """The published stack's 47 routed layers are 11 periods and three
    window layers more: here a dense layer, one period and a tail of two
    window layers (a period holds three), the tail a third sub-stack.  Cache-less, and through generate()'s
    dense cache, which stands at each layer by its index in the whole
    stack."""
    cfg = dict(CFG, num_hidden_layers=7, layer_types=KINDS[:7],
               mlp_layer_types=CFG["mlp_layer_types"][:7],
               gating_types=CFG["gating_types"][:7],
               num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6, 6])
    model, params, flat = _world(cfg)
    assert model.config.period() == 4
    assert model.stack_counts()["layers_window"] == 5
    prompt = _prompts([13], seed=6)[0]
    got = np.asarray(model(params, np.asarray([prompt], np.int32))["logits"])
    np.testing.assert_allclose(got[0], _ref_logits(flat, prompt, cfg),
                               atol=2e-5, rtol=2e-5)
    served = np.asarray(generate(
        model, params, np.asarray([prompt], np.int32),
        config=GenerationConfig(max_new_tokens=5, do_sample=False,
                                eos_token_id=None)))[0].tolist()
    want = np.argmax(_ref_logits(flat, prompt + served, cfg), -1)
    assert served == want[len(prompt) - 1:-1].tolist()


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-7 and 8-15 as two shares of the program's routed block
    (each with the shared expert, which every chip computes alike), the
    shared expert counted once, give the uncut reference layer: the router
    over all 16, the top-4 and their 2.5-scaled softmax, all 16 experts."""
    uncut = dict(CFG, num_experts=16, held_experts=None, published={})
    flat = jax.jit(lambda w: ref.make(uncut, w))(bench_weights.seed_words(7))
    m32 = jax.random.normal(jax.random.key(1), (2, 12, 64), jnp.float32)
    p = {n: flat["f." + n][0].astype(jnp.float32) for n in ref.ROUTED
         if not n.startswith("e_")}
    e = {n: flat["f." + n][0] for n in ("e_gate", "e_up", "e_down")}
    @jax.jit
    @ref.highest
    def uncut_layer(x, p, e):
        chosen, w, _ = ref.route(x, p["router"], 4, 2.5)
        return (ref.routed_experts(x, chosen, w, e["e_gate"], e["e_up"],
                                   e["e_down"], 0)
                + ref.swiglu(x, p["s_gate"], p["s_up"], p["s_down"]))

    want = uncut_layer(m32.reshape(-1, 64), p, e)
    parts = []
    for first in (0, 8):
        share = dict(CFG, num_experts=8, held_experts=[first, 8],
                     published={"num_experts": 16})
        model = build_model(config=ref.model_config(share),
                            compute_dtype=jnp.float32, remat=False)
        mlp = {"router": {"kernel": p["router"]},
               "experts": {
                   name: {"kernel": e[leaf][first:first + 8].astype(
                       jnp.float32)}
                   for name, leaf in (("gate_proj", "e_gate"),
                                      ("up_proj", "e_up"),
                                      ("down_proj", "e_down"))},
               "shared_expert": {"gate_proj": {"kernel": p["s_gate"]},
                                 "up_proj": {"kernel": p["s_up"]},
                                 "down_proj": {"kernel": p["s_down"]}}}
        with jax.default_matmul_precision("highest"):
            parts.append(jax.jit(lambda m, p, model=model: (
                model._experts_block(m, p, None, None, None)[0],
                model._swiglu(m, p["shared_expert"], "s")))(m32, mlp))
    got = parts[0][0] + parts[1][0] - parts[0][1]
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64),
                               np.asarray(want), atol=1e-5, rtol=1e-5)
    # each share alone is not the layer: the other's experts were chosen
    assert float(jnp.max(jnp.abs(parts[0][0].reshape(-1, 64) - want))) > 1e-3


def test_one_head_count_for_every_layer_still_runs():
    """No per-layer lists, no per-kind rotary tables: one stack of full
    layers at one head count, Llama's rotary settings, a cache of one
    group; the forward runs."""
    keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "mlp_only_layers")
    cfg = dict({k: CFG[k] for k in keys}, num_hidden_layers=3)
    model = build_model(config=cfg, compute_dtype=jnp.float32, remat=False)
    assert model.stack_counts() == {"dense_layers": 1, "layers_full": 2,
                                    "layers_window": 0}
    assert not model.config.gated and model.config.period() == 1
    params = model.init(jax.random.key(0))
    assert "layers_window" not in params
    assert list(model.paged_cache_planes()) == ["full"]
    logits = model(params, np.asarray(_prompts([9], seed=5), np.int32))
    assert logits["logits"].shape == (1, 9, 128)
    assert bool(jnp.all(jnp.isfinite(logits["logits"])))


def test_the_published_widths_hold_5_572_042_752_matrix_parameters():
    """The benchmark's configuration through the program: 11.14 GB of
    matrices in bfloat16 (the norms add 35,072 parameters)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1.json")) as f:
        cfg = json.load(f)
    model = build_model(config=ref.model_config(cfg))
    leaves = jax.tree_util.tree_flatten_with_path(model.abstract_params())[0]
    count = lambda keep: sum(int(np.prod(a.shape)) for path, a in leaves
                             if keep(path[-1].key))
    assert count(lambda k: k != "weight") == 5_572_042_752
    assert count(lambda k: k == "weight") == 35_072
    assert model.config.heads("full") == 48
    assert model.config.heads("window") == 72
    assert model.config.held_experts == (0, 128)


@pytest.mark.parametrize("dim", [8, 16])
def test_partial_rotary_turns_the_first_channels_and_scales_only_them(dim):
    """``apply_rope(rotary_dim=...)``, as the full layers' YaRN table (and a
    partial-rotary family) uses it: the first ``dim`` channels of a head
    rotate, pairing i with i + dim / 2, and carry the attention factor; the
    rest pass unchanged.  Against the rotation written out in numpy."""
    from automodel_tpu.ops.rotary import apply_rope

    rng = np.random.default_rng(dim)
    q = rng.standard_normal((1, 5, 3, 16)).astype(np.float32)
    k = rng.standard_normal((1, 5, 2, 16)).astype(np.float32)
    pos = np.arange(5)[None]
    inv = 1.0 / 10_000 ** (np.arange(0, dim, 2) / dim)
    qr, kr = apply_rope(jnp.asarray(q), jnp.asarray(k), jnp.asarray(pos),
                        jnp.asarray(inv, jnp.float32),
                        attention_scaling=1.5, rotary_dim=dim)
    ang = pos[0][:, None] * inv                        # [S, dim / 2]
    cos, sin = 1.5 * np.cos(ang)[:, None], 1.5 * np.sin(ang)[:, None]
    for x, got in ((q, qr), (k, kr)):
        x1, x2 = x[0, ..., :dim // 2], x[0, ..., dim // 2:dim]
        want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                               x[0, ..., dim:]], axis=-1)
        np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5,
                                   atol=1e-5)
