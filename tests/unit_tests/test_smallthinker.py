"""SmallThinker (``model_type: smallthinker``): window and full (NoPE)
layers in one stack over a cache of two block groups, routed ReGLU experts
behind a router that reads the pre-attention stream.  The cache-less
forward (loss and gradients), ``generate()`` and the decode engine
(chunked prefill across the window's edge, preemption, replay) against the
benchmark's plain float32 reference (``benchmark/reference/smallthinker.py``,
which imports nothing of the program); the two-group allocator; what is
refused for such a cache.  Small sizes that keep what matters: seven query
heads a key/value head, top-6 of 16 experts, one full NoPE and three
window layers a period, two periods, a window SMALLER than the contexts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models.auto_model import build_model
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.models.smallthinker import (
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
)
from automodel_tpu.ops import moe
from automodel_tpu.ops import paged_attention_kernel as pak
from automodel_tpu.ops.paged_attention import (
    window_first_block,
    window_span_blocks,
)
from automodel_tpu.serving import DecodeEngine, ServingConfig
from automodel_tpu.serving.kv_cache import cache_groups
from automodel_tpu.training.timers import Timers
from benchmark import weights as bench_weights
from benchmark.reference import smallthinker as ref

WINDOW, BS = 8, 4
CFG = {
    "model_type": "smallthinker", "head_dim": 16, "hidden_size": 64,
    "max_position_embeddings": 512, "moe_ffn_hidden_size": 32,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 16,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 7, "num_hidden_layers": 8,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": WINDOW, "tie_word_embeddings": False,
    "vocab_size": 128,
}


@pytest.fixture(scope="module")
def world():
    model = build_model(config=ref.model_config(CFG),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(CFG, w))(bench_weights.seed_words(11))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    return model, params, flat


def _ref_logits(flat, ids):
    hidden, _ = ref.hidden_states(flat, CFG, jnp.asarray(ids, jnp.int32))
    return np.asarray(ref.logits_of(flat, hidden))


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG["vocab_size"], n).tolist() for n in lens]


def _engine(world, timers=None, **kw):
    model, params, _ = world
    cfg = dict(max_num_seqs=4, max_model_len=64, kv_block_size=BS,
               prefill_chunk=4)
    cfg.update(kw)
    return DecodeEngine(
        model, params, ServingConfig(**cfg), timers=timers,
        generation=GenerationConfig(max_new_tokens=64, do_sample=False,
                                    eos_token_id=None))


def _greedy_by_reference(flat, prompt, served):
    """Teacher-forced: the reference's choice after each served prefix."""
    logits = _ref_logits(flat, prompt + served)
    return np.argmax(logits, -1)[len(prompt) - 1:-1].tolist()


# ---------------------------------------------------------------------------
# The family: config, registry, parameter tree
# ---------------------------------------------------------------------------
def test_config_reads_the_published_keys_and_finds_the_period(world):
    model = world[0]
    assert isinstance(model, SmallThinkerForCausalLM)
    cfg = model.config
    assert isinstance(cfg, SmallThinkerConfig)
    assert (cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
            cfg.moe_ffn_hidden_size, cfg.sliding_window_size) == (16, 6, 32, 8)
    assert cfg.period() == 4
    assert cfg.layer_kinds()[:4] == ((False, False), (True, True),
                                     (True, True), (True, True))
    assert model.cache_group_of() == (
        ("full", 0), ("window", 0), ("window", 1), ("window", 2),
        ("full", 1), ("window", 3), ("window", 4), ("window", 5))
    # a stack with no period is one body of L layers
    odd = dataclasses.replace(cfg, sliding_window_layout=(0, 1, 1, 1, 1, 1,
                                                          1, 0))
    assert odd.period() == 8
    with pytest.raises(ValueError, match="rope_layout"):
        SmallThinkerConfig(num_hidden_layers=4, rope_layout=(0, 1))


def test_key_map_names_every_parameter(world):
    model = world[0]
    key_map = model.hf_key_map()
    leaves = jax.tree_util.tree_flatten_with_path(model.abstract_params())[0]
    paths = {tuple(k.key for k in path) for path, _ in leaves}
    assert paths == set(key_map)
    spec = key_map[("layers", "block_sparse_moe", "experts", "gate",
                    "kernel")]
    assert spec.template == \
        "model.layers.{i}.block_sparse_moe.experts.{e}.gate.weight"
    assert spec.expert_stacked and spec.transpose


def test_reference_tree_is_the_program_tree(world):
    model, params, flat = world
    want = jax.tree.map(lambda a: a.shape, model.abstract_params())
    assert jax.tree.map(lambda a: a.shape, params) == want
    back = ref.from_program_tree(ref.to_program_tree(flat))
    assert all(back[k] is flat[k] for k in flat)


def test_llama_still_refuses_a_mixed_stack_it_was_not_told_of():
    with pytest.raises(NotImplementedError, match="mixed sliding/full"):
        LlamaForCausalLM(LlamaConfig(num_hidden_layers=4, sliding_window=8,
                                     max_window_layers=2))


def test_the_router_reads_the_norm_before_it_is_rounded(world):
    """Under bfloat16 the attention block hands the router the norm's
    float32 result: layer 0's router logits (a function of the token id
    alone) then agree with the float32 reference to round-off, so a token
    whose 6th and 7th logit nearly tie chooses the same experts in both, at
    every position that holds it.  Rounded to bfloat16 first, the logits
    move by a thousand times more."""
    _, params, flat = world
    model = build_model(config=ref.model_config(CFG),
                        compute_dtype=jnp.bfloat16, remat=False)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    embed = flat["embed"]                                   # bfloat16 rows
    ids = jnp.arange(CFG["vocab_size"])[None]
    _, u, _ = model._attention_block(
        embed[ids], layer0, False, False, ids, None, None,
        *model._rope_tables(ids), None)
    assert u.dtype == jnp.float32
    want = ref.rms_norm(embed.astype(jnp.float32),
                        flat["input_norm"][0].astype(jnp.float32),
                        CFG["rms_norm_eps"])
    router = flat["router"][0].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact, got = want @ router, u[0] @ router
        rounded = want.astype(jnp.bfloat16).astype(jnp.float32) @ router
    scale = float(jnp.sqrt(jnp.mean(exact * exact)))
    assert float(jnp.abs(got - exact).max()) < 1e-5 * scale
    assert float(jnp.abs(rounded - exact).max()) > 1e-3 * scale


# ---------------------------------------------------------------------------
# The cache-less forward (the training path): logits, loss, gradients
# ---------------------------------------------------------------------------
def test_forward_without_a_cache_matches_the_reference(world):
    model, params, flat = world
    ids = np.asarray(_prompts([40, 40], seed=1), np.int32)
    got = np.asarray(jax.jit(lambda p, i: model(p, i)["logits"])(params, ids))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(flat, ids[b]),
                                   atol=2e-4, rtol=2e-4)


def test_loss_and_gradients_match_the_reference(world):
    model, params, flat = world
    ids = np.asarray(_prompts([33], seed=2)[0], np.int32)

    def ce(logits):
        logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=1))

    def program(p):
        return ce(model(p, ids[None])["logits"][0])

    def reference(p):
        f = ref.from_program_tree(p)
        hidden, _ = ref.hidden_states(f, CFG, jnp.asarray(ids))
        return ce(ref.logits_of(f, hidden))

    (lp, gp), (lr, gr) = (jax.value_and_grad(f)(params)
                          for f in (program, reference))
    assert float(lp) == pytest.approx(float(lr), abs=1e-5)
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a - b)),
                                       float(jnp.linalg.norm(b))), gp, gr)
    for path, (err, size) in jax.tree_util.tree_flatten_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple))[0]:
        assert err <= 2e-3 * size + 1e-7, (jax.tree_util.keystr(path), err,
                                           size)
    # the router's gradient is not nought: it reaches u, not m
    router = gp["layers"]["block_sparse_moe"]["primary_router"]["kernel"]
    assert float(jnp.linalg.norm(router)) > 0


# ---------------------------------------------------------------------------
# generate() and the decode engine against the reference's full forward
# ---------------------------------------------------------------------------
def test_generate_matches_the_reference(world):
    model, params, flat = world
    prompts = _prompts([21, 21], seed=3)
    out = generate(model, params, np.asarray(prompts, np.int32),
                   config=GenerationConfig(max_new_tokens=14, do_sample=False,
                                           eos_token_id=None))
    for prompt, served in zip(prompts, np.asarray(out).tolist()):
        assert served == _greedy_by_reference(flat, prompt, served)


def test_engine_logits_match_the_reference_past_the_window(world):
    """Prefill in chunks of 4 across the window's edge (8), then decode to
    several blocks past it: the last column's logits of EVERY step are the
    reference's at that position."""
    model, params, flat = world
    eng = _engine(world, max_num_seqs=2)
    prompts = _prompts([23, 6], seed=4)
    rids = [eng.submit(p, max_new_tokens=18) for p in prompts]
    checked = 0
    while eng.scheduler.has_work():
        plan = eng.scheduler.schedule()
        args = eng._assemble(plan)
        greedy, last, eng.pools, _ = eng.step_fn(plan.step_width)(
            eng.params, eng.pools, *args)
        greedy, logits = np.asarray(greedy), np.asarray(last.logits)
        for w in plan.active:
            req = w.req
            n = w.start_pos + len(w.tokens)         # the context after it
            want = _ref_logits(flat, req.seq[:n])[-1]
            np.testing.assert_allclose(logits[req.slot], want, atol=3e-4,
                                       rtol=3e-4)
            checked += 1
        eng.scheduler.finish_step(plan, {
            w.req.slot: [int(greedy[w.req.slot, len(w.tokens) - 1])]
            for w in plan.active if w.samples_next})
    assert checked > 30
    for rid, prompt in zip(rids, prompts):
        served = eng.requests[rid].out_tokens
        assert len(served) == 18 and len(prompt) + 18 > WINDOW + 3 * BS
        assert served == _greedy_by_reference(flat, prompt, served)
    assert eng.all_free


@pytest.mark.parametrize("how", ["preemption", "replay"])
def test_engine_through_preemption_and_replay(world, how):
    """A pool too small for the three requests (preemption), or a watchdog
    recovery in mid-run (replay): both groups' tables are freed and
    re-prefilled, and the tokens are the reference's."""
    model, params, flat = world
    blocks = ({"full": 17, "window": 9} if how == "preemption"
              else {"full": 40, "window": 17})
    eng = _engine(world, max_num_seqs=3, num_kv_blocks=blocks)
    prompts = _prompts([19, 9, 14], seed=5)
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    if how == "replay":
        for _ in range(9):
            eng.step()
        assert any(r.group_blocks.get("window") for r in
                   eng.scheduler.active)
        eng._watchdog_recover("the test asks for it")
        assert eng.all_free
    out = eng.run()
    if how == "preemption":
        assert eng.stats()["preemptions"] > 0
    else:
        assert eng.stats()["watchdog_recoveries"] == 1
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == _greedy_by_reference(flat, prompt, out[rid])
    assert eng.all_free
    assert all(g.allocator.all_free for g in eng.block_groups)


def test_engine_on_the_pallas_rung_in_interpret_mode(world, monkeypatch):
    """The kernel itself (seven query rows a kv head, the window layers'
    walk from its start index over released-and-null entries) inside the
    engine's step: the same tokens as the XLA anchor serves."""
    model, params, flat = world
    cfg = dict(CFG, head_dim=128, hidden_size=128)
    big = build_model(config=ref.model_config(cfg),
                      compute_dtype=jnp.float32, remat=False)
    bflat = jax.jit(lambda w: ref.make(cfg, w))(bench_weights.seed_words(5))
    bparams = jax.tree.map(lambda a: a.astype(jnp.float32),
                           ref.to_program_tree(bflat))
    prompts = _prompts([13, 5], seed=6)

    def run():
        eng = _engine((big, bparams, bflat), max_num_seqs=2)
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        out = eng.run()
        return [out[r] for r in rids]

    from automodel_tpu.ops.kernel_lib import registry

    anchor = run()
    before = registry.resolved_rungs().get("attention.paged_decode", 0)
    monkeypatch.setattr(pak, "_INTERPRET", True)
    assert run() == anchor
    assert registry.resolved_rungs()["attention.paged_decode"] > before


# ---------------------------------------------------------------------------
# The cache of two block groups
# ---------------------------------------------------------------------------
def test_the_model_declares_its_cache_per_group(world):
    model = world[0]
    groups = cache_groups(model.paged_cache_planes(), 8)
    assert [(g.name, g.layers, g.window) for g in groups] == [
        ("full", 2, None), ("window", 6, WINDOW)]
    assert all(g.planes == {"k": (1, 16), "v": (1, 16)} for g in groups)
    eng = _engine(world)
    # one kv head, fewer than a tile's rows: each group's pools are rows
    assert eng.pools["full"]["k"].shape == (2, 4 * 16 + 1, BS, 16)
    # a window group resides fully at what a step of the widest width sees
    span = window_span_blocks(WINDOW, 4, BS)
    assert span == 4 and eng.pools["window"]["v"].shape == (
        6, 4 * span + 1, BS, 16)
    # a flat declaration is a cache of one unnamed group
    flat = cache_groups({"k": (2, 8), "v": (2, 8)}, 5)
    assert [(g.name, g.layers, g.window) for g in flat] == [(None, 5, None)]
    with pytest.raises(ValueError, match="do not cover"):
        cache_groups(model.paged_cache_planes(), 9)


def test_the_engine_reports_rows_for_both_groups(world, caplog):
    """The pools' layout is the engine's to report, by group, in
    ``stats()`` and in its build log."""
    import logging

    with caplog.at_level(logging.INFO, logger="automodel_tpu.serving.engine"):
        eng = _engine(world)
    assert eng.stats()["kv_layout"] == {"full": "rows", "window": "rows"}
    assert "paged KV cache layout: {'full': 'rows', 'window': 'rows'}" \
        in caplog.text


def test_window_arithmetic():
    assert window_span_blocks(4096, 1, 128) == 33
    assert window_span_blocks(4096, 32, 128) == 34
    assert window_span_blocks(8, 1, 4) == 3
    assert [window_first_block(p, 8, 4) for p in (0, 7, 10, 11, 12, 40)] \
        == [0, 0, 0, 1, 1, 8]
    np.testing.assert_array_equal(
        window_first_block(jnp.asarray([0, 11, 40]), 8, 4), [0, 1, 8])


def test_window_blocks_are_bounded_released_and_reused(world):
    """While requests run: a row's live window blocks never exceed what a
    step's queries can see; an entry once released holds the null page for
    good; a released block serves another row; both groups drain."""
    eng = _engine(world, max_num_seqs=3,
                  num_kv_blocks={"full": 64, "window": 3 * 4 + 1})
    prompts = _prompts([22, 7, 30, 12, 18], seed=7)
    for p in prompts:
        eng.submit(p, max_new_tokens=24)
    owners, reused, nulled = {}, 0, {}
    inner = eng.scheduler.schedule

    def schedule(*a, **k):
        nonlocal reused
        plan = inner(*a, **k)
        for w in (plan.active if plan is not None else ()):
            req, table = w.req, w.req.group_blocks["window"]
            live = [b for b in table if b]
            width = len(w.tokens)
            assert len(live) <= window_span_blocks(WINDOW, width, BS)
            if width == 1:
                assert len(live) <= -(-WINDOW // BS) + 1
            first = window_first_block(w.start_pos, WINDOW, BS)
            assert all(b == 0 for b in table[:first])
            assert all(b != 0 for b in table[first:])
            assert req.released.get("window", 0) == first
            # never read again: what was null stays null
            assert first >= nulled.get(req.rid, 0)
            nulled[req.rid] = first
            assert len(req.blocks) == len(table) and all(req.blocks)
            for b in live:
                if owners.get(b, req.rid) != req.rid:
                    reused += 1
                owners[b] = req.rid
        return plan

    eng.scheduler.schedule = schedule
    eng.run()
    st = eng.stats()
    assert reused > 0 and st["preemptions"] == 0
    assert st["window_blocks_released"]["window"] > 20
    assert st["kv_blocks_peak"]["window"] <= 3 * 4
    assert st["kv_blocks_peak"]["full"] > st["kv_blocks_peak"]["window"]
    assert eng.all_free and st["kv_blocks_free"] == {"full": 63, "window": 12}


def test_a_request_is_admitted_only_if_every_group_can_serve_it(world):
    eng = _engine(world, num_kv_blocks={"full": 64, "window": 3})
    with pytest.raises(ValueError, match="group 'window'"):
        eng.submit(_prompts([20])[0], max_new_tokens=4)
    eng = _engine(world, num_kv_blocks={"full": 5, "window": 17})
    with pytest.raises(ValueError, match="needs 6 KV blocks"):
        eng.submit(_prompts([20])[0], max_new_tokens=4)


def test_the_step_stamps_the_keys_each_group_read(world):
    timers = Timers()
    seen = []
    real = timers.event
    timers.event = lambda name, **st: (seen.append((name, st)),
                                       real(name, **st))[1]
    eng = _engine(world, timers=timers, max_num_seqs=2)
    eng.scheduler._event = timers.event
    for p in _prompts([11, 3], seed=8):
        eng.submit(p, max_new_tokens=6)
    eng.run()
    reads = [st for name, st in seen if name == "serve_kv_read"]
    assert len(reads) == eng.steps_run
    first = reads[0]                    # two rows' first chunks: 4 and 3
    assert (first["rows"], first["positions"], first["full_keys"],
            first["window_keys"]) == (2, 7, 7, 7)
    last = reads[-1]                    # one row left, context 11 + 6 - 1
    assert (last["full_keys"], last["window_keys"]) == (16, WINDOW)
    experts = [st for name, st in seen if name == "serve_experts"]
    assert experts and all(e["assignments"] % 6 == 0 for e in experts)


@pytest.mark.parametrize("option, match", [
    (dict(prefix_caching="on"), "prefix_caching"),
    (dict(speculative="ngram"), "speculative"),
    (dict(kv_cache_dtype="int8"), "int8"),
])
def test_what_is_not_wired_for_block_groups_is_refused(world, option, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(world, **option)


def test_one_count_of_blocks_for_named_groups_and_the_reverse(world):
    with pytest.raises(ValueError, match="num_kv_blocks"):
        ServingConfig(num_kv_blocks={"full": 1})
    model, params, _ = world
    llama = build_model(config=dict(
        model_type="llama", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=1))
    with pytest.raises(ValueError, match="one unnamed group"):
        DecodeEngine(llama, llama.abstract_params(),
                     ServingConfig(num_kv_blocks={"full": 8}))


# ---------------------------------------------------------------------------
# ops/moe.py: the router's input apart from the experts', the activation
# ---------------------------------------------------------------------------
def _moe_world(seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    T, H, I, E = 24, 32, 16, 8
    return dict(
        x=jax.random.normal(k[0], (2, T // 2, H)),
        u=jax.random.normal(k[1], (2, T // 2, H)),
        router=jax.random.normal(k[2], (H, E)) * 0.3,
        wg=jax.random.normal(k[3], (E, H, I)) * 0.2,
        wu=jax.random.normal(k[4], (E, H, I)) * 0.2,
        wd=jax.random.normal(k[5], (E, I, H)) * 0.2)


def _moe_by_hand(w, activation, top=3):
    x, u = (w[n].reshape(-1, w[n].shape[-1]) for n in ("x", "u"))
    logits = u @ w["router"]
    vals, idx = jax.lax.top_k(logits, top)
    share = jax.nn.softmax(vals, axis=-1)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    out = jnp.zeros_like(x)
    for e in range(w["wg"].shape[0]):
        y = (act(x @ w["wg"][e]) * (x @ w["wu"][e])) @ w["wd"][e]
        out += jnp.sum(jnp.where(idx == e, share, 0.0), -1)[:, None] * y
    return out.reshape(w["x"].shape), idx, share


@pytest.mark.parametrize("dispatch", ["sorted", "onehot"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_moe_block_routes_on_one_tensor_and_feeds_another(activation,
                                                          dispatch):
    w = _moe_world()
    want, _, _ = _moe_by_hand(w, activation)
    got, _ = moe.moe_mlp_block(
        w["x"], w["router"], w["wg"], w["wu"], w["wd"],
        num_experts_per_tok=3, capacity_factor=None,
        compute_dtype=jnp.float32, norm_topk=True, dispatch=dispatch,
        router_input=w["u"], activation=activation)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # routing on x itself is another result: the argument is read
    own, _ = moe.moe_mlp_block(
        w["x"], w["router"], w["wg"], w["wu"], w["wd"],
        num_experts_per_tok=3, capacity_factor=None,
        compute_dtype=jnp.float32, norm_topk=True, dispatch=dispatch,
        activation=activation)
    assert float(jnp.max(jnp.abs(own - want))) > 1e-3


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_decode_expert_ffn_takes_the_activation(activation):
    w = _moe_world(1)
    want, idx, share = _moe_by_hand(w, activation)
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a])     # layer 1 of 2
    got, counts = moe.decode_expert_ffn(
        w["x"].reshape(-1, 32), share, idx, stack(w["wg"]), stack(w["wu"]),
        stack(w["wd"]), layer=jnp.int32(1), compute_dtype=jnp.float32,
        activation=activation)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-5,
                               rtol=1e-5)
    assert int(counts.sum()) == 24 * 3


def test_decode_expert_ffn_takes_the_quantized_compute_path():
    """The model's quantized-compute knob reaches the decode dispatch's
    three products (the benchmark's int8 control rests on it): near the
    bfloat16 result, and not it."""
    from automodel_tpu.ops.quant import QuantConfig

    w = _moe_world(2)
    _, idx, share = _moe_by_hand(w, "relu")
    stack = lambda a: a[None]
    args = (w["x"].reshape(-1, 32), share, idx, stack(w["wg"]),
            stack(w["wu"]), stack(w["wd"]))
    kw = dict(layer=jnp.int32(0), compute_dtype=jnp.float32,
              activation="relu")
    plain, _ = moe.decode_expert_ffn(*args, **kw)
    quant, _ = moe.decode_expert_ffn(
        *args, quant=QuantConfig(enabled=True, dtype="int8",
                                 recipe_name="tensorwise"), **kw)
    err = float(jnp.max(jnp.abs(quant - plain)))
    assert 0 < err < 0.1 * float(jnp.max(jnp.abs(plain)))
