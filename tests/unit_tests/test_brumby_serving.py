"""Brumby (``model_type: brumby``: Qwen3's block over power retention)
through the normal entry points: the family's plain forward, the decode
engine over per-sequence state planes (prefill chunks, decoding, a reused
row, preemption and watchdog replay, the refusals at build), ``generate()``
through the same cache protocol, and two planted faults that the comparison
must see.  Small sizes, seeded random weights, float32; the plain reference
is the benchmark's (``benchmark/reference/brumby.py``: the ATTENTION form,
which imports nothing of the program)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models import hf_io
from automodel_tpu.models.auto_model import build_model
from automodel_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from automodel_tpu.ops import power_retention as pr
from automodel_tpu.ops import power_retention_kernel as pk
from automodel_tpu.serving import DecodeEngine, ServingConfig
from automodel_tpu.serving.kv_cache import (
    StatePlaneView,
    init_paged_pools,
    init_state_planes,
    pool_bytes,
    sequence_planes,
)
from automodel_tpu.serving.scheduler import RequestState
from automodel_tpu.utils import fault_injection as fi
from benchmark import weights as bench_weights
from benchmark.reference import brumby as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "tests", "data",
                       "tiny-brumby.json")) as f:
    CFG = json.load(f)         # hidden 64, 4 query / 2 kv heads of 16, 2 layers


@pytest.fixture(scope="module")
def world():
    model = build_model(config=ref.model_config(CFG),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(CFG, w))(
        bench_weights.seed_words(2 ** 31 + 33))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    return model, flat, params


def _reference_logits(flat, ids):
    h = ref.hidden_states(flat, jnp.asarray(ids), ref.dims_of(CFG),
                          CFG["num_hidden_layers"])
    return np.asarray(ref.logits_of(flat, h))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


def _engine(world, **kw):
    model, _, params = world
    cfg = dict(max_num_seqs=2, max_model_len=96, prefill_chunk=8)
    cfg.update(kw)
    return DecodeEngine(model, params, ServingConfig(**cfg),
                        generation=GenerationConfig(
                            max_new_tokens=12, do_sample=False,
                            eos_token_id=None))


# -- the family on the registry --------------------------------------------
def test_build_model_builds_the_family(world):
    model, flat, params = world
    assert isinstance(model, BrumbyForCausalLM)
    assert isinstance(model.config, BrumbyConfig) and model.config.qk_norm
    want = jax.tree.map(lambda a: a.shape, model.abstract_params())
    assert jax.tree.map(lambda a: a.shape, params) == want
    gate = want["layers"]["self_attn"]["g_proj"]
    assert gate == {"kernel": (2, 64, 2), "bias": (2, 2)}
    back = ref.from_program_tree(ref.to_program_tree(flat))
    assert all(back[n] is flat[n] for n in flat)
    is_tuple = lambda x: isinstance(x, tuple)
    ranks = jax.tree.map(len, model.param_axes(), is_leaf=is_tuple)
    assert ranks == jax.tree.map(len, want, is_leaf=is_tuple)


def test_hf_key_map_names_the_gate(world):
    model = world[0]
    m = hf_io.brumby_key_map(model.config)
    assert m[("layers", "self_attn", "g_proj", "kernel")].template == (
        "model.layers.{i}.self_attn.g_proj.weight")
    assert m[("layers", "self_attn", "g_proj", "bias")].template == (
        "model.layers.{i}.self_attn.g_proj.bias")
    for name in ("q_norm", "k_norm"):
        assert ("layers", "self_attn", name, "weight") in m
    leaves = {p for p, _ in jax.tree_util.tree_flatten_with_path(
        model.abstract_params())[0]}
    assert len(m) == len(leaves)


def test_another_degree_is_refused():
    with pytest.raises(NotImplementedError, match="degree 2"):
        BrumbyConfig(power_degree=4)


def test_the_seeded_gate_remembers(world):
    """``b_g`` in [4, 8]: the gate stays near 1, so a fault in the carried
    state shows; a zero-mean gate would forget in two tokens."""
    b = np.asarray(world[1]["g_bias"], np.float32)
    assert b.shape == (2, 2) and 4.0 <= b.min() and b.max() <= 8.0


# -- (a) the plain forward == the reference --------------------------------
def test_plain_forward_matches_the_reference(world):
    model, flat, params = world
    ids = _ids(45)
    with jax.default_matmul_precision("highest"):
        got = model(params, jnp.asarray(ids[None]))["logits"][0]
    np.testing.assert_allclose(got, _reference_logits(flat, ids),
                               atol=2e-5, rtol=1e-4)


def test_plain_forward_keeps_packed_documents_apart(world):
    model, flat, params = world
    a, b = _ids(19, 1), _ids(23, 2)
    row = np.concatenate([a, b, np.zeros(6, np.int32)])
    seg = np.array([1] * 19 + [2] * 23 + [0] * 6, np.int32)
    pos = np.concatenate([np.arange(19), np.arange(23), np.zeros(6)])
    with jax.default_matmul_precision("highest"):
        got = model(params, jnp.asarray(row[None]),
                    position_ids=jnp.asarray(pos[None], jnp.int32),
                    segment_ids=jnp.asarray(seg[None]))["logits"][0]
    np.testing.assert_allclose(got[:19], _reference_logits(flat, a),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(got[19:42], _reference_logits(flat, b),
                               atol=2e-5, rtol=1e-4)


def test_a_toy_training_step_moves_the_gate(world):
    model, _, params = world
    ids = jnp.asarray(_ids(33)[None])

    def loss(p):
        logits = model(p, ids[:, :-1])["logits"]
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), ids[:, 1:, None], axis=-1))

    g = jax.grad(loss)(params)["layers"]["self_attn"]["g_proj"]
    assert float(jnp.abs(g["kernel"]).max()) > 0
    assert float(jnp.abs(g["bias"]).max()) > 0


# -- (b) prefill then decode through the state planes == the reference ------
def _state_logits(model, params, ids, chunk, decode_from, rows=3, row=1,
                  planes=None):
    """``ids [T]`` through the state planes as the engine steps them:
    chunks of ``chunk`` tokens up to ``decode_from``, then one at a time, in
    row ``row`` of ``rows`` (the others idle).  Returns (logits [T, V],
    planes)."""
    T = len(ids)
    if planes is None:
        planes = init_state_planes(
            num_layers=model.config.num_hidden_layers, rows=rows,
            planes=model.paged_cache_planes())

    @jax.jit        # one program per width, as the engine has
    def step(planes, toks, pos, tables):
        view = StatePlaneView(planes, tables, pos)
        return model(params, toks, position_ids=pos, kv_cache=view)

    out, start = [], 0
    while start < T:
        w = chunk if start < decode_from else 1
        n = min(w, (decode_from if start < decode_from else T) - start)
        toks = np.zeros((rows, w), np.int32)
        pos = np.zeros((rows, w), np.int32)
        tables = np.zeros((rows, 1), np.int32)
        toks[row, :n] = ids[start:start + n]
        pos[row] = start + np.minimum(np.arange(w), n - 1)
        tables[row] = row + 1
        res = step(planes, jnp.asarray(toks), jnp.asarray(pos),
                   jnp.asarray(tables))
        planes = res["kv_cache"]
        assert np.isfinite(np.asarray(res["logits"])).all()   # idle rows too
        out.append(np.asarray(res["logits"][row, :n]))
        start += n
    return np.concatenate(out), planes


@pytest.mark.parametrize("chunk", [1, 3, 8], ids=["width1", "ragged3",
                                                  "chunk8"])
def test_state_planes_match_the_plain_reference(world, chunk):
    """Logits, not tokens: 29 prompt tokens in chunks (the last one ragged),
    then 12 decode steps, against ONE full forward of the reference."""
    model, flat, params = world
    ids = _ids(41)
    with jax.default_matmul_precision("highest"):
        got, planes = _state_logits(model, params, ids, chunk, 29)
    np.testing.assert_allclose(got, _reference_logits(flat, ids),
                               atol=3e-5, rtol=1e-4)
    assert not np.asarray(planes["state"][:, 0]).any()      # idle rows
    assert not np.asarray(planes["state"][:, 2]).any()


def test_a_reused_row_starts_from_zero(world):
    """A second request in a row that another held reads what a fresh row
    gives: the step resets the row where its positions start at 0."""
    model, flat, params = world
    a, b = _ids(21, 3), _ids(17, 4)
    with jax.default_matmul_precision("highest"):
        _, planes = _state_logits(model, params, a, 8, 13)
        assert np.asarray(planes["state"][:, 1]).any()
        got, _ = _state_logits(model, params, b, 8, 9, planes=planes)
    np.testing.assert_allclose(got, _reference_logits(flat, b), atol=3e-5,
                               rtol=1e-4)


# -- (c) the engine -----------------------------------------------------------
def _served_gap(flat, prompt, tokens):
    return float(ref.served_token_gaps(flat, CFG, prompt, tokens,
                                       pad_to=16).max())


def test_engine_serves_what_the_reference_prefers(world):
    """Three requests through two rows (one row is reused), greedy: every
    served token is the reference's best at its position (gap 0 up to
    float32 round-off), the counters count, and no block was ever taken."""
    _, flat, _ = world
    eng = _engine(world)
    prompts = [_ids(13, 5).tolist(), _ids(29, 6).tolist(),
               _ids(5, 7).tolist()]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    for p, r in zip(prompts, rids):
        assert eng.requests[r].state is RequestState.FINISHED
        assert _served_gap(flat, p, out[r]) < 1e-4
    st = eng.stats()
    assert st["state_resets_sum"] == 3
    assert st["state_rows_sum"] == st["rows_sum"] > 0
    assert st["state_plane_bytes"] == st["kv_pool_bytes"] == pool_bytes(
        eng.pools) == 2 * 2 * 2 * (9 * 16 + 16) * 16 * 4
    assert st["kv_blocks_peak"] == 0 and eng.allocator.all_free
    assert sorted(eng.pools) == ["norm", "state"]


def test_admission_is_bounded_by_rows_not_by_context(world):
    """``max_model_len`` costs no memory and a long request no blocks: the
    planes are the same size whatever the context, and both rows admit."""
    short, long_ = _engine(world), _engine(world, max_model_len=4096)
    assert pool_bytes(short.pools) == pool_bytes(long_.pools)
    for n in (900, 700, 40):
        long_.submit(_ids(n, n).tolist(), max_new_tokens=2)
    long_.step()
    assert len(long_.scheduler.active) == 2
    assert len(long_.scheduler.waiting) == 1
    assert long_.allocator.used_blocks == 0


def test_a_row_reused_by_a_second_request_gives_what_a_fresh_engine_gives(
        world):
    first, second = _ids(23, 8).tolist(), _ids(11, 9).tolist()
    eng = _engine(world, max_num_seqs=1)
    ra = eng.submit(first, max_new_tokens=9)
    rb = eng.submit(second, max_new_tokens=9)
    out = eng.run()
    assert eng.requests[ra].finish_time <= eng.requests[rb].admit_time
    fresh = _engine(world, max_num_seqs=1)
    rf = fresh.submit(second, max_new_tokens=9)
    assert fresh.run()[rf] == out[rb]


def test_a_preempted_request_continues_identically(world):
    prompts = [_ids(19, 10).tolist(), _ids(9, 11).tolist()]
    plain = _engine(world)
    want = [plain.submit(p, max_new_tokens=12) for p in prompts]
    want = [plain.run()[r] for r in want]
    eng = _engine(world)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    for _ in range(7):
        eng.step()
    victim = eng.requests[rids[0]]
    assert victim.state is RequestState.DECODE and victim.out_tokens
    eng.scheduler._preempt(victim)
    assert victim.num_computed == 0 and victim.slot is None
    out = eng.run()
    assert [out[r] for r in rids] == want
    assert eng.stats()["preemptions"] == 1
    assert eng.stats()["state_resets_sum"] == 3     # two firsts, one replay


@pytest.mark.fault
def test_watchdog_replay_rebuilds_the_planes_and_continues_identically(
        world):
    prompts = [_ids(19, 12).tolist(), _ids(9, 13).tolist()]
    plain = _engine(world)
    want = [plain.submit(p, max_new_tokens=12) for p in prompts]
    want = [plain.run()[r] for r in want]
    fi.configure_faults("serve_watchdog_stall:5")
    try:
        eng = _engine(world, watchdog_s=30.0)
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        out = eng.run()
    finally:
        fi.reset_faults()
    assert eng.watchdog_recoveries == 1
    assert [out[r] for r in rids] == want
    assert sorted(eng.pools) == ["norm", "state"]


def test_generate_decodes_through_the_same_protocol(world):
    """``generate()`` (left-padded lockstep batch, ``DenseKVView.retain``)
    is token-identical to the engine, as for every other family."""
    model, _, params = world
    prompts = [_ids(13, 14), _ids(29, 15)]
    ids = np.zeros((2, 29), np.int32)
    for b, p in enumerate(prompts):
        ids[b, :len(p)] = p
    cfg = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=None)
    got = generate(model, params, ids, prompt_lens=np.asarray([13, 29]),
                   config=cfg)
    eng = _engine(world)
    np.testing.assert_array_equal(
        eng.generate(ids, np.asarray([13, 29]), config=cfg), got)
    cache = model.init_kv_cache(2, 4096)
    assert pool_bytes(cache) == pool_bytes(model.init_kv_cache(2, 64))


# -- the refusals at build ---------------------------------------------------
@pytest.mark.parametrize("option,missing", [
    (dict(prefix_caching="on"), "SNAPSHOT of the state at block boundaries"),
    (dict(speculative="ngram"), "rolls it BACK"),
    (dict(kv_cache_dtype="int8"), "no scale plane"),
], ids=["prefix_caching", "speculative", "int8_kv"])
def test_options_of_a_per_token_cache_are_refused_loudly(world, option,
                                                         missing):
    with pytest.raises(NotImplementedError) as e:
        _engine(world, **option)
    assert "per-sequence state planes ['norm', 'state']" in str(e.value)
    assert missing in str(e.value)


def test_planes_are_of_one_kind(world):
    planes = world[0].paged_cache_planes()
    assert sequence_planes(planes)
    assert not sequence_planes({"k": (2, 16), "v": (2, 16)})
    assert not sequence_planes({"kv": (576,)})
    with pytest.raises(NotImplementedError, match="hybrid"):
        sequence_planes(dict(planes, k=(2, 16)))
    with pytest.raises(ValueError, match="init_state_planes"):
        init_paged_pools(num_layers=2, num_blocks=4, block_size=16,
                         cache_dtype=jnp.float32, quantized=False,
                         planes=planes)


# -- planted faults: the comparison sees a fault in the carried state --------
def _run_two_in_one_row(world, gate_shift=0.0):
    """Two requests through ONE row; the gap of the second's tokens.
    ``gate_shift`` moves the gate's bias (program and reference alike)."""
    model, flat, _ = world
    flat = dict(flat, g_bias=(flat["g_bias"].astype(jnp.float32)
                              + gate_shift).astype(jnp.bfloat16))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    eng = _engine((model, flat, params), max_num_seqs=1)
    first, second = _ids(31, 16).tolist(), _ids(27, 17).tolist()
    eng.submit(first, max_new_tokens=6)
    rb = eng.submit(second, max_new_tokens=12)
    return _served_gap(flat, second, eng.run()[rb])


def test_planted_fault_no_reset_on_row_reuse_reads_wide(world, monkeypatch):
    sound = _run_two_in_one_row(world)
    real = pr.retention
    monkeypatch.setattr(pr, "retention", lambda *a, reset, **k: real(
        *a, reset=jnp.zeros_like(reset), **k))
    assert sound < 1e-4 < 0.01 < _run_two_in_one_row(world)


def test_planted_fault_decay_dropped_between_chunks_reads_wide(
        world, monkeypatch):
    """The state carried into a chunk without the decay to each token
    (``exp(G_t)`` left out of the inter-chunk term).  At the seeded gate
    (``g`` 0.98-0.9997) a toy's 27-token prompt hardly decays at all and a
    toy's served tokens do not change, so the bias is moved down by 5
    (``g`` ~0.3-0.95, in program and reference alike) and the logits are
    compared, as in ``test_state_planes_match_the_plain_reference``."""
    model, flat, _ = world
    flat = dict(flat, g_bias=(flat["g_bias"].astype(jnp.float32)
                              - 5.0).astype(jnp.bfloat16))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    ids = _ids(41)
    want = _reference_logits(flat, ids)

    def worst():
        with jax.default_matmul_precision("highest"):
            got, _ = _state_logits(model, params, ids, 8, 29)
        return float(np.abs(got - want).max())

    sound = worst()
    real_exp = jnp.exp

    class NoDecay:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            # chunk_step's decays of a [B, C, Hk] cumsum: exp(G_t) into the
            # chunk and exp(G_C - G_s) out of it
            return jnp.ones_like(x) if x.ndim == 3 else real_exp(x)

    monkeypatch.setattr(pr, "jnp", NoDecay())
    assert sound < 3e-5 < 3e-3 < worst()


def test_kernels_serve_the_engine_in_interpret_mode(monkeypatch):
    """Head size 128 (the kernels' own) through the engine with both Pallas
    rungs resolved, against the XLA anchors: same tokens."""
    cfg = dict(CFG, head_dim=128, hidden_size=128, num_attention_heads=2,
               num_key_value_heads=1, num_hidden_layers=1,
               intermediate_size=128)
    model = build_model(config=ref.model_config(cfg),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(cfg, w))(bench_weights.seed_words(5))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    prompt = _ids(11, 18).tolist()

    def serve():
        eng = DecodeEngine(model, params, ServingConfig(
            max_num_seqs=2, max_model_len=64, prefill_chunk=8),
            generation=GenerationConfig(max_new_tokens=5, do_sample=False,
                                        eos_token_id=None))
        rid = eng.submit(prompt, max_new_tokens=5)
        return eng.run()[rid]

    want = serve()
    from automodel_tpu.ops.kernel_lib import registry

    before = registry.resolved_rungs()
    monkeypatch.setattr(pk, "_INTERPRET", True)
    assert serve() == want
    after = registry.resolved_rungs()
    for rung in ("attention.retention_decode", "attention.retention_chunk"):
        assert after.get(rung, 0) > before.get(rung, 0)
