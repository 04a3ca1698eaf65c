"""Prefix caching: content-hash block sharing, copy-on-write forks, and
the group-level rollout fork.

The anchor is the same parity oracle as ``test_serving.py``, one level
up: greedy decode with ``serving.prefix_caching: on`` must be
token-identical to the cache-off engine (and to ``generate()``) on every
drilled path — batch-of-one, mixed shared-prefix batches, warm-cache
reruns, preemption pressure, int8 KV, a fleet replica-loss replay, and
both injected faults (``kv_prefix_lookup`` / ``kv_cow_fork``).  The cache
may only ever change WHERE tokens come from, never WHICH tokens come out;
``allocator.all_free`` stays the leak oracle after every terminal state
with sharing enabled.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.analysis.jaxpr_audit import (
    assert_compiles_once,
    jaxpr_census,
)
from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.serving import (
    BlockAllocator,
    DecodeEngine,
    FleetRouter,
    PrefixIndex,
    RequestState,
    ServingConfig,
)
from automodel_tpu.utils import fault_injection as fi

CFG = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)

BS = 8          # kv_block_size in every engine below
MAX_NEW = 8


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    params = model.init(jax.random.key(0))
    leaves, td = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(td, [
        l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])
    return model, params


@pytest.fixture(scope="module")
def shared_prompts():
    """Mixed-length prompts over one 24-token (3 full blocks) shared
    prefix — the system-prompt traffic shape prefix caching targets."""
    rng = np.random.default_rng(11)
    shared = rng.integers(1, 255, 3 * BS).tolist()
    return [shared + rng.integers(1, 255, k).tolist() for k in (3, 5, 1, 7)]


def _cfg(**kw):
    base = dict(kv_block_size=BS, max_num_seqs=4, max_model_len=64,
                prefill_chunk=8)
    base.update(kw)
    return ServingConfig(**base)


def _engine(model_and_params, **kw):
    model, params = model_and_params
    return DecodeEngine(model, params, _cfg(**kw),
                        generation=GenerationConfig(max_new_tokens=MAX_NEW))


def _run_prompts(eng, prompts):
    for p in prompts:
        eng.submit(list(p))
    return eng.run()


# ---------------------------------------------------------------------------
# Allocator refcounts + PrefixIndex units (pure host, no model)
# ---------------------------------------------------------------------------
def test_allocator_refcount_shared_block_lifecycle():
    alloc = BlockAllocator(8)
    [b] = alloc.allocate(1)
    assert alloc.ref_count(b) == 1 and not alloc.all_free
    alloc.incref([b])                      # a second holder (a prefix hit)
    assert alloc.ref_count(b) == 2
    alloc.free([b])                        # holder 1's decref: still live
    assert alloc.ref_count(b) == 1 and not alloc.all_free
    alloc.free([b])                        # last holder: back on the ledger
    assert alloc.ref_count(b) == 0 and alloc.all_free
    # the O(1) double-free mirror extends to shared blocks: one decref per
    # holder is legal, one more past zero is the loud error
    with pytest.raises(ValueError, match="double free"):
        alloc.free([b])
    with pytest.raises(ValueError, match="incref of non-live"):
        alloc.incref([b])
    assert alloc.all_free


def test_prefix_index_chain_lookup_and_lru_eviction():
    alloc = BlockAllocator(8)
    idx = PrefixIndex(alloc, block_size=4)
    toks = list(range(40, 52))                      # 3 full blocks of 4
    keys = idx.chain_keys(toks)
    assert len(keys) == 3 and len(set(keys)) == 3
    # the chain is position-dependent: same content under another parent
    # hashes differently
    assert idx.chain_keys(toks[4:8]) != [keys[1]]
    assert idx.peek(keys) == 0 and idx.acquire(keys) == []
    blocks = alloc.allocate(3)
    parent = None
    for i, b in enumerate(blocks):
        parent = idx.commit(parent, toks[4 * i:4 * (i + 1)], b)
    assert parent == keys[-1] and idx.cached_blocks == 3
    alloc.free(blocks)                   # refcount zero -> parked warm
    assert alloc.all_free and idx.cached_blocks == 3
    assert idx.peek(keys) == 3
    chain = idx.acquire(keys)            # revives all three at refcount 1
    assert chain == blocks and not alloc.all_free
    assert idx.peek(keys[:2] + ["nope"]) == 2
    alloc.free(chain)
    # allocator pressure evicts warm blocks LRU-first, never a live one
    got = alloc.allocate(7)              # the whole pool: must evict all 3
    assert sorted(got) == list(range(1, 8)) and idx.cached_blocks == 0
    assert idx.evictions == 3
    alloc.free(got)


def test_prefix_index_lru_blocks_bound_and_flush():
    alloc = BlockAllocator(10)
    idx = PrefixIndex(alloc, block_size=2, lru_blocks=2)
    blocks = alloc.allocate(4)
    parent = None
    for i, b in enumerate(blocks):
        parent = idx.commit(parent, [7 + i, 9 + i], b)
    alloc.free(blocks)                   # 4 candidates, LRU bound is 2
    assert idx.cached_blocks == 2 and idx.evictions == 2
    assert alloc.all_free
    idx.flush()
    assert idx.cached_blocks == 0 and alloc.all_free
    assert alloc.allocate(9) and True    # every block reachable post-flush


# ---------------------------------------------------------------------------
# The parity oracle, cache on
# ---------------------------------------------------------------------------
def test_cache_on_token_identical_mixed_batch_and_generate(
        model_and_params, shared_prompts):
    """Cache-on == cache-off == generate() on a mixed shared-prefix batch,
    and the cache actually fired (hits, saved tokens, all_free after)."""
    model, params = model_and_params
    S = max(len(p) for p in shared_prompts)
    ids = np.zeros((len(shared_prompts), S), np.int64)
    for b, p in enumerate(shared_prompts):
        ids[b, :len(p)] = p
    lens = np.asarray([len(p) for p in shared_prompts])
    oracle = np.asarray(generate(
        model, params, ids, prompt_lens=lens,
        config=GenerationConfig(max_new_tokens=MAX_NEW)))
    off = _engine(model_and_params).generate(ids, lens)
    on_eng = _engine(model_and_params, prefix_caching="on")
    on = on_eng.generate(ids, lens)
    np.testing.assert_array_equal(off, oracle)
    np.testing.assert_array_equal(on, oracle)
    s = on_eng.stats()
    assert s["prefix_cache"]["hits"] >= 1
    assert s["prefill_tokens_saved"] >= 2 * 3 * BS   # >=2 followers reuse
    assert 0.0 < s["cache_hit_rate"] <= 1.0
    assert on_eng.allocator.all_free


def test_warm_cache_rerun_batch_of_one_identical(model_and_params,
                                                 shared_prompts):
    """A COLD run then a WARM rerun of the same prompt, batch-of-one: the
    warm pass reuses every full prompt block and emits the same tokens."""
    eng = _engine(model_and_params, max_num_seqs=1, prefix_caching="on")
    p = shared_prompts[3]
    first = _run_prompts(eng, [p])
    saved0 = eng.stats()["prefill_tokens_saved"]
    second = _run_prompts(eng, [p])
    assert second[1] == first[0]
    assert eng.stats()["prefill_tokens_saved"] - saved0 \
        >= (len(p) // BS) * BS - 1
    assert eng.allocator.all_free


def test_cache_on_under_preemption_pressure(model_and_params,
                                            shared_prompts):
    """A pool too small for full residency preempts under sharing; the
    recompute replay may legitimately re-hit the cache — output unchanged
    vs the cache-off engine under the same pressure."""
    kw = dict(max_model_len=40, num_kv_blocks=12)
    off = _engine(model_and_params, **kw)
    on = _engine(model_and_params, prefix_caching="on", **kw)
    out_off = _run_prompts(off, shared_prompts)
    out_on = _run_prompts(on, shared_prompts)
    assert out_on == out_off
    assert on.allocator.all_free and off.allocator.all_free


def test_cache_on_int8_kv_scales_ride_shared_blocks(model_and_params,
                                                    shared_prompts):
    """int8 KV: the per-slot scale planes are addressed by the same block
    ids as the data, so a shared (or COW-copied) block carries its scales
    — cache-on int8 matches cache-off int8 exactly."""
    off = _engine(model_and_params, kv_cache_dtype="int8")
    on = _engine(model_and_params, kv_cache_dtype="int8",
                 prefix_caching="on")
    out_off = _run_prompts(off, shared_prompts)
    out_on = _run_prompts(on, shared_prompts)
    assert out_on == out_off
    assert on.stats()["prefix_cache"]["hits"] >= 1
    assert on.allocator.all_free


# ---------------------------------------------------------------------------
# Copy-on-write forks + the group-level rollout fork
# ---------------------------------------------------------------------------
def test_identical_prompts_cow_fork_one_prefill_per_group(model_and_params):
    """G identical block-aligned prompts (a GRPO group): the followers hit
    the full chain, fork the last block copy-on-write, and the group pays
    ~1 prefill — token-identical to cache-off."""
    G = 4
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 255, 3 * BS).tolist()
    off = _engine(model_and_params)
    on = _engine(model_and_params, prefix_caching="on")
    out_off = _run_prompts(off, [prompt] * G)
    out_on = _run_prompts(on, [prompt] * G)
    assert out_on == out_off
    s = on.stats()
    assert s["prefix_cache"]["cow_forks"] == G - 1
    assert s["prefix_cache"]["deferrals"] >= 1   # followers waited, once
    # each follower recomputes exactly the forked block's last token, so
    # the exact bound is (G-1)*(L-1) — within 1/L of the issue's
    # (G-1)/G-of-group-tokens target
    L = len(prompt)
    assert s["prefill_tokens_saved"] >= (G - 1) * (L - 1)
    assert s["prefill_tokens_saved"] >= 0.9 * (G - 1) / G * (G * L)
    assert on.allocator.all_free


def test_grpo_rollout_group_fork_stats(model_and_params):
    """The rollout layer gets the group fork for free: a grouped rollout
    through a prefix-cached engine reports the saved prefill tokens."""
    from automodel_tpu.post_training.rollout import (
        RolloutConfig,
        RolloutWorker,
    )

    model, params = model_and_params
    G = 4
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 255, 2 * BS).tolist() for _ in range(2)]
    outs = {}
    for mode in ("off", "on"):
        eng = DecodeEngine(
            model, params, _cfg(prefix_caching=mode),
            generation=GenerationConfig(max_new_tokens=4))
        worker = RolloutWorker(eng, RolloutConfig(
            group_size=G, max_new_tokens=4, max_prompt_len=2 * BS,
            eos_token_id=None))
        batch = worker.generate(prompts)
        outs[mode] = batch.completions
        if mode == "on":
            L = 2 * BS
            assert batch.stats["prefill_tokens_saved"] \
                >= len(prompts) * (G - 1) * (L - 1)
            assert batch.stats["cache_hit_rate"] > 0.0
        else:
            assert batch.stats["prefill_tokens_saved"] == 0.0
        assert eng.allocator.all_free
    assert outs["on"] == outs["off"]     # greedy group members identical


# ---------------------------------------------------------------------------
# Fault drills
# ---------------------------------------------------------------------------
@pytest.mark.fault
def test_kv_prefix_lookup_fault_degrades_to_cold_prefill(
        model_and_params, shared_prompts):
    """An armed ``kv_prefix_lookup`` on a would-be hit degrades to a cold
    prefill byte-identically — the cache is an optimization, never a
    correctness dependency."""
    baseline = _run_prompts(_engine(model_and_params), shared_prompts)
    eng = _engine(model_and_params, prefix_caching="on")
    fi.configure_faults("kv_prefix_lookup:1")
    try:
        out = _run_prompts(eng, shared_prompts)
    finally:
        fi.reset_faults()
    assert out == baseline
    s = eng.stats()["prefix_cache"]
    assert s["misses"] >= 1              # the drilled lookup counted a miss
    assert eng.allocator.all_free


@pytest.mark.fault
def test_kv_cow_fork_fault_never_corrupts_shared_block(model_and_params):
    """An armed ``kv_cow_fork`` on a fully-cached sequence returns the
    acquired chain's refs and falls back to a cold prefill — the shared
    source block is never touched, and the group still converges
    token-identical."""
    G = 3
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 255, 2 * BS).tolist()
    baseline = _run_prompts(_engine(model_and_params), [prompt] * G)
    eng = _engine(model_and_params, prefix_caching="on")
    fi.configure_faults("kv_cow_fork:1")
    try:
        out = _run_prompts(eng, [prompt] * G)
    finally:
        fi.reset_faults()
    assert out == baseline
    s = eng.stats()["prefix_cache"]
    assert s["cow_fork_failures"] == 1
    assert s["cow_forks"] == G - 2       # the other follower still forked
    assert eng.allocator.all_free


@pytest.mark.fault
def test_cache_on_fleet_replica_loss_replay(model_and_params,
                                            shared_prompts, monkeypatch):
    """A prefix-cached fleet losing a replica mid-traffic replays on the
    survivor token-identically — the dead replica's shared blocks die with
    its pools (chain state reset by the harvest) and every allocator ends
    ``all_free``."""
    monkeypatch.setenv("AUTOMODEL_LOST_REPLICA", "0")
    model, params = model_and_params
    baseline = _run_prompts(_engine(model_and_params), shared_prompts)
    fleet = FleetRouter(
        model, params,
        _cfg(replicas=2, fleet_probation_polls=2, prefix_caching="on"),
        generation=GenerationConfig(max_new_tokens=MAX_NEW))
    rids = [fleet.submit(list(p)) for p in shared_prompts]
    for _ in range(3):
        fleet.step()
    fi.configure_faults("fleet_replica_loss:1")
    try:
        fleet.poll_health(step=3)
    finally:
        fi.reset_faults()
    assert not fleet.replicas[0].alive
    fleet.run()
    for i, rid in enumerate(rids):
        req = fleet.requests[rid]
        assert req.state is RequestState.FINISHED
        assert list(req.out_tokens) == baseline[rids[i]]
    assert fleet.all_free()
    assert fleet.stats()["prefill_tokens_saved"] >= 0


@pytest.mark.fault
def test_preemption_drill_with_sharing_all_free(model_and_params,
                                                shared_prompts):
    """The drilled ``serve_block_alloc`` exhaustion under sharing: the
    preempted row's decrefs never strand a shared block, output is
    unchanged, and the pool drains to ``all_free``."""
    baseline = _run_prompts(_engine(model_and_params), shared_prompts)
    eng = _engine(model_and_params, prefix_caching="on")
    fi.configure_faults("serve_block_alloc:4")
    try:
        out = _run_prompts(eng, shared_prompts)
    finally:
        fi.reset_faults()
    assert out == baseline
    assert eng.scheduler.preemptions >= 1
    assert eng.allocator.all_free


# ---------------------------------------------------------------------------
# Compile-once / census, watchdog flush, admission guard, config hygiene
# ---------------------------------------------------------------------------
def test_compile_once_across_hits_misses_and_forks(model_and_params,
                                                   shared_prompts):
    """Cache hits, misses, COW forks and the warm rerun all ride the same
    two compiled programs (widths 1 and prefill_chunk), and the decode
    step's census stays collective- and callback-free with the COW-copy
    args in the signature."""
    eng = _engine(model_and_params, prefix_caching="on")
    _run_prompts(eng, shared_prompts)                     # misses + hits
    aligned = shared_prompts[0][:3 * BS]                  # fully cached now
    _run_prompts(eng, [aligned] * 2)                      # COW forks
    assert eng.stats()["prefix_cache"]["cow_forks"] >= 1
    assert sorted(eng._steps) == [1, 8]
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"prefix-cached step width={width}")
    fn = eng._steps[1]
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a))(eng.params, eng.pools,
                           np.zeros((4, 1), np.int32),
                           np.zeros((4, 1), np.int32),
                           np.zeros((4, 1), np.int32),
                           np.zeros((4, eng.max_blocks_per_seq), np.int32),
                           np.ones((4,), np.int32),
                           np.zeros((4,), np.int32),
                           np.zeros((4,), np.int32),
                           np.zeros((4,), np.int32),
                           np.zeros((4,), np.int32),      # prev_tok
                           np.zeros((4,), np.bool_))
    census = jaxpr_census(jaxpr)
    assert not census.collectives, census.collectives
    assert not census.host_callbacks


def test_watchdog_recovery_flushes_stale_index(model_and_params,
                                               shared_prompts):
    """Pool rebuild zeroes cached contents, so recovery must flush the
    index — a post-recovery run re-misses (no stale garbage hit) and still
    matches the cache-off output."""
    baseline = _run_prompts(_engine(model_and_params), shared_prompts)
    eng = _engine(model_and_params, prefix_caching="on")
    out1 = _run_prompts(eng, shared_prompts)
    assert eng.prefix_index.cached_blocks > 0
    eng._watchdog_recover("drill: rebuild pools under a warm cache")
    assert eng.prefix_index.cached_blocks == 0
    assert eng.allocator.all_free
    out2 = _run_prompts(eng, shared_prompts)
    assert out1 == baseline
    assert list(out2.values())[-len(shared_prompts):] \
        == list(baseline.values())
    assert eng.allocator.all_free


def test_admission_guard_discounts_cached_prefix(model_and_params):
    """A prompt whose worst case exceeds the pool is a ValueError cold —
    but once its prefix is cached, admission discounts the shared blocks
    and accepts it (the pool-pressure machinery governs actual growth);
    an abort then drains back to ``all_free``."""
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 255, 3 * BS).tolist()     # 3 full blocks
    # pool: 6 usable blocks.  prompt + 40 new tokens = 64 -> 8 blocks:
    # rejected cold, admitted once the 3 prompt blocks are cached
    # (worst 8 - (3 - 1) = 6).  prompt + 96 = 120 -> 15 blocks: a loud
    # caller bug even fully discounted (13 > 6).
    kw = dict(max_num_seqs=2, num_kv_blocks=7, max_model_len=128)
    off = _engine(model_and_params, **kw)
    with pytest.raises(ValueError, match="KV blocks"):
        off.submit(list(prompt), max_new_tokens=40)
    on = _engine(model_and_params, prefix_caching="on", **kw)
    on.submit(list(prompt), max_new_tokens=8)
    on.run()                                           # warms the cache
    with pytest.raises(ValueError, match="KV blocks"):
        on.submit(list(prompt), max_new_tokens=96)
    rid = on.submit(list(prompt), max_new_tokens=40)   # discounted: admits
    on.abort(rid)
    assert on.requests[rid].state is RequestState.ABORTED
    assert on.allocator.all_free


def test_prefix_config_validation_and_cli_reval(tmp_path):
    from automodel_tpu.config.arg_parser import parse_args_and_load_config
    from automodel_tpu.config.loader import load_yaml_config

    with pytest.raises(ValueError, match="prefix_caching"):
        ServingConfig(prefix_caching="sometimes")
    with pytest.raises(ValueError, match="prefix_lru_blocks"):
        ServingConfig(prefix_lru_blocks=0)
    # YAML 1.1 bools normalize like kernels.autotune
    assert ServingConfig(prefix_caching=True).prefix_caching == "on"
    assert ServingConfig(prefix_caching=False).prefix_caching == "off"
    assert ServingConfig(prefix_caching="null").prefix_caching is None
    p = tmp_path / "serve.yaml"
    p.write_text("serving:\n  prefix_caching: true\n"
                 "  prefix_lru_blocks: 32\n")
    cfg = load_yaml_config(str(p))
    assert cfg.get("serving.prefix_caching") is True   # normalized at use
    p.write_text("serving:\n  prefix_caching: maybe\n")
    with pytest.raises(ValueError, match=r"serving\.prefix_caching"):
        load_yaml_config(str(p))
    p.write_text("serving:\n  prefix_lru_blocks: -1\n")
    with pytest.raises(ValueError, match=r"serving\.prefix_lru_blocks"):
        load_yaml_config(str(p))
    yaml = "examples/serve/tiny_llama_serve.yaml"
    cfg = parse_args_and_load_config(
        ["--config", yaml, "--serving.prefix_caching", "on",
         "--serving.prefix_lru_blocks", "16"])
    assert cfg.get("serving.prefix_caching") == "on"
    assert cfg.get("serving.prefix_lru_blocks") == 16
    with pytest.raises(ValueError, match=r"serving\.prefix_caching"):
        parse_args_and_load_config(
            ["--config", yaml, "--serving.prefix_caching", "sometimes"])
    scfg = dataclasses.replace(ServingConfig(), prefix_caching="on",
                               prefix_lru_blocks=16)
    assert scfg.prefix_caching == "on"
