"""Multi-tenant serving: batched multi-LoRA decode on the grouped-GEMM
substrate (docs/guides/serving.md "Multi-tenant serving").

The anchor is the MULTI-LoRA PARITY ORACLE: a mixed batch over N tenants
(per-request ``adapter_id`` routed through the stacked A/B slabs with
grouped GEMMs) must be token-identical, per row, to that request alone
through a single-adapter MERGED-WEIGHTS engine — the two mathematically
equivalent LoRA execution strategies (docs/guides/peft.md "Merge vs
bypass") cross-checked through the full serving stack.  Base traffic
(id 0) must be token-identical to a plain adapter-free engine, and the
oracle is crossed with prefix caching (namespaced chains), int8 KV,
speculation, preemption pressure, and fleet replica-loss replay.

The hot-swap contract rides the ``adapter_load``/``adapter_swap`` fault
drills: a failed load is a typed :class:`AdapterLoadError` with every
slab byte untouched, and a failed swap mid-batch leaves in-flight rows
finishing token-identically under the OLD adapter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.analysis.jaxpr_audit import (
    assert_compiles_once,
    jaxpr_census,
)
from automodel_tpu.generation import GenerationConfig
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.lora_gmm import (
    multi_lora_delta,
    multi_lora_delta_reference,
)
from automodel_tpu.peft.lora import LoRAModel, PeftConfig
from automodel_tpu.serving import (
    AdapterLoadError,
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

LENS = [9, 6, 13, 5]
MAX_NEW = 8
RANK = 4
MIXED_IDS = [1, 2, 0, 1]      # two tenants + base sharing one batch


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    params = model.init(jax.random.key(0))
    # perturb so argmax isn't degenerate
    leaves, td = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(td, [
        l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    S = max(LENS)
    ids = np.zeros((len(LENS), S), np.int64)
    for b, n in enumerate(LENS):
        ids[b, :n] = rng.integers(1, 255, n)
    return ids


@pytest.fixture(scope="module")
def adapters(model_and_params):
    """Two trained-shaped LoRA trees with NONZERO B (init_lora is the
    identity — B=0 — so fresh trees would make every tenant the base
    model) plus the LoRAModel that defines merge_params."""
    model, _ = model_and_params
    pc = PeftConfig(dim=RANK, alpha=16)
    lm = LoRAModel(model, pc)
    base = lm.init_lora(jax.random.key(7))

    def tree(seed):
        return {k: {"A": v["A"],
                    "B": 0.2 * jax.random.normal(
                        jax.random.key(seed), v["B"].shape, v["B"].dtype)}
                for k, v in base.items()}

    return lm, pc, {1: tree(11), 2: tree(13)}


def _cfg(**kw):
    base = dict(kv_block_size=8, max_num_seqs=4, max_model_len=64,
                prefill_chunk=8)
    base.update(kw)
    return ServingConfig(**base)


def _engine(model_and_params, **kw):
    model, params = model_and_params
    return DecodeEngine(model, params, _cfg(**kw),
                        generation=GenerationConfig(max_new_tokens=MAX_NEW))


def _mt_engine(model_and_params, adapters, *, load=(1, 2), **kw):
    """A 2-tenant engine with both adapters loaded through the
    digest-verified hot-swap path."""
    kw.setdefault("max_adapters", 2)
    kw.setdefault("adapter_rank", RANK)
    eng = _engine(model_and_params, **kw)
    _, pc, trees = adapters
    for slot in load:
        eng.load_adapter(slot, trees[slot], name=f"tenant-{slot}",
                         scale=pc.scale)
    return eng


def _run_mixed(eng, prompts, aids=MIXED_IDS):
    rids = [eng.submit(prompts[b, :LENS[b]], adapter_id=aids[b])
            for b in range(len(LENS))]
    eng.run()
    return [list(eng.requests[r].out_tokens) for r in rids]


@pytest.fixture(scope="module")
def merged_oracle(model_and_params, prompts, adapters):
    """Per (row, adapter): that request ALONE through a single-adapter
    merged-weights engine — the strictest baseline (no batching, no
    bypass, no grouping)."""
    model, params = model_and_params
    lm, _, trees = adapters
    out = {}
    for b in range(len(LENS)):
        for aid in {0, *MIXED_IDS}:
            mp = (params if aid == 0 else
                  lm.merge_params({"base": params, "lora": trees[aid]}))
            eng = DecodeEngine(
                model, mp, _cfg(max_num_seqs=1),
                generation=GenerationConfig(max_new_tokens=MAX_NEW))
            out[(b, aid)] = np.asarray(
                eng.generate(prompts[b:b + 1, :LENS[b]])[0])
    return out


# ---------------------------------------------------------------------------
# The grouped-GEMM dispatch op
# ---------------------------------------------------------------------------
def test_grouped_delta_matches_gather_reference():
    """Sorted grouped dispatch == per-row gathered einsum, and slot-0
    rows (all-zero slabs) contribute an EXACTLY-zero delta."""
    rng = np.random.default_rng(0)
    B, S, fin, r, fout, E = 5, 3, 16, 4, 24, 4
    x = jnp.asarray(rng.standard_normal((B, S, fin)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((E, fin, r)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((E, r, fout)), jnp.float32)
    a = a.at[0].set(0.0)
    b = b.at[0].set(0.0)
    ids = jnp.asarray([2, 0, 1, 2, 0], jnp.int32)
    got = multi_lora_delta(x, a, b, ids)
    want = multi_lora_delta_reference(x, a, b, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[4]), 0.0)


# ---------------------------------------------------------------------------
# The multi-LoRA parity oracle
# ---------------------------------------------------------------------------
def test_base_only_traffic_token_identical_to_plain_engine(
        model_and_params, prompts, adapters):
    """An adapter-armed engine serving ONLY base traffic (id 0 routes
    through the all-zero slot-0 slabs) equals the adapter-free engine."""
    plain = _engine(model_and_params).generate(prompts, np.asarray(LENS))
    mt = _mt_engine(model_and_params, adapters).generate(
        prompts, np.asarray(LENS))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(mt))


def test_mixed_batch_parity_vs_merged_single_adapter_engines(
        model_and_params, prompts, adapters, merged_oracle):
    """THE ORACLE: every row of a mixed 2-tenants+base batch is token-
    identical to its request alone through the merged-weights engine."""
    eng = _mt_engine(model_and_params, adapters)
    outs = _run_mixed(eng, prompts)
    for b, (aid, got) in enumerate(zip(MIXED_IDS, outs)):
        np.testing.assert_array_equal(
            np.asarray(got, np.int32), merged_oracle[(b, aid)][:len(got)])
        assert len(got) == MAX_NEW
    pt = eng.stats()["multi_tenant"]["per_tenant"]
    assert pt[1]["finished"] == 2 and pt[2]["finished"] == 1
    assert pt[1]["tokens"] == 2 * MAX_NEW


def test_mixed_parity_under_prefix_caching(model_and_params, prompts,
                                           adapters):
    eng_off = _mt_engine(model_and_params, adapters)
    eng_on = _mt_engine(model_and_params, adapters, prefix_caching="on")
    assert _run_mixed(eng_on, prompts) == _run_mixed(eng_off, prompts)


def test_mixed_parity_under_speculation(model_and_params, prompts,
                                        adapters):
    """The spec_k+1 verify step carries the same adapter routing as the
    plain decode step — greedy output stays token-identical."""
    eng_off = _mt_engine(model_and_params, adapters)
    eng_spec = _mt_engine(model_and_params, adapters,
                          speculative="ngram", spec_k=2)
    assert _run_mixed(eng_spec, prompts) == _run_mixed(eng_off, prompts)


def test_mixed_parity_under_preemption_pressure(model_and_params, prompts,
                                                adapters):
    """An oversubscribed pool preempts mid-batch; recompute replay keeps
    each row's adapter id, so the mixed output is unchanged."""
    free = _run_mixed(_mt_engine(model_and_params, adapters), prompts)
    tight = _mt_engine(model_and_params, adapters, num_kv_blocks=9)
    assert _run_mixed(tight, prompts) == free
    assert tight.scheduler.preemptions >= 1


def test_mixed_int8_kv_token_match_bounded(model_and_params, prompts,
                                           adapters):
    fp32 = np.asarray(
        _run_mixed(_mt_engine(model_and_params, adapters), prompts),
        dtype=object)
    q = np.asarray(
        _run_mixed(_mt_engine(model_and_params, adapters,
                              kv_cache_dtype="int8"), prompts),
        dtype=object)
    match = np.mean([a == b for ra, rb in zip(fp32, q)
                     for a, b in zip(ra, rb)])
    assert match >= 0.9, f"int8 KV mixed-batch token match {match}"


@pytest.mark.fault
def test_fleet_replica_loss_replay_keeps_adapter_ids(
        model_and_params, prompts, adapters, merged_oracle, monkeypatch):
    """A 2-replica fleet with tenants loaded fleet-wide: a drilled
    ``fleet_replica_loss`` mid-decode replays the dead replica's adapter
    rows on the survivor (slot kept) token-identical to the oracle, and
    a healed replica re-admits with the peer's slabs + registry."""
    monkeypatch.setenv("AUTOMODEL_LOST_REPLICA", "0")
    model, params = model_and_params
    _, pc, trees = adapters
    fleet = FleetRouter(
        model, params,
        _cfg(max_adapters=2, adapter_rank=RANK, replicas=2),
        generation=GenerationConfig(max_new_tokens=MAX_NEW))
    entries = fleet.load_adapter(1, trees[1], scale=pc.scale)
    fleet.load_adapter(2, trees[2], scale=pc.scale)
    assert set(entries) == {0, 1}       # broadcast to both replicas
    rids = [fleet.submit(prompts[b, :LENS[b]], adapter_id=MIXED_IDS[b])
            for b in range(len(LENS))]
    for _ in range(3):
        fleet.step()
    fi.configure_faults("fleet_replica_loss:1")
    try:
        fleet.poll_health(step=3)
    finally:
        fi.reset_faults()
    assert not fleet.replicas[0].alive
    fleet.run()
    for b, rid in enumerate(rids):
        req = fleet.requests[rid]
        assert req.state is RequestState.FINISHED
        assert req.adapter_id == MIXED_IDS[b]    # replay kept the slot
        np.testing.assert_array_equal(
            np.asarray(req.out_tokens),
            merged_oracle[(b, MIXED_IDS[b])])
    # grow-back: the healed engine clones the survivor's tenants
    fleet.note_return(0)
    for p in range(4, 4 + 8):
        fleet.poll_health(step=p)
        if fleet.replicas[0].alive:
            break
    assert fleet.replicas[0].alive
    healed = fleet.replicas[0].engine.adapter_slots
    assert sorted(healed.loaded_slots()) == [1, 2]
    assert fleet.stats()["per_tenant"][1]["finished"] >= 2


# ---------------------------------------------------------------------------
# Prefix-cache namespacing
# ---------------------------------------------------------------------------
def test_prefix_chain_keys_namespaced_by_adapter():
    """Base (id 0) chain keys are byte-identical to the pre-adapter
    index; tenant chains seed from per-adapter roots so equal prompts
    never collide across tenants."""
    from automodel_tpu.serving import BlockAllocator

    idx = PrefixIndex(BlockAllocator(8), block_size=4)
    toks = list(range(1, 13))
    base = idx.chain_keys(toks)
    assert base == idx.chain_keys(toks, adapter_id=0)
    # the id-0 root is the un-namespaced None parent, byte-for-byte
    assert base[0] == idx.chain_key(None, toks[:4])
    k1, k2 = idx.chain_keys(toks, 1), idx.chain_keys(toks, 2)
    assert len({base[0], k1[0], k2[0]}) == 3
    assert not set(base) & set(k1) and not set(k1) & set(k2)
    assert PrefixIndex.root_key(0) is None
    assert PrefixIndex.root_key(3) == "adapter:3"


def test_prefix_reuse_within_tenant_never_across(model_and_params,
                                                 adapters):
    """Same tenant + same prompt -> full block reuse; a DIFFERENT tenant
    with the same prompt prefills cold (its KV depends on its adapter)."""
    eng = _mt_engine(model_and_params, adapters, prefix_caching="on")
    prompt = list(range(1, 17))         # two full 8-token blocks

    def reused(aid):
        before = eng.scheduler.prefix_tokens_reused
        rid = eng.submit(prompt, adapter_id=aid)
        eng.run()
        assert eng.requests[rid].state is RequestState.FINISHED
        return eng.scheduler.prefix_tokens_reused - before

    # a full-prompt hit still prefills the last token (it produces the
    # first logit), so warm reuse is len - 1
    assert reused(1) == 0               # cold: commits tenant-1's chain
    assert reused(1) == len(prompt) - 1     # warm within the tenant
    assert reused(2) == 0               # same prompt, other tenant: cold
    assert reused(0) == 0               # base: its own namespace, cold
    assert reused(0) == len(prompt) - 1     # and warm thereafter


# ---------------------------------------------------------------------------
# Hot-swap fault drills (L005: adapter_load / adapter_swap)
# ---------------------------------------------------------------------------
@pytest.mark.fault
def test_fault_adapter_load_typed_error_slot_stays_unloaded(
        model_and_params, prompts, adapters):
    """An armed ``adapter_load``: the load raises AdapterLoadError, no
    slab byte is written, submits naming the slot stay rejected, and the
    next un-drilled load succeeds."""
    _, pc, trees = adapters
    eng = _mt_engine(model_and_params, adapters, load=())
    slabs_before = eng.adapter_slots.slabs
    fi.configure_faults("adapter_load:1")
    try:
        with pytest.raises(AdapterLoadError, match="slot 1"):
            eng.load_adapter(1, trees[1], scale=pc.scale)
    finally:
        fi.reset_faults()
    assert eng.adapter_slots.slabs is slabs_before      # untouched
    assert not eng.adapter_slots.is_loaded(1)
    assert eng.adapter_slots.load_failures == 1
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(prompts[0, :LENS[0]], adapter_id=1)
    eng.load_adapter(1, trees[1], scale=pc.scale)       # clean retry
    assert eng.adapter_slots.is_loaded(1)


@pytest.mark.fault
def test_fault_adapter_swap_midbatch_keeps_old_adapter_token_identical(
        model_and_params, prompts, adapters, merged_oracle):
    """An armed ``adapter_swap`` mid-batch: the swap fails typed, the
    slot keeps serving its OLD adapter, and the in-flight mixed batch
    finishes token-identical to an undisturbed run."""
    _, pc, trees = adapters
    eng = _mt_engine(model_and_params, adapters)
    old_entry = eng.adapter_slots.loaded_slots()[1]
    rids = [eng.submit(prompts[b, :LENS[b]], adapter_id=MIXED_IDS[b])
            for b in range(len(LENS))]
    for _ in range(3):                  # batch is mid-decode
        eng.step()
    fi.configure_faults("adapter_swap:1")
    try:
        with pytest.raises(AdapterLoadError, match="swap"):
            eng.load_adapter(1, trees[2], scale=pc.scale)
    finally:
        fi.reset_faults()
    entry = eng.adapter_slots.loaded_slots()[1]
    assert entry["digest"] == old_entry["digest"]       # old adapter kept
    assert entry["version"] == old_entry["version"]
    assert eng.adapter_slots.swaps == 0
    assert eng.adapter_slots.load_failures == 1
    eng.run()
    for b, rid in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(eng.requests[rid].out_tokens),
            merged_oracle[(b, MIXED_IDS[b])])


# ---------------------------------------------------------------------------
# Compile-once + census across adapter churn
# ---------------------------------------------------------------------------
def test_adapter_churn_never_adds_a_program(model_and_params, prompts,
                                            adapters):
    """Load, serve, hot-swap, serve, remove, serve base: the engine ends
    with exactly the two step widths it started with, each compiled
    once — adapter churn is data, never shape."""
    _, pc, trees = adapters
    eng = _mt_engine(model_and_params, adapters, load=())
    eng.generate(prompts, np.asarray(LENS))             # base warm-up
    eng.load_adapter(1, trees[1], scale=pc.scale)       # add
    _run_mixed(eng, prompts, [1, 0, 1, 0])
    eng.load_adapter(1, trees[2], scale=pc.scale)       # swap
    _run_mixed(eng, prompts, [1, 1, 0, 0])
    assert eng.adapter_slots.swaps == 1
    eng.remove_adapter(1)                               # remove
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(prompts[0, :LENS[0]], adapter_id=1)
    eng.generate(prompts, np.asarray(LENS))
    assert sorted(eng._steps) == [1, 8]     # decode + prefill, nothing new
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"multi-LoRA step width={width}")


def test_adapter_decode_step_census_clean(model_and_params, adapters):
    """The adapter-enabled decode step lowers with no collectives and no
    host callbacks — the grouped dispatch (sort/bincount/gmm) is pure
    device work."""
    eng = _mt_engine(model_and_params, adapters, max_num_seqs=2)
    eng.submit([5, 6, 7], adapter_id=1)
    while not eng._steps.get(1):
        eng.step()
    fn = eng._steps[1]
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a))(eng.params, eng.pools,
                           np.zeros((2, 1), np.int32),
                           np.zeros((2, 1), np.int32),
                           np.zeros((2, 1), np.int32),
                           np.zeros((2, eng.max_blocks_per_seq), np.int32),
                           np.ones((2,), np.int32),
                           np.zeros((2,), np.int32),
                           np.zeros((2,), np.int32),
                           np.zeros((2,), np.int32),
                           np.zeros((2,), np.int32),      # prev_tok
                           np.zeros((2,), np.bool_),
                           np.zeros((2,), np.int32),
                           eng.adapter_slots.slabs)
    census = jaxpr_census(jaxpr)
    assert not census.collectives, census.collectives
    assert not census.host_callbacks


# ---------------------------------------------------------------------------
# Tenant quotas + the update_params hot-swap arm
# ---------------------------------------------------------------------------
def test_tenant_quota_defers_never_rejects(model_and_params, prompts,
                                           adapters):
    """tenant_quota=1: one tenant's burst holds at most one engine slot
    at a time (over-quota rows WAIT), yet every request finishes."""
    eng = _mt_engine(model_and_params, adapters, tenant_quota=1)
    rids = [eng.submit(prompts[b, :LENS[b]], adapter_id=1)
            for b in range(3)]
    rids.append(eng.submit(prompts[3, :LENS[3]]))       # base rides along
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        active_t1 = sum(1 for r in eng.scheduler.active
                        if r.adapter_id == 1)
        assert active_t1 <= 1, "tenant 1 exceeded its quota"
        steps += 1
        assert steps < 500
    for rid in rids:
        assert eng.requests[rid].state is RequestState.FINISHED
    s = eng.stats()["multi_tenant"]
    assert s["quota_deferrals"] >= 1
    assert s["per_tenant"][1]["finished"] == 3


def test_sjf_tenant_fair_share_admits_idle_tenant_first(
        model_and_params, prompts, adapters):
    """Under sjf, a tenant already holding a slot sees its next request's
    aged length scaled by (1 + active) — so with one free slot and two
    identical waiting requests, the IDLE tenant admits first even though
    the busy tenant submitted earlier."""
    eng = _mt_engine(model_and_params, adapters, max_num_seqs=2,
                     scheduler_policy="sjf")
    busy = eng.submit(prompts[2, :LENS[2]], adapter_id=1)
    eng.step()                          # tenant 1 now holds a slot
    r1 = eng.submit(prompts[1, :LENS[1]], adapter_id=1)   # earlier arrival
    r2 = eng.submit(prompts[1, :LENS[1]], adapter_id=2)   # idle tenant
    eng.step()                          # one free slot: fair-share decides
    assert eng.requests[r2].was_admitted
    assert not eng.requests[r1].was_admitted
    eng.run()                           # nobody starves
    for rid in (busy, r1, r2):
        assert eng.requests[rid].state is RequestState.FINISHED


def test_update_params_adapter_arm_and_guards(model_and_params, adapters):
    """``update_params(adapter_slot=k, adapters=...)`` is the hot-swap
    arm; argument-free calls stay a loud error; weight syncs and adapter
    loads are independently counted."""
    _, pc, trees = adapters
    eng = _mt_engine(model_and_params, adapters, load=())
    eng.update_params(adapter_slot=1, adapters=trees[1],
                      adapter_name="t1", adapter_scale=pc.scale)
    assert eng.adapter_slots.loaded_slots()[1]["name"] == "t1"
    assert eng.weight_syncs == 0        # no base-weight sync happened
    with pytest.raises(ValueError):
        eng.update_params()
    base_only = _engine(model_and_params)
    with pytest.raises(ValueError, match="max_adapters"):
        base_only.load_adapter(1, trees[1])
    with pytest.raises(ValueError, match="adapter"):
        base_only.submit([5, 6, 7], adapter_id=1)
    with pytest.raises(AdapterLoadError, match="out of range"):
        eng.load_adapter(3, trees[1])   # beyond max_adapters=2


def test_rollout_generate_routes_one_tenant(model_and_params, adapters):
    """``rollout.generate(..., adapter_id=k)`` rolls the whole batch out
    under one tenant and reports per-tenant token deltas."""
    from automodel_tpu.post_training.rollout import (
        RolloutConfig,
        RolloutWorker,
    )

    _, pc, trees = adapters
    eng = _mt_engine(model_and_params, adapters)
    rc = RolloutConfig(group_size=2, rollout_batch_size=2,
                       max_new_tokens=4, max_prompt_len=8)
    worker = RolloutWorker(eng, rc)
    rb = worker.generate([[5, 6, 7], [8, 9]], adapter_id=2)
    assert list(rb.stats["per_tenant_tokens"]) == [2]
    assert rb.stats["per_tenant_tokens"][2] == rb.stats["tokens"]


# ---------------------------------------------------------------------------
# Config hygiene: load-time + CLI-override guards
# ---------------------------------------------------------------------------
def test_adapter_config_validation_and_cli_reval(tmp_path):
    from automodel_tpu.config.arg_parser import parse_args_and_load_config
    from automodel_tpu.config.loader import load_yaml_config

    for field in ("max_adapters", "adapter_rank", "tenant_quota"):
        with pytest.raises(ValueError, match=field):
            ServingConfig(**{field: 0})
        p = tmp_path / "serve.yaml"
        p.write_text(f"serving:\n  {field}: -1\n")
        with pytest.raises(ValueError, match=rf"serving\.{field}"):
            load_yaml_config(str(p))
    yaml = "examples/serve/tiny_llama_serve.yaml"
    cfg = parse_args_and_load_config(
        ["--config", yaml, "--serving.max_adapters", "4",
         "--serving.tenant_quota", "2"])
    assert cfg.get("serving.max_adapters") == 4
    assert cfg.get("serving.tenant_quota") == 2
    # the post-override re-validation catches a bad CLI value too
    with pytest.raises(ValueError, match=r"serving\.max_adapters"):
        parse_args_and_load_config(
            ["--config", yaml, "--serving.max_adapters", "0"])
