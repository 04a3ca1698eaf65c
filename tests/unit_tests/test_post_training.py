"""Post-training subsystem: the sharding-preserving logprob pass, GRPO/DPO
losses + steps, the rollout layer's weight handoff + fault containment,
and the recipes' e2e learning + checkpoint round-trip.

Anchors (the ISSUE-15 acceptance surface):

* logprob parity vs a dense ``jax.nn.log_softmax`` oracle — unsharded AND
  on dp2xtp2 (< 1e-4; measured ~1e-6), padded and packed rows EXACT;
* the logprob pass adds NO new collective kinds vs the train forward
  (dp2xtp2 census, jaxpr + HLO);
* ``assert_compiles_once`` across repeated rollout→train cycles;
* DPO loss/grad parity vs an independent dense reference < 1e-4 on
  dp2xtp2;
* GRPO e2e on the mock model: the seeded length-target reward measurably
  improves over >= 20 steps, rollouts generated by the ENGINE from live
  params (weight handoff asserted bitwise after sync);
* both recipes checkpoint/resume through the async protocol with RL
  state round-tripping exactly;
* fault drills (raise mode): rollout_weight_sync / rollout_engine_step /
  reward_fn — typed aborts, blocks reclaimed, training state untouched,
  next rollout clean.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.analysis.jaxpr_audit import (
    assert_compiles_once,
    census_of,
)
from automodel_tpu.distributed.mesh import MeshManager
from automodel_tpu.distributed.shardings import build_parallel_plan
from automodel_tpu.generation import GenerationConfig
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.optim import build_optimizer
from automodel_tpu.post_training.logprobs import (
    IGNORE_INDEX,
    build_logprob_fn,
    completion_logprobs,
    make_sequence_batch,
)
from automodel_tpu.post_training.losses import (
    PT_ALGORITHMS,
    dpo_losses,
    group_normalized_advantages,
    grpo_token_objective,
)
from automodel_tpu.post_training.rollout import (
    REWARD_SOURCES,
    RolloutConfig,
    RolloutError,
    RolloutWorker,
    build_rollout_config,
    compute_rewards,
)
from automodel_tpu.post_training.steps import (
    build_dpo_step,
    build_grpo_step,
)
from automodel_tpu.serving.engine import DecodeEngine, ServingConfig
from automodel_tpu.utils import fault_injection as fi

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GRPO_YAML = os.path.join(ROOT, "examples", "rl", "tiny_llama_grpo_mock.yaml")
DPO_YAML = os.path.join(ROOT, "examples", "rl", "tiny_llama_dpo_mock.yaml")

CFG = LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG, param_dtype=jnp.float32,
                            compute_dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(3)
    seqs = [[int(t) for t in rng.integers(2, 63, n)]
            for n in (9, 6, 12, 10)]
    return seqs, [4, 3, 5, 4]


def _dense_oracle(model, params, batch):
    """Per-token logprobs via the FULL logit tensor + log_softmax — the
    independent reference the chunked/sharded pass must match."""
    out = model(params, jnp.asarray(batch["input_ids"]),
                position_ids=jnp.asarray(batch["position_ids"]),
                segment_ids=(jnp.asarray(batch["segment_ids"])
                             if batch.get("segment_ids") is not None
                             else None))
    logsm = np.asarray(jax.nn.log_softmax(
        jnp.asarray(out["logits"]).astype(jnp.float32), axis=-1))
    lab = np.asarray(batch["labels"])
    ref = np.zeros(lab.shape, np.float32)
    m = lab != IGNORE_INDEX
    b_idx, s_idx = np.nonzero(m)
    ref[b_idx, s_idx] = logsm[b_idx, s_idx, lab[b_idx, s_idx]]
    return ref


# ---------------------------------------------------------------------------
# Logprob pass: parity, padding, packing
# ---------------------------------------------------------------------------
def test_logprobs_match_dense_log_softmax_oracle(model, params, sequences):
    seqs, plens = sequences
    batch = make_sequence_batch(seqs, plens, pad_to=16)
    lp = np.asarray(build_logprob_fn(model)(params, batch))
    ref = _dense_oracle(model, params, batch)
    assert np.abs(lp - ref).max() < 1e-4
    # supervised-position count: len(seq) - prompt_len per row
    lab = batch["labels"]
    for b, (s, p) in enumerate(zip(seqs, plens)):
        assert (lab[b] != IGNORE_INDEX).sum() == len(s) - p


def test_logprobs_padded_rows_exact(model, params, sequences):
    """Right-padding cannot change any valid position's value (causal
    attention + ignored pad labels).  The two pad widths are two compiled
    programs, and XLA promises no bitwise equality across shapes (the
    reduction blocking follows the shape), so "same value" is held to
    4 float32 ulp — a leaked pad token would move values by far more."""
    seqs, plens = sequences
    tight = make_sequence_batch(seqs, plens)            # S = longest
    wide = make_sequence_batch(seqs, plens, pad_to=24)  # extra pads
    fn = build_logprob_fn(model)
    lp_t = np.asarray(fn(params, tight))
    lp_w = np.asarray(fn(params, wide))
    S = lp_t.shape[1]
    assert lp_t.dtype == lp_w.dtype == np.float32
    np.testing.assert_array_max_ulp(lp_t, lp_w[:, :S], maxulp=4)
    assert np.all(lp_w[:, S:] == 0.0)                   # pads contribute 0


def test_logprobs_packed_rows_exact(model, params):
    """Two sequences packed into one row (segment_ids + per-segment
    positions) produce EXACTLY the per-sequence values of the unpacked
    rows — the packed-batch contract."""
    rng = np.random.default_rng(7)
    a = [int(t) for t in rng.integers(2, 63, 8)]
    b = [int(t) for t in rng.integers(2, 63, 6)]
    una = make_sequence_batch([a], [3], pad_to=8)
    unb = make_sequence_batch([b], [2], pad_to=8)
    lp_a = np.asarray(completion_logprobs(model, params, una))
    lp_b = np.asarray(completion_logprobs(model, params, unb))

    packed = {
        "input_ids": np.asarray([a + b], np.int32),
        "labels": np.concatenate(
            [una["labels"][:, :8], unb["labels"][:, :6]], axis=1),
        "position_ids": np.asarray(
            [list(range(8)) + list(range(6))], np.int32),
        "segment_ids": np.asarray([[1] * 8 + [2] * 6], np.int32),
    }
    lp_p = np.asarray(completion_logprobs(model, params, packed))
    np.testing.assert_array_equal(lp_p[0, :8], lp_a[0, :8])
    np.testing.assert_array_equal(lp_p[0, 8:], lp_b[0, :6])


def test_make_sequence_batch_validation():
    with pytest.raises(ValueError, match="exceeds pad_to"):
        make_sequence_batch([[1, 2, 3, 4]], [2], pad_to=3)
    with pytest.raises(ValueError, match="prompt_len"):
        make_sequence_batch([[1, 2]], [3])
    with pytest.raises(ValueError, match="no sequences"):
        make_sequence_batch([], [])


# ---------------------------------------------------------------------------
# dp2xtp2: sharded parity + the no-new-collective-kinds census pin
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dp2xtp2(model):
    mm = MeshManager(dp_size=2, tp_size=2, devices=jax.devices()[:4])
    return build_parallel_plan(model, mm)


def test_logprobs_sharded_parity_dp2xtp2(model, params, sequences, dp2xtp2):
    seqs, plens = sequences
    plan = dp2xtp2
    sharded = jax.device_put(params, plan.param_sharding)
    batch = make_sequence_batch(seqs, plens, pad_to=16)
    lp = np.asarray(build_logprob_fn(model, plan)(sharded, batch))
    ref = _dense_oracle(model, params, batch)
    assert np.abs(lp - ref).max() < 1e-4


def test_logprob_pass_adds_no_new_collective_kinds_dp2xtp2(model, dp2xtp2):
    """THE census satellite: every collective kind (per mesh axis) the
    logprob pass lowers to — jaxpr AND optimized HLO — already appears in
    the train step's forward (the fused-linear-CE step the golden
    censuses pin).  The unshard-free design claim, statically checked."""
    from automodel_tpu.loss.linear_ce import FusedLinearCrossEntropy
    from automodel_tpu.training.train_step import build_train_step

    plan = dp2xtp2
    fns = build_train_step(
        model, build_optimizer(name="adamw", lr=1e-3),
        loss_fn=FusedLinearCrossEntropy(chunk_len=16), plan=plan)

    def _abs(tree, shardings):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            tree, shardings)

    abs_params = _abs(model.abstract_params(), plan.param_sharding)
    abs_opt = _abs(jax.eval_shape(fns.init_opt_state, abs_params),
                   fns.opt_state_sharding)
    tok = jax.ShapeDtypeStruct((1, 4, 16), jnp.int32,
                               sharding=fns.microbatch_sharding)
    train_census = census_of(
        fns.train_step, abs_params, abs_opt,
        {"input_ids": tok, "labels": tok}, mesh=plan.mesh)

    lp_fn = build_logprob_fn(model, plan)
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(plan.mesh, PartitionSpec())
    row = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=rep)
    lp_census = census_of(
        lp_fn, abs_params,
        {"input_ids": row, "labels": row, "position_ids": row},
        mesh=plan.mesh)

    def kinds(table):
        return {(kind, axis) for kind, per in (table or {}).items()
                for axis in per}

    new_jaxpr = kinds(lp_census.collectives) - kinds(train_census.collectives)
    assert not new_jaxpr, (
        f"logprob pass introduced new jaxpr collective kinds: {new_jaxpr}")
    new_hlo = (kinds(lp_census.hlo_collectives)
               - kinds(train_census.hlo_collectives))
    assert not new_hlo, (
        f"logprob pass introduced new HLO collective kinds: {new_hlo}")


# ---------------------------------------------------------------------------
# Losses: units + DPO parity vs an independent reference
# ---------------------------------------------------------------------------
def test_group_normalized_advantages():
    r = jnp.asarray([1.0, 3.0, -2.0, -2.0, 5.0, 1.0], jnp.float32)
    adv = np.asarray(group_normalized_advantages(r, 2))
    g = np.asarray(r, np.float32).reshape(3, 2)
    for i in range(3):
        assert abs(adv.reshape(3, 2)[i].sum()) < 1e-5   # mean 0 per group
    # identical rewards in a group -> EXACTLY zero advantages (eps guard)
    flat = np.asarray(group_normalized_advantages(
        jnp.asarray([2.0, 2.0, 2.0, 2.0], jnp.float32), 4))
    assert np.all(flat == 0.0)
    with pytest.raises(ValueError, match="divisible"):
        group_normalized_advantages(jnp.zeros(5), 2)


def test_grpo_objective_units():
    lp = jnp.log(jnp.asarray([[0.5, 0.25], [0.125, 0.5]], jnp.float32))
    mask = jnp.ones((2, 2), jnp.float32)
    adv = jnp.asarray([1.0, -1.0], jnp.float32)
    # behavior == policy -> ratio 1, pg == -adv per token
    loss, aux = grpo_token_objective(lp, lp, lp, adv, mask, kl_coef=0.0)
    assert abs(float(loss) - (-(1.0) * 2 + 1.0 * 2)) < 1e-6
    # policy == ref -> k3 KL exactly 0
    _, aux = grpo_token_objective(lp, lp, lp, adv, mask, kl_coef=0.5)
    assert float(aux["kl_sum"]) == 0.0
    # k3 KL > 0 whenever policy != ref
    _, aux2 = grpo_token_objective(lp, lp, lp * 0.5, adv, mask,
                                   kl_coef=0.5)
    assert float(aux2["kl_sum"]) > 0.0


def test_dpo_losses_units():
    z = jnp.zeros((3,), jnp.float32)
    losses, margins = dpo_losses(z, z, z, z, beta=0.1)
    assert np.allclose(np.asarray(losses), np.log(2.0))   # sigmoid(0)
    assert np.all(np.asarray(margins) == 0.0)
    # the policy preferring chosen drives loss below log 2
    losses2, _ = dpo_losses(z + 2.0, z, z, z, beta=0.5)
    assert np.all(np.asarray(losses2) < np.log(2.0))


def test_dpo_loss_and_grad_parity_vs_reference_dp2xtp2(model, params,
                                                       dp2xtp2):
    """The acceptance pin: DPO loss AND grads through the sharded logprob
    machinery match an INDEPENDENT dense implementation (full logits,
    jax.nn.log_softmax, hand-written DPO formula, jax.grad) < 1e-4."""
    plan = dp2xtp2
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(2, 63, 4)] for _ in range(4)]
    chosen = [[int(t) for t in rng.integers(2, 30, 5)] for _ in range(4)]
    rejected = [[int(t) for t in rng.integers(31, 63, 6)] for _ in range(4)]
    beta = 0.3
    S = 12
    cb = make_sequence_batch([p + c for p, c in zip(prompts, chosen)],
                             [len(p) for p in prompts], pad_to=S)
    rb = make_sequence_batch([p + r for p, r in zip(prompts, rejected)],
                             [len(p) for p in prompts], pad_to=S)
    ref_c = jnp.asarray(rng.normal(size=4) * 0.1, jnp.float32)
    ref_r = jnp.asarray(rng.normal(size=4) * 0.1, jnp.float32)

    # ---- independent dense reference (no post_training code) ----
    def dense_seq_logp(p, batch):
        out = model(p, jnp.asarray(batch["input_ids"]),
                    position_ids=jnp.asarray(batch["position_ids"]))
        logsm = jax.nn.log_softmax(out["logits"].astype(jnp.float32), -1)
        lab = jnp.asarray(batch["labels"])
        safe = jnp.where(lab == IGNORE_INDEX, 0, lab)
        picked = jnp.take_along_axis(logsm, safe[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(lab == IGNORE_INDEX, 0.0, picked), -1)

    def dense_loss(p):
        pc = dense_seq_logp(p, cb)
        pr = dense_seq_logp(p, rb)
        margins = beta * ((pc - ref_c) - (pr - ref_r))
        return jnp.mean(-jax.nn.log_sigmoid(margins))

    ref_loss, ref_grads = jax.value_and_grad(dense_loss)(params)

    # ---- the subsystem under test, sharded on dp2xtp2 ----
    # NOTE on ordering: device_put may ALIAS the source's device-0 shard,
    # so the donating step() runs LAST — after every read of `sharded`.
    tx = build_optimizer(name="adamw", lr=1e-3)
    fns = build_dpo_step(model, tx, plan=plan, beta=beta)
    sharded = jax.device_put(jax.tree.map(jnp.array, params),
                             plan.param_sharding)
    opt = fns.init_opt_state(sharded)
    batch = {
        "chosen_input_ids": cb["input_ids"],
        "chosen_labels": cb["labels"],
        "chosen_position_ids": cb["position_ids"],
        "rejected_input_ids": rb["input_ids"],
        "rejected_labels": rb["labels"],
        "rejected_position_ids": rb["position_ids"],
        "ref_chosen_logp": ref_c, "ref_rejected_logp": ref_r,
    }

    # grad parity: differentiate the same sharded loss path directly
    from automodel_tpu.distributed.shardings import sharding_context

    def sharded_loss(p):
        with sharding_context(plan.mesh, plan.rules):
            c_lp = completion_logprobs(model, p, cb)
            r_lp = completion_logprobs(model, p, rb)
        losses, _ = dpo_losses(jnp.sum(c_lp, -1), jnp.sum(r_lp, -1),
                               ref_c, ref_r, beta=beta)
        return jnp.mean(losses)

    got_grads = jax.device_get(jax.jit(jax.grad(sharded_loss))(sharded))
    dev = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(ref_grads), got_grads)
    worst = max(jax.tree.leaves(dev))
    assert worst < 1e-4, f"DPO grad parity {worst} >= 1e-4"

    _, _, metrics = fns.step(sharded, opt, batch)   # donates `sharded`
    got = fns.unpack_metrics(metrics)
    assert abs(got["loss"] - float(ref_loss)) < 1e-4


# ---------------------------------------------------------------------------
# Rollout layer: handoff, grouped generation, compile-once cycles
# ---------------------------------------------------------------------------
def _engine(model, params, rc: RolloutConfig, **kw):
    eng_kw = dict(
        config=ServingConfig(kv_block_size=8, max_num_seqs=8,
                             max_model_len=rc.sequence_length + 2,
                             prefill_chunk=8),
        generation=GenerationConfig(
            max_new_tokens=rc.max_new_tokens, do_sample=True,
            temperature=1.0, eos_token_id=rc.eos_token_id),
        sample_seed=5)
    eng_kw.update(kw)
    return DecodeEngine(model, params, **eng_kw)


@pytest.fixture()
def rollout_setup(model, params):
    rc = RolloutConfig(group_size=2, rollout_batch_size=3,
                       max_new_tokens=6, max_prompt_len=8, eos_token_id=1,
                       reward_target_len=3)
    eng = _engine(model, params, rc)
    worker = RolloutWorker(eng, rc)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(2, 63, 5)] for _ in range(3)]
    return rc, eng, worker, prompts


def test_update_params_bitwise_and_guards(model, params):
    eng = _engine(model, params,
                  RolloutConfig(max_new_tokens=4, max_prompt_len=8))
    newp = jax.tree.map(lambda x: x * 1.5, params)
    eng.update_params(newp)
    assert eng.weight_syncs == 1
    dev = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(eng.params), jax.device_get(newp))
    assert max(jax.tree.leaves(dev)) == 0.0     # bitwise
    with pytest.raises(ValueError, match="shapes/dtypes"):
        eng.update_params(jax.tree.map(lambda x: x[..., :1], params))
    with pytest.raises(ValueError, match="structure"):
        eng.update_params({"nope": jnp.zeros(3)})


def test_rollout_groups_and_determinism(model, params, rollout_setup):
    rc, eng, worker, prompts = rollout_setup
    rb = worker.generate(prompts, params=params)
    assert len(rb.completions) == rc.completions_per_rollout
    assert rb.prompts[0] == rb.prompts[1] == prompts[0]   # G contiguous
    assert all(1 <= len(c) <= rc.max_new_tokens for c in rb.completions)
    assert eng.allocator.all_free
    compute_rewards(rb, rc)
    assert rb.rewards.shape == (rc.completions_per_rollout,)
    # distinct engines with the same seed reproduce the stream
    eng2 = _engine(model, params, rc)
    rb2 = RolloutWorker(eng2, rc).generate(prompts, params=params)
    assert rb2.completions == rb.completions


def test_rollout_train_cycles_compile_once(model, params, rollout_setup):
    """The tier-1 half of the dryrun pin: repeated rollout→train cycles
    hit every compiled program exactly once (static [B, S] via pad_to)."""
    rc, eng, worker, prompts = rollout_setup
    tx = build_optimizer(name="adamw", lr=1e-3)
    fns = build_grpo_step(model, tx, kl_coef=0.05)
    lp_fn = build_logprob_fn(model)
    # fresh copies: fns.step donates its params, and the module fixture
    # must survive this test
    p = jax.tree.map(jnp.array, params)
    opt = fns.init_opt_state(p)
    for _ in range(3):
        rb = worker.generate(prompts, params=p)
        compute_rewards(rb, rc)
        batch = make_sequence_batch(rb.sequences, rb.prompt_lens,
                                    pad_to=rc.sequence_length)
        lp = lp_fn(p, batch)
        batch["behavior_logps"] = lp
        batch["ref_logps"] = lp
        batch["advantages"] = group_normalized_advantages(
            jnp.asarray(rb.rewards), rc.group_size)
        p, opt, _ = fns.step(p, opt, batch)
    assert_compiles_once(fns.step, "grpo step")
    assert_compiles_once(lp_fn, "logprob pass")


# ---------------------------------------------------------------------------
# Fault drills (raise mode): typed aborts, clean next rollout
# ---------------------------------------------------------------------------
@pytest.mark.fault
def test_fault_rollout_weight_sync(model, params, rollout_setup):
    """rollout_weight_sync: the engine keeps its PREVIOUS weights, the
    typed error surfaces, the next rollout re-syncs cleanly."""
    rc, eng, worker, prompts = rollout_setup
    before = jax.device_get(eng.params)
    newp = jax.tree.map(lambda x: x * 2.0, params)
    fi.configure_faults("rollout_weight_sync:1")
    try:
        with pytest.raises(RolloutError, match="weight sync"):
            worker.generate(prompts, params=newp)
        assert eng.weight_syncs == 0
        dev = jax.tree.map(
            lambda a, b: float(np.abs(np.asarray(a)
                                      - np.asarray(b)).max()),
            jax.device_get(eng.params), before)
        assert max(jax.tree.leaves(dev)) == 0.0   # old weights intact
        # next rollout is clean (point fired once)
        rb = worker.generate(prompts, params=newp)
        assert len(rb.completions) == rc.completions_per_rollout
        assert eng.weight_syncs == 1
    finally:
        fi.reset_faults()


@pytest.mark.fault
def test_fault_rollout_engine_step(model, params, rollout_setup):
    """rollout_engine_step mid-generation: every in-flight request is
    aborted via the serving abort path (block tables reclaimed —
    allocator.all_free), training state untouched, next rollout clean
    and identical to a no-fault run."""
    rc, eng, worker, prompts = rollout_setup
    fi.configure_faults("rollout_engine_step:3")   # mid-generation
    try:
        with pytest.raises(RolloutError, match="aborted"):
            worker.generate(prompts, params=params)
        assert eng.allocator.all_free, "leaked KV blocks after abort"
        assert worker.failed_rollouts == 1
        rb = worker.generate(prompts, params=params)
        assert len(rb.completions) == rc.completions_per_rollout
        assert eng.allocator.all_free
    finally:
        fi.reset_faults()


@pytest.mark.fault
def test_fault_reward_fn(model, params, rollout_setup):
    """reward_fn: the completed rollout is discarded typed; the next
    reward computation is clean."""
    rc, eng, worker, prompts = rollout_setup
    rb = worker.generate(prompts, params=params)
    fi.configure_faults("reward_fn:1")
    try:
        with pytest.raises(RolloutError, match="reward"):
            compute_rewards(rb, rc)
        assert rb.rewards is None
        rewards = compute_rewards(rb, rc)    # fired once; clean now
        assert rewards.shape == (rc.completions_per_rollout,)
    finally:
        fi.reset_faults()


# ---------------------------------------------------------------------------
# Config hygiene
# ---------------------------------------------------------------------------
def test_rollout_config_validation():
    with pytest.raises(ValueError, match="rl.group_size"):
        RolloutConfig(group_size=0)
    with pytest.raises(ValueError, match="rl.kl_coef"):
        RolloutConfig(kl_coef=-0.1)
    with pytest.raises(ValueError, match="reward_source"):
        RolloutConfig(reward_source="vibes")
    with pytest.raises(ValueError, match="reward_fn"):
        RolloutConfig(reward_source="callable")
    assert RolloutConfig(kl_coef="null").kl_coef is None
    assert RolloutConfig().sequence_length == 32 + 16
    with pytest.raises(ValueError, match="unknown rl config key"):
        build_rollout_config({"group_sise": 4})


def test_post_training_enums_validated_at_load(tmp_path):
    import yaml as _yaml

    from automodel_tpu.config.loader import load_yaml_config

    def write(data):
        p = tmp_path / "cfg.yaml"
        p.write_text(_yaml.safe_dump(data))
        return str(p)

    assert "grpo" in PT_ALGORITHMS and "length_target" in REWARD_SOURCES
    with pytest.raises(ValueError, match="post_training.algorithm"):
        load_yaml_config(write({"post_training": {"algorithm": "ppo"}}))
    with pytest.raises(ValueError, match="rl.reward_source"):
        load_yaml_config(write({"rl": {"reward_source": "vibes"}}))
    with pytest.raises(ValueError, match="rl.group_size"):
        load_yaml_config(write({"rl": {"group_size": 0}}))
    with pytest.raises(ValueError, match="rl.beta"):
        load_yaml_config(write({"rl": {"beta": -1.0}}))
    # nulls mean "default" everywhere
    load_yaml_config(write({"post_training": {"algorithm": None},
                            "rl": {"kl_coef": "null", "group_size": 4}}))


def test_post_training_enums_recheck_after_cli_overrides():
    from automodel_tpu.config.arg_parser import parse_args_and_load_config

    with pytest.raises(ValueError, match="post_training.algorithm"):
        parse_args_and_load_config(
            ["--config", GRPO_YAML, "--post_training.algorithm", "ppo"])
    with pytest.raises(ValueError, match="rl.rollout_batch_size"):
        parse_args_and_load_config(
            ["--config", GRPO_YAML, "--rl.rollout_batch_size", "0"])
    with pytest.raises(ValueError, match="rl.kl_coef"):
        parse_args_and_load_config(
            ["--config", GRPO_YAML, "--rl.kl_coef", "-0.5"])


# ---------------------------------------------------------------------------
# Recipes: e2e learning + checkpoint/resume round-trip
# ---------------------------------------------------------------------------
def _grpo_cfg(tmp_path, **overrides):
    from automodel_tpu.config.loader import load_yaml_config

    cfg = load_yaml_config(GRPO_YAML)
    cfg.set_by_dotted("distributed.dp_size", len(jax.devices()))
    cfg.set_by_dotted("checkpoint.checkpoint_dir",
                      str(tmp_path / "grpo_ckpt"))
    cfg.set_by_dotted("online_eval.enabled", False)
    for k, v in overrides.items():
        cfg.set_by_dotted(k, v)
    return cfg


def _dpo_cfg(tmp_path, **overrides):
    from automodel_tpu.config.loader import load_yaml_config

    cfg = load_yaml_config(DPO_YAML)
    cfg.set_by_dotted("distributed.dp_size", len(jax.devices()))
    cfg.set_by_dotted("checkpoint.checkpoint_dir",
                      str(tmp_path / "dpo_ckpt"))
    for k, v in overrides.items():
        cfg.set_by_dotted(k, v)
    return cfg


@pytest.mark.core
def test_grpo_e2e_reward_improves_with_engine_rollouts(tmp_path):
    """THE acceptance drill: >= 20 GRPO steps on the mock model with the
    seeded length-target reward — reward measurably improves, rollouts
    come from the ENGINE under live params (weight handoff asserted
    bitwise after a sync), RL state tracks the run."""
    from automodel_tpu.recipes.llm.train_grpo import GRPORecipeForCausalLM

    cfg = _grpo_cfg(tmp_path, **{
        "post_training.max_steps": 22,
        "post_training.ckpt_every_steps": 0,
        "checkpoint.enabled": False,
        "optimizer.lr": 3e-3,
        "rl.kl_coef": None,
    })
    recipe = GRPORecipeForCausalLM(cfg).setup()
    rewards = []
    orig_one_step = recipe._one_step

    def traced_one_step(step):
        m = orig_one_step(step)
        rewards.append(m["reward_mean"])
        return m

    recipe._one_step = traced_one_step
    recipe.run_post_training_loop()
    assert recipe.rl_state.step >= 20
    assert recipe.rl_state.rollouts >= 20
    assert recipe.engine.weight_syncs == recipe.rl_state.rollouts
    early = float(np.mean(rewards[:5]))
    late = float(np.mean(rewards[-5:]))
    assert late > early + 0.5, (
        f"reward did not improve: early {early:.3f} -> late {late:.3f}")
    # the handoff is bitwise: a fresh sync leaves engine == train params
    recipe.rollout_worker.sync_weights(recipe.params)
    dev = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(recipe.engine.params),
        jax.device_get(recipe.params))
    assert max(jax.tree.leaves(dev)) == 0.0


def test_grpo_checkpoint_resume_rl_state_roundtrip(tmp_path):
    """Both halves of the resume pin: params restore through the async
    protocol AND RL state (reward EMA, rollout counters, prompt cursor)
    round-trips EXACTLY."""
    from automodel_tpu.recipes.llm.train_grpo import GRPORecipeForCausalLM

    cfg = _grpo_cfg(tmp_path, **{
        "post_training.max_steps": 4,
        "post_training.ckpt_every_steps": 2,
    })
    r1 = GRPORecipeForCausalLM(cfg).setup()
    r1.run_post_training_loop()
    saved_state = dict(r1.rl_state.state_dict())
    saved_params = jax.device_get(r1.params)
    assert saved_state["step"] == 4 and saved_state["reward_ema"] is not None

    cfg2 = _grpo_cfg(tmp_path, **{
        "post_training.max_steps": 4,
        "post_training.ckpt_every_steps": 2,
    })
    r2 = GRPORecipeForCausalLM(cfg2).setup()   # resumes from step 4
    assert r2.rl_state.state_dict() == saved_state
    dev = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(r2.params), saved_params)
    assert max(jax.tree.leaves(dev)) == 0.0
    r2.teardown()


def test_dpo_e2e_learns_and_resumes(tmp_path):
    """DPO on the learnable mock pairs: loss drops / margin rises in a
    few steps; resume restores RL state exactly and continues the SAME
    pair stream (cursor round-trip)."""
    from automodel_tpu.recipes.llm.train_dpo import DPORecipeForCausalLM

    cfg = _dpo_cfg(tmp_path, **{
        "post_training.max_steps": 6,
        "post_training.ckpt_every_steps": 3,
    })
    r1 = DPORecipeForCausalLM(cfg).setup()
    losses = []
    orig = r1._one_step

    def traced(step):
        m = orig(step)
        losses.append(m["loss"])
        return m

    r1._one_step = traced
    r1.run_post_training_loop()
    assert losses[-1] < losses[0], f"DPO loss did not drop: {losses}"
    saved_state = dict(r1.rl_state.state_dict())
    assert saved_state["data_cursor"] == 6 * 8   # 6 steps x 8 pairs

    cfg2 = _dpo_cfg(tmp_path, **{
        "post_training.max_steps": 6,
        "post_training.ckpt_every_steps": 3,
    })
    r2 = DPORecipeForCausalLM(cfg2).setup()
    assert r2.rl_state.state_dict() == saved_state
    r2.teardown()


# ---------------------------------------------------------------------------
# Online eval watcher
# ---------------------------------------------------------------------------
def test_eval_watcher_scores_each_committed_checkpoint_once(tmp_path,
                                                            model, params):
    from automodel_tpu.checkpoint import checkpointing as ckpt
    from automodel_tpu.post_training.eval_watch import CheckpointEvalWatcher

    ckdir = str(tmp_path / "watch")
    cfg = ckpt.CheckpointingConfig(checkpoint_dir=ckdir,
                                   model_save_format="safetensors",
                                   save_consolidated=True)
    # two committed checkpoints + one staging dir the watcher must ignore
    from automodel_tpu.recipes.base_recipe import BaseRecipe

    for step in (1, 2):
        rec = BaseRecipe()
        rec.checkpoint_config = dataclasses.replace(cfg, async_save=False)
        rec.model, rec.params = model, params
        rec.save_checkpoint(0, step)
    os.makedirs(os.path.join(ckdir, "epoch_0_step_3.tmp"))

    rng = np.random.default_rng(5)
    rows = [([int(t) for t in rng.integers(2, 63, 5)],
             [int(t) for t in rng.integers(2, 63, 3)]) for _ in range(3)]
    results = []
    w = CheckpointEvalWatcher(model, ckdir, rows, checkpoint_config=cfg,
                              max_new_tokens=3,
                              on_result=results.append)
    first = w.poll()
    assert [r["step"] for r in first] == [1, 2]
    assert all("eval/score" in r for r in first)
    assert w.poll() == []                      # at-most-once
    assert [r["step"] for r in results] == [1, 2]
    assert w.drain_results() == first and w.drain_results() == []
