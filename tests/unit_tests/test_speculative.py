"""Speculative decoding: n-gram drafting, one-step chunked-q verify,
token-identical greedy.

The anchor is the same parity oracle as ``test_serving.py`` /
``test_prefix_cache.py``: greedy decode with ``serving.speculative:
ngram`` must be **token-identical** to the spec-off engine (and to
``generate()``) on every drilled path — mixed batches across spec_k ∈
{1, 2, 4}, prefix caching on/off, int8 KV, preemption pressure, watchdog
pool rebuilds, a fleet replica-loss replay, and both injected faults
(``spec_draft`` / ``spec_verify``).  Speculation may only ever change HOW
MANY device steps produce the tokens, never WHICH tokens come out;
``allocator.all_free`` stays the leak oracle (rejected draft positions
never strand blocks), and the engine keeps one compiled program per step
width ({spec_k+1, prefill_chunk} with spec on) with a collective- and
callback-free census.
"""

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
    DecodeEngine,
    FleetRouter,
    RequestState,
    ServingConfig,
)
from automodel_tpu.serving.speculative import (
    longest_accepted,
    normalize_speculative,
    propose_ngram,
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
def spec_prompts():
    """Mixed-length batch: periodic prompts (the traffic prompt-lookup
    drafting wins on — tiny greedy models also loop, so acceptance is
    high) alongside plain random ones that mostly reject."""
    rng = np.random.default_rng(21)
    motif = rng.integers(1, 255, 6).tolist()
    return [
        motif * 3 + motif[:2],              # 20 tokens, strongly periodic
        rng.integers(1, 255, 11).tolist(),  # random: low acceptance
        (motif + motif)[:9],                # short periodic
        rng.integers(1, 255, 17).tolist(),
    ]


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


@pytest.fixture(scope="module")
def baseline(model_and_params, spec_prompts):
    """The spec-off output every speculative configuration must equal."""
    return _run_prompts(_engine(model_and_params), spec_prompts)


# ---------------------------------------------------------------------------
# Proposer + acceptance rule units (pure host, no model)
# ---------------------------------------------------------------------------
def test_propose_ngram_prompt_lookup_rule():
    # trailing 3-gram (4,5,6) recurs: propose what followed it, up to k
    seq = [4, 5, 6, 9, 9, 2, 4, 5, 6]
    assert propose_ngram(seq, 4) == [9, 9, 2, 4]
    assert propose_ngram(seq, 2) == [9, 9]
    # ties resolve to the MOST RECENT prior occurrence
    seq = [7, 1, 7, 2, 7]
    assert propose_ngram(seq, 2) == [2, 7]
    # longest n-gram wins over a shorter, fresher match
    seq = [1, 2, 3, 8, 2, 3, 1, 2, 3]
    assert propose_ngram(seq, 1) == [8]
    # no prior occurrence of any trailing n-gram -> empty draft
    assert propose_ngram([1, 2, 3, 4, 5], 4) == []
    # degenerate inputs never raise
    assert propose_ngram([5], 4) == []
    assert propose_ngram([], 4) == []
    assert propose_ngram([1, 2, 1], 0) == []


def test_longest_accepted_prefix_rule():
    assert longest_accepted([3, 4, 5], [3, 4, 5, 9]) == 3
    assert longest_accepted([3, 4, 5], [3, 7, 5, 9]) == 1   # prefix only
    assert longest_accepted([3, 4], [9, 4]) == 0
    assert longest_accepted([], [9]) == 0


# ---------------------------------------------------------------------------
# The parity oracle: spec-on == spec-off == generate()
# ---------------------------------------------------------------------------
def test_spec_on_token_identical_and_generate(model_and_params,
                                              spec_prompts, baseline):
    """spec-on == spec-off == the generate() oracle on the mixed batch,
    and speculation actually fired (accepted tokens, fewer steps)."""
    model, params = model_and_params
    S = max(len(p) for p in spec_prompts)
    ids = np.zeros((len(spec_prompts), S), np.int64)
    for b, p in enumerate(spec_prompts):
        ids[b, :len(p)] = p
    lens = np.asarray([len(p) for p in spec_prompts])
    oracle = np.asarray(generate(
        model, params, ids, prompt_lens=lens,
        config=GenerationConfig(max_new_tokens=MAX_NEW)))
    off_eng = _engine(model_and_params)
    off = off_eng.generate(ids, lens)
    on_eng = _engine(model_and_params, speculative="ngram", spec_k=4)
    on = on_eng.generate(ids, lens)
    np.testing.assert_array_equal(off, oracle)
    np.testing.assert_array_equal(on, oracle)
    s = on_eng.stats()
    assert s["speculative"]["enabled"] and s["speculative"]["mode"] == "ngram"
    assert s["speculative"]["tokens_proposed"] >= 1
    assert s["spec_tokens_accepted"] >= 1
    assert 0.0 < s["accept_rate"] <= 1.0
    assert s["steps"] < off_eng.stats()["steps"]   # the point of all this
    assert on_eng.allocator.all_free


@pytest.mark.parametrize("spec_k,cache,traffic", [
    (1, None, "mixed"), (2, None, "mixed"), (4, None, "mixed"),
    (1, "on", "mixed"), (2, "on", "mixed"), (4, "on", "mixed"),
    (4, None, "distinct")])
def test_spec_matrix_token_identical(model_and_params, spec_prompts,
                                     baseline, spec_k, cache, traffic):
    """The spec_k x prefix-caching matrix: every cell token-identical to
    the spec-off baseline, pool drained after.  ``distinct`` is the
    adversarial traffic: prompts of all-distinct tokens, whose trailing
    n-gram has no earlier occurrence to draft from."""
    if traffic == "distinct":
        rng = np.random.default_rng(22)
        spec_prompts = [rng.permutation(np.arange(1, 255))[:n].tolist()
                        for n in (20, 11, 9, 17)]
        baseline = _run_prompts(_engine(model_and_params), spec_prompts)
    eng = _engine(model_and_params, speculative="ngram", spec_k=spec_k,
                  prefix_caching=cache)
    out = _run_prompts(eng, spec_prompts)
    assert out == baseline
    assert eng.allocator.all_free


def test_spec_int8_kv_token_identical(model_and_params, spec_prompts):
    """int8 KV: the verify step reads quantized pools through the same
    dequant as plain decode — spec-on int8 == spec-off int8 exactly."""
    off = _engine(model_and_params, kv_cache_dtype="int8")
    on = _engine(model_and_params, kv_cache_dtype="int8",
                 speculative="ngram", spec_k=2)
    out_off = _run_prompts(off, spec_prompts)
    out_on = _run_prompts(on, spec_prompts)
    assert out_on == out_off
    assert on.allocator.all_free


def test_spec_under_preemption_pressure(model_and_params, spec_prompts):
    """A pool too small for full residency preempts mid-speculation; the
    stateless proposer re-drafts from the replayed sequence — output
    unchanged vs the spec-off engine under the same pressure."""
    kw = dict(max_model_len=40, num_kv_blocks=12)
    off = _engine(model_and_params, **kw)
    on = _engine(model_and_params, speculative="ngram", spec_k=2, **kw)
    out_off = _run_prompts(off, spec_prompts)
    out_on = _run_prompts(on, spec_prompts)
    assert out_on == out_off
    assert on.scheduler.preemptions >= 1     # the pressure actually bit
    assert on.allocator.all_free and off.allocator.all_free


def test_spec_watchdog_recovery_token_identical(model_and_params,
                                                spec_prompts, baseline):
    """A watchdog pool rebuild mid-fleet of speculative traffic: replayed
    requests re-draft deterministically (no draft state to migrate) and
    finish token-identical."""
    eng = _engine(model_and_params, speculative="ngram", spec_k=2)
    out1 = _run_prompts(eng, spec_prompts)
    assert out1 == baseline
    eng._watchdog_recover("drill: rebuild pools under speculation")
    assert eng.allocator.all_free
    out2 = _run_prompts(eng, spec_prompts)
    assert list(out2.values())[-len(spec_prompts):] == list(baseline.values())
    assert eng.allocator.all_free


# ---------------------------------------------------------------------------
# Acceptance stats + the spec-off bitwise guarantee
# ---------------------------------------------------------------------------
def test_spec_stats_and_admission_ewma(model_and_params, spec_prompts):
    """Speculation reports its own ledger (proposed/accepted/accept_rate/
    tokens_per_step) and feeds the admission guard's accepted-tokens EWMA;
    the spec-off engine's EWMA stays EXACTLY 1.0 so its admission
    arithmetic is bit-unchanged from before this feature existed."""
    off = _engine(model_and_params)
    _run_prompts(off, spec_prompts)
    assert off.scheduler._tokens_per_row_ewma == 1.0
    s_off = off.stats()
    assert not s_off["speculative"]["enabled"]
    assert s_off["speculative"]["tokens_proposed"] == 0
    assert s_off["spec_tokens_accepted"] == 0 and s_off["accept_rate"] == 0.0

    on = _engine(model_and_params, speculative="ngram", spec_k=4)
    _run_prompts(on, spec_prompts)
    s = on.stats()
    assert s["speculative"]["spec_k"] == 4
    assert 1 <= s["speculative"]["tokens_accepted"] \
        <= s["speculative"]["tokens_proposed"]
    assert s["tokens_per_step"] > 1.0         # multi-token steps happened
    assert s["tokens_generated"] == s_off["tokens_generated"]
    # accepted drafts pull the EWMA above the 1-token-per-row floor
    assert on.scheduler._tokens_per_row_ewma > 1.0


def test_spec_do_sample_disabled_loudly(model_and_params, caplog):
    """Verification is greedy-only: a do_sample generation config disables
    speculation with a warning instead of silently changing samples."""
    model, params = model_and_params
    with caplog.at_level("WARNING"):
        eng = DecodeEngine(
            model, params, _cfg(speculative="ngram"),
            generation=GenerationConfig(max_new_tokens=MAX_NEW,
                                        do_sample=True))
    assert eng.spec_mode == "off"
    assert eng.scheduler.spec_proposer is None
    assert not eng.stats()["speculative"]["enabled"]
    assert any("do_sample" in r.message for r in caplog.records)


def test_grpo_rollout_spec_stats(model_and_params):
    """The rollout layer gets speculation for free: a greedy grouped
    rollout through a spec-on engine is token-identical and reports its
    per-rollout acceptance deltas in ``RolloutBatch.stats``."""
    from automodel_tpu.post_training.rollout import (
        RolloutConfig,
        RolloutWorker,
    )

    model, params = model_and_params
    rng = np.random.default_rng(4)
    motif = rng.integers(1, 255, 4).tolist()
    prompts = [motif * 4, rng.integers(1, 255, 2 * BS).tolist()]
    outs = {}
    for mode in ("off", "ngram"):
        eng = DecodeEngine(
            model, params, _cfg(speculative=mode, spec_k=3),
            generation=GenerationConfig(max_new_tokens=4))
        worker = RolloutWorker(eng, RolloutConfig(
            group_size=2, max_new_tokens=4, max_prompt_len=2 * BS,
            eos_token_id=None))
        batch = worker.generate(prompts)
        outs[mode] = batch.completions
        if mode == "ngram":
            assert batch.stats["spec_tokens_accepted"] >= 1
            assert 0.0 < batch.stats["accept_rate"] <= 1.0
            assert batch.stats["tokens_per_step"] > 1.0
        else:
            assert batch.stats["spec_tokens_accepted"] == 0.0
        assert eng.allocator.all_free
    assert outs["ngram"] == outs["off"]


# ---------------------------------------------------------------------------
# Fault drills
# ---------------------------------------------------------------------------
@pytest.mark.fault
def test_spec_draft_fault_rides_as_plain_decode(model_and_params,
                                                spec_prompts, baseline):
    """An armed ``spec_draft`` degrades that row to an empty draft — it
    rides the verify step as plain decode, byte-identical output, and the
    failure is counted."""
    eng = _engine(model_and_params, speculative="ngram", spec_k=2)
    fi.configure_faults("spec_draft:1")
    try:
        out = _run_prompts(eng, spec_prompts)
    finally:
        fi.reset_faults()
    assert out == baseline
    assert eng.stats()["speculative"]["draft_faults"] == 1
    assert eng.allocator.all_free


@pytest.mark.fault
def test_spec_verify_fault_discards_all_drafts(model_and_params,
                                               spec_prompts, baseline):
    """An armed ``spec_verify`` discards every draft in that step (no
    partial acceptance) — each row keeps only its real next token, KV
    advancement excludes all drafts, output byte-identical."""
    eng = _engine(model_and_params, speculative="ngram", spec_k=2)
    fi.configure_faults("spec_verify:1")
    try:
        out = _run_prompts(eng, spec_prompts)
    finally:
        fi.reset_faults()
    assert out == baseline
    assert eng.stats()["speculative"]["verify_failures"] == 1
    assert eng.allocator.all_free


@pytest.mark.fault
def test_spec_fleet_replica_loss_replay(model_and_params, spec_prompts,
                                        monkeypatch):
    """A speculative fleet losing a replica mid-traffic replays on the
    survivor token-identically — the stateless proposer re-drafts from the
    replayed sequences, and the fleet ledger sums acceptance."""
    monkeypatch.setenv("AUTOMODEL_LOST_REPLICA", "0")
    model, params = model_and_params
    baseline = _run_prompts(_engine(model_and_params), spec_prompts)
    fleet = FleetRouter(
        model, params,
        _cfg(replicas=2, fleet_probation_polls=2, speculative="ngram",
             spec_k=2),
        generation=GenerationConfig(max_new_tokens=MAX_NEW))
    rids = [fleet.submit(list(p)) for p in spec_prompts]
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
    s = fleet.stats()
    assert s["spec_tokens_accepted"] >= 1
    assert 0.0 < s["accept_rate"] <= 1.0


# ---------------------------------------------------------------------------
# Compile-once / census, config hygiene
# ---------------------------------------------------------------------------
def test_spec_compile_once_per_width_and_census(model_and_params,
                                                spec_prompts):
    """Speculation adds exactly ONE program shape — the verify width
    spec_k+1 — and acceptance churn (0..k accepted per row per step) is
    data, not shape.  The verify step's census stays collective- and
    callback-free with the same 10-arg signature as plain decode."""
    eng = _engine(model_and_params, speculative="ngram", spec_k=2)
    _run_prompts(eng, spec_prompts)
    assert sorted(eng._steps) == [3, 8]      # verify width + prefill chunk
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"speculative step width={width}")
    fn = eng._steps[3]
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a))(eng.params, eng.pools,
                           np.zeros((4, 3), np.int32),
                           np.zeros((4, 3), np.int32),
                           np.zeros((4, 3), np.int32),
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


def test_spec_config_validation_and_cli_reval(tmp_path):
    from automodel_tpu.config.arg_parser import parse_args_and_load_config
    from automodel_tpu.config.loader import load_yaml_config

    with pytest.raises(ValueError, match="speculative"):
        ServingConfig(speculative="warp")
    with pytest.raises(ValueError, match="spec_k"):
        ServingConfig(spec_k=0)
    # YAML 1.1 bools normalize like prefix_caching: true -> ngram
    assert ServingConfig(speculative=True).speculative == "ngram"
    assert ServingConfig(speculative=False).speculative == "off"
    assert ServingConfig(speculative="null").speculative is None
    assert normalize_speculative("none") is None
    p = tmp_path / "serve.yaml"
    p.write_text("serving:\n  speculative: true\n  spec_k: 2\n")
    cfg = load_yaml_config(str(p))
    assert cfg.get("serving.speculative") is True      # normalized at use
    assert cfg.get("serving.spec_k") == 2
    p.write_text("serving:\n  speculative: warp\n")
    with pytest.raises(ValueError, match=r"serving\.speculative"):
        load_yaml_config(str(p))
    p.write_text("serving:\n  spec_k: -1\n")
    with pytest.raises(ValueError, match=r"serving\.spec_k"):
        load_yaml_config(str(p))
    yaml = "examples/serve/tiny_llama_serve.yaml"
    cfg = parse_args_and_load_config(
        ["--config", yaml, "--serving.speculative", "ngram",
         "--serving.spec_k", "3"])
    assert cfg.get("serving.speculative") == "ngram"
    assert cfg.get("serving.spec_k") == 3
    with pytest.raises(ValueError, match=r"serving\.speculative"):
        parse_args_and_load_config(
            ["--config", yaml, "--serving.speculative", "warp"])
