"""The engine's look-ahead of one step against the depth-0 order.

A greedy engine dispatches step N+1 before it fetches step N; ``do_sample``
and speculation keep the fetch at once (depth 0), in the SAME loop.  The
oracle here runs one traffic through both orders (``engine.lookahead`` set
by the test: nothing of the program's configuration chooses it) and holds
every request to token-identical output and the same terminal state, over
the situations in which the two orders differ: an EOS learnt one step late,
short requests, preemption, a cancel, a stall and a weight handoff with a
step in flight, and the three kinds of cache.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.analysis.jaxpr_audit import assert_compiles_once
from automodel_tpu.generation import GenerationConfig
from automodel_tpu.models.auto_model import build_model
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.serving import (
    BlockAllocator,
    DecodeEngine,
    Request,
    RequestState,
    Scheduler,
    ServingConfig,
)
from automodel_tpu.serving.scheduler import FED_TOKEN
from automodel_tpu.utils import fault_injection as fi
from benchmark import weights as bench_weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LLAMA = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)
LENS = [9, 6, 13, 5, 11, 7]
MAX_NEW = 8


def _perturbed(params, seed):
    leaves, td = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(td, [
        l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def llama():
    model = LlamaForCausalLM(LLAMA, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    return model, _perturbed(model.init(jax.random.key(0)), 5)


def _family_world(ref, cfg, seed):
    model = build_model(config=ref.model_config(cfg),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(cfg, w))(bench_weights.seed_words(seed))
    return model, jax.tree.map(lambda a: a.astype(jnp.float32),
                               ref.to_program_tree(flat))


@pytest.fixture(scope="module")
def brumby():
    """``test_brumby_serving.py``'s toy: per-sequence state planes."""
    from benchmark.reference import brumby as ref

    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "tiny-brumby.json")) as f:
        return _family_world(ref, json.load(f), 2 ** 31 + 33)


@pytest.fixture(scope="module")
def kimi():
    """``test_kimi_k2_serving.py``'s toy: a latent plane, routed experts."""
    from benchmark.reference import kimi_k2 as ref
    from tests.unit_tests.test_kimi_k2_serving import CFG

    return _family_world(ref, CFG, 2 ** 31 + 77)


def _prompts(vocab, lens=LENS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab - 1, n).tolist() for n in lens]


def _engine(world, lookahead, generation=None, **kw):
    model, params = world
    cfg = dict(kv_block_size=8, max_num_seqs=4, max_model_len=64,
               prefill_chunk=8)
    cfg.update(kw)
    eng = DecodeEngine(model, params, ServingConfig(**cfg),
                       generation=generation
                       or GenerationConfig(max_new_tokens=MAX_NEW))
    assert eng.lookahead == 1       # what a greedy engine is built with
    eng.lookahead = lookahead
    return eng


def _outcome(eng, rids):
    return [(list(eng.requests[r].out_tokens), eng.requests[r].state,
             eng.requests[r].finish_reason) for r in rids]


def _nothing_in_flight(eng):
    assert not eng._in_flight
    assert not eng.scheduler.has_work()
    assert all(r.in_flight == 0 or r.finished
               for r in eng.requests.values())
    assert eng.allocator.all_free


def _both(world, drive, **kw):
    """``drive(engine) -> rids`` through both orders; returns the look-ahead
    engine after holding it to the depth-0 outcome."""
    out = {}
    for depth in (0, 1):
        eng = _engine(world, depth, **kw)
        rids = drive(eng)
        _nothing_in_flight(eng)
        out[depth] = (eng, _outcome(eng, rids))
    assert out[1][1] == out[0][1]
    assert out[0][0].stats()["ahead_steps"] == 0
    return out[1][0], out[0][0]


# ---------------------------------------------------------------------------
# The oracle: one traffic, both orders
# ---------------------------------------------------------------------------
def _run_all(max_new=None, eos="default"):
    def drive(eng):
        vocab = eng.model.config.vocab_size
        rids = [eng.submit(p, max_new_tokens=max_new, eos_token_id=eos)
                for p in _prompts(vocab)]
        eng.run()
        return rids
    return drive


@pytest.mark.parametrize("kw", [
    {}, {"kv_cache_dtype": "int8"}, {"prefix_caching": "on"},
    {"scheduler_policy": "sjf"}, {"max_num_seqs": 1},
], ids=["bf16", "int8-pools", "prefix-cache", "sjf", "one-row"])
def test_mixed_prefill_and_decode(llama, kw):
    ahead, plain = _both(llama, _run_all(), **kw)
    st = ahead.stats()
    assert st["mixed_steps"] and st["decode_steps"]
    # not ahead: the first step, and one after every time the ONLY rows
    # left had their last sample in flight (one row: after every request)
    restarts = st["steps"] - st["ahead_steps"]
    assert restarts <= (len(LENS) if kw.get("max_num_seqs") == 1 else 2)
    assert st["discarded_rows"] == 0                # no EOS: nothing wasted
    assert st["tokens_generated"] == len(LENS) * MAX_NEW
    # six requests over four rows: a finish by length is known at dispatch,
    # so it costs no row, and both orders run the same number of steps but
    # for the one step a freed row waits for its delivery
    assert 0 <= st["steps"] - plain.stats()["steps"] <= len(LENS)


@pytest.mark.parametrize("max_new", [1, 2, 3])
def test_short_requests(llama, max_new):
    ahead, _ = _both(llama, _run_all(max_new=max_new))
    assert all(len(r.out_tokens) == max_new
               for r in ahead.requests.values())
    assert ahead.stats()["discarded_rows"] == 0


@pytest.mark.parametrize("kw", [{}, {"prefix_caching": "on"}],
                         ids=["paged", "prefix-cache"])
def test_eos_is_learnt_one_step_late_and_costs_a_discarded_row(llama, kw):
    # an id that the greedy chains of these prompts produce mid-way
    probe = _engine(llama, 0)
    rids = _run_all()(probe)
    chains = [probe.requests[r].out_tokens for r in rids]
    eos = next(t for c in chains for t in c[2:-2])
    ahead, plain = _both(llama, _run_all(eos=eos), **kw)
    reasons = [r.finish_reason for r in ahead.requests.values()]
    assert "eos" in reasons
    assert any(len(r.out_tokens) < MAX_NEW for r in ahead.requests.values())
    assert ahead.stats()["discarded_rows"] > 0
    assert plain.stats()["discarded_rows"] == 0
    assert ahead.allocator.all_free                 # no leaked block


def test_preemption_under_block_pressure(llama):
    # 8 usable blocks of 8 for four rows that each grow to 3: they preempt
    ahead, plain = _both(llama, _run_all(), num_kv_blocks=9)
    assert ahead.stats()["preemptions"] >= 1
    assert plain.stats()["preemptions"] >= 1


def test_a_stale_delivery_leaves_a_readmitted_request_alone():
    """Preempted in the plan after its sample was dispatched, and
    re-admitted before that sample is delivered: the delivery must not
    touch the request's new life."""
    s = Scheduler(BlockAllocator(9), max_num_seqs=2, prefill_chunk=8,
                  block_size=8, max_model_len=64)
    req = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=6)
    s.add(req)
    first = s.schedule()
    s.advance(first)                            # dispatched: sample in flight
    assert req.in_flight == 1 and req.num_computed == 3
    ahead = s.schedule()                        # the look-ahead plan: fed
    row = ahead.active[0]
    assert row.fed and row.tokens == [FED_TOKEN] and row.samples_next
    assert row.start_pos == 3 and len(row.tokens) == 1
    s._preempt(req)                             # before it is dispatched
    assert req.in_flight == 0 and req.num_computed == 0
    again = s.schedule()                        # re-admitted, from scratch
    assert again.active[0].tokens == [3, 4, 5] and not again.active[0].fed
    s.advance(again)
    assert s.deliver(first, {0: 42}) == []      # the stale one: dropped
    assert req.out_tokens == [] and req.in_flight == 1
    assert req.num_computed == 3 and s.discarded_rows == 1
    s.deliver(again, {req.slot: 7})
    assert req.out_tokens == [7] and req.in_flight == 0
    assert s.has_work()


def test_a_finish_by_length_is_known_without_the_token():
    s = Scheduler(BlockAllocator(9), max_num_seqs=2, prefill_chunk=8,
                  block_size=8, max_model_len=64)
    req = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=2)
    s.add(req)
    one = s.schedule()
    s.advance(one)
    two = s.schedule()
    s.advance(two)
    assert req.in_flight == 2
    # both samples are in flight: no third row, but the request is work
    assert s.schedule() is None and s.has_work()
    s.deliver(one, {0: 11})
    assert s.has_work() and req.state is RequestState.DECODE
    assert s.deliver(two, {0: 12}) == [req]
    assert req.out_tokens == [11, 12] and req.finish_reason == "length"
    assert not s.has_work() and s.allocator.all_free
    assert s.discarded_rows == 0


def test_abort_with_a_step_in_flight(llama):
    def drive(eng):
        rids = [eng.submit(p, max_new_tokens=4 + 2 * i)
                for i, p in enumerate(_prompts(256, LENS[:4]))]
        returned = []
        for _ in range(5):                  # rids[0] has one sample to go
            returned += eng.step()
        if eng.lookahead:
            assert eng._in_flight and eng.scheduler.has_work()
        eng.abort(rids[1])
        assert not eng._in_flight           # the cancel delivered it first
        while eng.scheduler.has_work():
            returned += eng.step()
        # a request that finished in the cancel's delivery is still
        # returned by a step(), and each finished request by exactly one
        assert sorted(r.rid for r in returned) == [rids[0], rids[2], rids[3]]
        return rids
    ahead, _ = _both(llama, drive)
    states = [r.state for r in ahead.requests.values()]
    assert states.count(RequestState.ABORTED) == 1
    assert ahead.stats()["aborts"] == 1


@pytest.mark.fault
def test_a_stall_with_a_step_in_flight_replays_identically(llama):
    def drive(eng):
        fi.configure_faults("serve_watchdog_stall:5")
        try:
            rids = [eng.submit(p) for p in _prompts(256, LENS[:4])]
            eng.run()
        finally:
            fi.reset_faults()
        assert eng.watchdog_recoveries == 1
        return rids
    ahead, _ = _both(llama, drive, watchdog_s=30.0)
    # the abandoned step's tokens were regenerated, not lost
    assert all(len(r.out_tokens) == MAX_NEW
               for r in ahead.requests.values())


def test_update_params_mid_run(llama):
    model, params = llama
    other = _perturbed(params, 9)

    def drive(eng):
        rids = [eng.submit(p) for p in _prompts(256, LENS[:4])]
        for _ in range(4):
            eng.step()
        eng.update_params(other)
        assert not eng._in_flight           # old weights' step: delivered
        eng.run()
        return rids
    ahead, _ = _both(llama, drive)
    assert ahead.weight_syncs == 1
    # and the handoff did change what was generated
    plain = _engine(llama, 0)
    rids = [plain.submit(p) for p in _prompts(256, LENS[:4])]
    plain.run()
    assert _outcome(plain, rids) != _outcome(ahead, rids)


def test_state_plane_model(brumby):
    gen = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=None)
    ahead, _ = _both(brumby, _run_all(), generation=gen, max_num_seqs=2,
                     max_model_len=96)
    st = ahead.stats()
    assert st["state_resets_sum"] == len(LENS)      # each request once
    assert st["ahead_steps"] >= st["steps"] - 2


def test_latent_model_with_routed_experts(kimi):
    gen = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=None)
    ahead, plain = _both(kimi, _run_all(), generation=gen)
    st = ahead.stats()
    # every step stamped its expert counts, one step behind its dispatch
    assert 0 < st["experts_hit_sum"] <= st["expert_assignments_sum"]
    assert st["ahead_steps"] >= st["steps"] - 2
    assert st["tokens_generated"] == plain.stats()["tokens_generated"]


# ---------------------------------------------------------------------------
# The loop's contract
# ---------------------------------------------------------------------------
def test_has_work_while_in_flight_and_what_step_returns(llama):
    eng = _engine(llama, 1, max_num_seqs=1)
    rid = eng.submit(_prompts(256)[0], max_new_tokens=3)
    req = eng.requests[rid]
    seen, returned = [], []
    while eng.scheduler.has_work():
        returned.append(eng.step())
        seen.append(len(req.out_tokens))
        if not req.finished:
            assert eng._in_flight           # in flight <=> has_work()
    # 9 prompt tokens: two prefill steps, then the decode steps; a token is
    # on the host one call after the call that dispatched its step
    assert seen == [0, 0, 1, 2, 3]
    assert returned == [[], [], [], [], [req]]
    assert eng.step() == [] and not eng._in_flight
    plain = _engine(llama, 0, max_num_seqs=1)
    plain.submit(_prompts(256)[0], max_new_tokens=3)
    seen0 = []
    while plain.scheduler.has_work():
        plain.step()
        seen0.append(len(plain.requests[0].out_tokens))
    assert seen0 == [0, 1, 2, 3]
    assert plain.requests[0].out_tokens == req.out_tokens


@pytest.mark.parametrize("how", ["run", "drain", "generate",
                                 "drain_deadline"])
def test_loops_end_with_nothing_in_flight(llama, how):
    now = [0.0]
    model, params = llama
    eng = DecodeEngine(model, params, ServingConfig(
        kv_block_size=8, max_num_seqs=4, max_model_len=64, prefill_chunk=8),
        generation=GenerationConfig(max_new_tokens=MAX_NEW),
        clock=lambda: now[0])
    prompts = _prompts(256, LENS[:4])
    if how == "generate":
        ids = np.zeros((4, max(LENS)), np.int64)
        for b, p in enumerate(prompts):
            ids[b, :len(p)] = p
        out = eng.generate(ids, np.asarray(LENS[:4]))
        assert out.shape == (4, MAX_NEW)
    else:
        for p in prompts:
            eng.submit(p)
        eng.step()
        eng.step()
        assert eng._in_flight
        if how == "run":
            eng.run()
        elif how == "drain":
            assert eng.drain() == {"finished": 4}
        else:
            # the grace runs out at once: everything expires, and the step
            # that was in flight is dropped row by row
            real = eng.step

            def step():
                now[0] += 10.0
                return real()
            eng.step = step
            counts = eng.drain(grace_s=5.0)
            assert counts == {"expired": 4}
            assert eng.stats()["discarded_rows"] >= 4
    _nothing_in_flight(eng)


def test_exactly_two_step_programs_compiled_once(llama):
    eng = _engine(llama, 1)
    _run_all()(eng)
    assert sorted(eng._steps) == [1, 8]
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"look-ahead step width={width}")
    # a second batch, an EOS and an abort later: still the two
    rids = [eng.submit(p, eos_token_id=7) for p in _prompts(256, seed=3)]
    eng.step()
    eng.step()
    eng.abort(rids[0])
    eng.run()
    assert sorted(eng._steps) == [1, 8]
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"look-ahead step width={width}")


@pytest.mark.parametrize("kw,gen,depth", [
    ({}, {}, 1),
    ({"prefix_caching": "on"}, {}, 1),
    ({"kv_cache_dtype": "int8"}, {}, 1),
    ({}, {"do_sample": True, "temperature": 0.8}, 0),
    ({"speculative": "ngram"}, {}, 0),
], ids=["greedy", "prefix-cache", "int8", "do_sample", "speculative"])
def test_depth_is_decided_at_build_from_what_needs_the_token(llama, kw, gen,
                                                             depth):
    model, params = llama
    eng = DecodeEngine(
        model, params, ServingConfig(kv_block_size=8, max_num_seqs=4,
                                     max_model_len=64, prefill_chunk=8, **kw),
        generation=GenerationConfig(max_new_tokens=MAX_NEW, **gen))
    assert eng.lookahead == depth
    rids = [eng.submit(p) for p in _prompts(256, LENS[:4])]
    eng.run()
    st = eng.stats()
    assert st["lookahead"] == depth
    assert (st["ahead_steps"] > 0) == bool(depth)
    assert all(eng.requests[r].state is RequestState.FINISHED for r in rids)
    _nothing_in_flight(eng)
