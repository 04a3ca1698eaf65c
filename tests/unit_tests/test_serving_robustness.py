"""Serving under fire: deadlines, admission control, starvation-free
scheduling, watchdog recovery, graceful drain — the request-lifecycle
robustness layer over the PR-12 decode engine.

The anchor is the OVERLOAD DRILL: a seeded 2x-capacity Poisson trace with
``serve_block_alloc`` + ``serve_watchdog_stall`` faults armed must
complete with zero engine crashes, every shed/expired request's blocks
back on the free list (allocator count pinned), and every request that
completes remaining greedy token-identical to ``generate()`` — including
requests replayed through watchdog recovery.

Determinism: the scheduler/engine clock is injectable, so every
deadline/TTL/watchdog test runs on a VIRTUAL clock — no wall-clock
sleeps, no flakes.
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
    Request,
    RequestRejected,
    RequestState,
    Scheduler,
    ServingConfig,
)
from automodel_tpu.serving.kv_cache import BlockAllocator
from automodel_tpu.utils import fault_injection as fi

CFG = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)

LENS = [9, 6, 13, 5]
MAX_NEW = 8


class VirtualClock:
    """Deterministic monotonic clock the scheduler/engine run on."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


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
def prompts():
    rng = np.random.default_rng(1)
    S = max(LENS)
    ids = np.zeros((len(LENS), S), np.int64)
    for b, n in enumerate(LENS):
        ids[b, :n] = rng.integers(1, 255, n)
    return ids


@pytest.fixture(scope="module")
def dense_oracle(model_and_params, prompts):
    model, params = model_and_params
    return np.asarray(generate(
        model, params, prompts, prompt_lens=np.asarray(LENS),
        config=GenerationConfig(max_new_tokens=MAX_NEW)))


def _cfg(**kw):
    base = dict(kv_block_size=8, max_num_seqs=4, max_model_len=64,
                prefill_chunk=8)
    base.update(kw)
    return ServingConfig(**base)


def _engine(model_and_params, clock=None, **kw):
    model, params = model_and_params
    kwargs = {} if clock is None else {"clock": clock}
    return DecodeEngine(model, params, _cfg(**kw),
                        generation=GenerationConfig(max_new_tokens=MAX_NEW),
                        **kwargs)


def _sched(allocator=None, clock=None, **kw):
    base = dict(max_num_seqs=2, prefill_chunk=4, block_size=4,
                max_model_len=64)
    base.update(kw)
    if clock is not None:
        base["clock"] = clock
    return Scheduler(allocator or BlockAllocator(64), **base)


def _req(rid, n_prompt=4, max_new=4, **kw):
    return Request(rid=rid, prompt=list(range(1, n_prompt + 1)),
                   max_new_tokens=max_new, **kw)


# ---------------------------------------------------------------------------
# Deadlines & TTLs
# ---------------------------------------------------------------------------
def test_deadline_expires_at_step_boundary_terminal_expired(
        model_and_params, prompts, dense_oracle):
    """A deadline-exceeded request transitions to EXPIRED (distinct from
    ABORTED) at the next step boundary with its whole block table
    reclaimed; every other request's greedy output is unaffected."""
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk)
    rids = [eng.submit(prompts[b, :LENS[b]],
                       deadline_s=2.0 if b == 0 else None)
            for b in range(len(LENS))]
    eng.step()
    clk.advance(5.0)               # r0's budget runs out mid-flight
    while eng.scheduler.has_work():
        eng.step()
    r0 = eng.requests[rids[0]]
    assert r0.state is RequestState.EXPIRED
    assert r0.state is not RequestState.ABORTED
    assert r0.finish_reason == "deadline"
    assert r0.blocks == [] and r0.slot is None
    assert eng.allocator.all_free
    assert eng.scheduler.expired == 1 and eng.stats()["expired"] == 1
    for b, rid in enumerate(rids[1:], start=1):
        req = eng.requests[rid]
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(
            np.asarray(req.out_tokens), dense_oracle[b][:len(req.out_tokens)])
        assert len(req.out_tokens) == MAX_NEW


def test_waiting_deadline_and_queue_ttl_expire(model_and_params):
    """WAITING rows are swept too: an end-to-end deadline and a
    queue-time TTL both expire a never-admitted request."""
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk, max_num_seqs=1)
    r0 = eng.submit([3, 4, 5, 6])                       # hogs the one slot
    r1 = eng.submit([7, 8], deadline_s=1.0)             # will run dry
    r2 = eng.submit([9, 10], max_queue_s=2.0)           # TTL'd in queue
    eng.step()
    clk.advance(3.0)
    eng.step()
    assert eng.requests[r1].state is RequestState.EXPIRED
    assert eng.requests[r1].finish_reason == "deadline"
    assert eng.requests[r2].state is RequestState.EXPIRED
    assert eng.requests[r2].finish_reason == "queue_ttl"
    eng.run()
    assert eng.requests[r0].state is RequestState.FINISHED
    assert eng.allocator.all_free


def test_admission_budget_check_never_admits_guaranteed_miss():
    """A request whose remaining budget cannot cover its prompt's minimum
    prefill time (EWMA-priced) expires at the admission boundary instead
    of occupying a slot."""
    clk = VirtualClock()
    s = _sched(clock=clk, max_num_seqs=1, prefill_chunk=4)
    s.note_step_time(1.0)          # 1s per step, so 8 tokens = 2 steps min
    doomed = _req(0, n_prompt=8, deadline_s=1.5)
    ok = _req(1, n_prompt=4, deadline_s=10.0)
    s.add(doomed)
    s.add(ok)
    plan = s.schedule()
    assert doomed.state is RequestState.EXPIRED
    assert doomed.finish_reason == "budget"
    assert [w.req.rid for w in plan.active] == [1]
    assert s.admissions == 1 and s.expired == 1
    # without an observed step time the check is disabled (no estimate)
    s2 = _sched(max_num_seqs=1)
    tight = _req(2, n_prompt=8, deadline_s=0.5)
    s2.add(tight)
    assert s2.schedule() is not None
    assert tight.state is RequestState.PREFILL


# ---------------------------------------------------------------------------
# Admission control / load shedding
# ---------------------------------------------------------------------------
def _hog_slot(s):
    """Admit one request into the single slot so later adds stay WAITING."""
    hog = _req(1000, n_prompt=4, max_new=8)
    s.add(hog)
    s.schedule()
    assert hog.slot is not None
    return hog


def test_shed_reject_newest():
    s = _sched(max_num_seqs=1, max_waiting=2, shed_policy="reject_newest")
    _hog_slot(s)
    a, b, c = _req(0), _req(1), _req(2)
    assert s.add(a) == [] and s.add(b) == []
    out = s.add(c)
    assert out == [RequestRejected(rid=2, reason="queue_full",
                                   policy="reject_newest")]
    assert c.state is RequestState.REJECTED and c.finished
    assert c.finish_reason == "queue_full"
    assert [r.rid for r in s.waiting] == [0, 1]
    assert s.rejected == 1


def test_shed_reject_oldest():
    s = _sched(max_num_seqs=1, max_waiting=2, shed_policy="reject_oldest")
    _hog_slot(s)
    a, b, c = _req(0), _req(1), _req(2)
    s.add(a)
    s.add(b)
    out = s.add(c)
    assert [o.rid for o in out] == [0]           # head-drop: oldest goes
    assert a.state is RequestState.REJECTED
    assert [r.rid for r in s.waiting] == [1, 2]


def test_shed_by_deadline_drops_least_remaining_budget():
    clk = VirtualClock()
    s = _sched(clock=clk, max_num_seqs=1, max_waiting=2,
               shed_policy="by_deadline")
    _hog_slot(s)
    tight = _req(0, deadline_s=1.0)
    loose = _req(1, deadline_s=100.0)
    s.add(tight)
    s.add(loose)
    newcomer = _req(2, deadline_s=50.0)
    out = s.add(newcomer)
    assert [o.rid for o in out] == [0]            # least budget sheds
    assert [r.rid for r in s.waiting] == [1, 2]
    # all-no-deadline pool: infinite budgets shed newest-first
    s2 = _sched(max_num_seqs=1, max_waiting=1, shed_policy="by_deadline")
    _hog_slot(s2)
    s2.add(_req(0))
    out2 = s2.add(_req(1))
    assert [o.rid for o in out2] == [1]


def test_rejection_is_typed_never_raises_out_of_engine(model_and_params,
                                                       prompts,
                                                       dense_oracle):
    """An engine under queue pressure sheds as REJECTED outcomes and keeps
    serving — no exception reaches the caller, admitted work completes
    token-identically, and nothing leaks."""
    eng = _engine(model_and_params, max_num_seqs=1, max_waiting=1)
    rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
    eng.run()                                       # never raises
    states = [eng.requests[r].state for r in rids]
    n_rej = sum(s is RequestState.REJECTED for s in states)
    assert n_rej >= 1 and len(eng.rejections) == n_rej
    assert all(isinstance(o, RequestRejected) for o in eng.rejections)
    assert eng.allocator.all_free
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        if req.state is RequestState.FINISHED:
            np.testing.assert_array_equal(np.asarray(req.out_tokens),
                                          dense_oracle[b])


def test_generate_oracle_refuses_to_pad_shed_rows(model_and_params,
                                                  prompts):
    """engine.generate() is the parity oracle: a row the robustness layer
    rejected must surface as a loud error, never a silently padded (and
    silently mis-scored) output row."""
    eng = _engine(model_and_params, max_num_seqs=1, max_waiting=1)
    with pytest.raises(RuntimeError, match="did not finish"):
        eng.generate(prompts, np.asarray(LENS))
    assert eng.allocator.all_free


def test_drain_rejects_new_submissions(model_and_params):
    eng = _engine(model_and_params, max_num_seqs=2)
    r0 = eng.submit([3, 4, 5])
    eng.step()
    eng.drain()
    assert eng.requests[r0].state is RequestState.FINISHED
    r1 = eng.submit([6, 7])
    assert eng.requests[r1].state is RequestState.REJECTED
    assert eng.requests[r1].finish_reason == "draining"
    assert eng.rejections[-1].rid == r1


# ---------------------------------------------------------------------------
# Preemption-storm breaker (pins)
# ---------------------------------------------------------------------------
def _wire_active(s, req, slot, n_blocks):
    """Hand-wire an admitted request holding ``n_blocks`` (the same
    technique as the stale-RowWork regression in test_serving.py)."""
    if req in s.waiting:
        s.waiting.remove(req)
    req.slot, s.slots[slot] = slot, req
    req.blocks = s.allocator.allocate(n_blocks)
    req.num_computed = len(req.prompt)
    req.state = RequestState.DECODE


def test_fcfs_victim_selection_respects_pins():
    """Victim selection skips pinned rows at every rung: youngest UNPINNED
    goes first; when every younger row is pinned the requester parks
    ITSELF (freeing its own blocks, so the pool still makes progress)."""
    a = BlockAllocator(8)            # 7 usable
    s = _sched(a, max_num_seqs=3, block_size=4, max_model_len=40)
    old = _req(0, n_prompt=4, max_new=8)
    mid = _req(1, n_prompt=4, max_new=8)
    young = _req(2, n_prompt=4, max_new=8)
    for r in (old, mid, young):
        s.add(r)
    _wire_active(s, old, 0, 2)
    _wire_active(s, mid, 1, 2)
    _wire_active(s, young, 2, 2)
    hold = a.allocate(a.free_blocks)          # pool genuinely dry
    # case A: young pinned, mid unpinned -> mid is the victim (NOT young,
    # even though young is strictly younger)
    young.pinned = True
    assert s._ensure_blocks(old, 12)          # needs a 3rd block
    assert mid.state is RequestState.WAITING and mid.blocks == []
    assert mid.preemptions == 1
    assert young.slot == 2 and len(young.blocks) == 2
    # case B: every younger row pinned -> the requester parks itself
    a.free(old.blocks[2:])                    # drop the grown block
    old.blocks = old.blocks[:2]
    hold2 = a.allocate(a.free_blocks)         # dry again
    assert not s._ensure_blocks(old, 12)
    assert old.state is RequestState.WAITING and old.blocks == []
    assert old.preemptions == 1
    assert young.slot == 2 and len(young.blocks) == 2   # never victimized
    a.free(hold + hold2)


def test_max_preemptions_pins_and_run_completes(model_and_params, prompts,
                                                dense_oracle):
    """Under sustained KV pressure with max_preemptions=1, preempted
    requests pin after one eviction, recompute cannot livelock, and the
    full run still finishes token-identically."""
    eng = _engine(model_and_params, max_model_len=32, num_kv_blocks=9,
                  max_preemptions=1)
    out = eng.generate(prompts, np.asarray(LENS))
    np.testing.assert_array_equal(out, dense_oracle)
    assert eng.scheduler.preemptions >= 1
    assert eng.scheduler.pins >= 1 and eng.stats()["pinned"] >= 1
    assert any(r.pinned for r in eng.requests.values())
    assert eng.allocator.all_free


# ---------------------------------------------------------------------------
# Starvation-free sjf (deadline-aware aging)
# ---------------------------------------------------------------------------
def _drive_sjf(aging_steps, iters=120):
    """Sustained short-job arrivals against one long job on a 1-slot
    scheduler; returns (long_request, scheduler) after ``iters`` ticks."""
    s = _sched(BlockAllocator(256), max_num_seqs=1, prefill_chunk=4,
               block_size=4, max_model_len=64, policy="sjf",
               sjf_aging_steps=aging_steps)
    long = Request(rid=-1, prompt=list(range(1, 17)), max_new_tokens=2)
    s.add(long)
    rid = 0
    for _ in range(iters):
        if long.finished:
            break
        # one fresh short job per tick: classic sjf starvation pressure
        s.add(Request(rid=rid, prompt=[1, 2], max_new_tokens=1))
        rid += 1
        plan = s.schedule()
        if plan is None:
            continue
        s.finish_step(plan, {w.req.slot: 7 for w in plan.active
                             if w.samples_next})
    return long, s


def test_sjf_aging_long_job_completes_under_short_job_stream():
    long, s = _drive_sjf(aging_steps=4)
    assert long.state is RequestState.FINISHED, (
        f"long job starved: state={long.state}, computed="
        f"{long.num_computed}")
    # contrast: with aging effectively disabled the same pressure starves
    # the long job for the whole window — the failure mode aging removes
    starved, _ = _drive_sjf(aging_steps=10**9)
    assert starved.state is RequestState.WAITING


def test_sjf_aging_tiebreaks_by_deadline_budget():
    clk = VirtualClock()
    s = _sched(clock=clk, max_num_seqs=1, policy="sjf", sjf_aging_steps=32)
    _hog_slot(s)
    urgent = _req(0, n_prompt=4, deadline_s=5.0)
    lazy = _req(1, n_prompt=4, deadline_s=500.0)
    s.add(lazy)
    s.add(urgent)
    now = clk()
    assert s._policy_key(urgent, now) < s._policy_key(lazy, now)


# ---------------------------------------------------------------------------
# Watchdog + drain
# ---------------------------------------------------------------------------
def test_watchdog_recovers_genuine_no_progress_livelock(
        model_and_params, prompts, dense_oracle):
    """Steps that produce NOTHING while work is pending (a stuck admission
    loop — here: the pool drained by an external leak) start the
    no-progress window; once it spans watchdog_s the engine recovers, and
    after the obstruction clears the run completes token-identically."""
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk, watchdog_s=10.0)
    rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
    stolen = eng.allocator.allocate(eng.allocator.free_blocks)  # the leak
    assert eng.step() == [] and eng._no_progress_since is not None
    clk.advance(60.0)              # the no-progress window spans > 10s
    eng.step()                     # watchdog fires before this plan
    assert eng.watchdog_recoveries == 1
    eng.allocator.free(stolen)     # the obstruction clears
    eng.run()
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(req.out_tokens),
                                      dense_oracle[b])
    assert eng.allocator.all_free


def test_caller_pause_between_steps_is_not_a_wedge(model_and_params,
                                                   prompts, dense_oracle):
    """A healthy engine whose CALLER pauses longer than watchdog_s between
    steps must not trigger a spurious recovery: productive steps clear the
    no-progress marker, so only consecutive empty steps count."""
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk, watchdog_s=5.0)
    rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
    eng.step()                     # productive
    clk.advance(60.0)              # slow client / GC pause / other work
    eng.step()                     # still productive — NOT a wedge
    assert eng.watchdog_recoveries == 0
    assert not any(eng.requests[r].pinned for r in rids)
    eng.run()
    for b, rid in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(eng.requests[rid].out_tokens), dense_oracle[b])


def test_real_step_failure_recovers_state_then_raises(model_and_params,
                                                      prompts,
                                                      dense_oracle):
    """A genuine runtime failure out of the device step (not the drilled
    fault) propagates — a real bug stays loud — but only AFTER recovery:
    tables reclaimed, pools rebuilt, and the engine can keep stepping to a
    token-identical finish."""
    eng = _engine(model_and_params)
    rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
    eng.step()
    real_step_fn = eng.step_fn

    def broken(width):
        def fail(*a, **k):
            raise RuntimeError("xla: device halted")
        return fail

    eng.step_fn = broken
    with pytest.raises(RuntimeError, match="device halted"):
        eng.step()
    assert eng.watchdog_recoveries == 1
    assert eng.allocator.all_free          # nothing stranded mid-failure
    eng.step_fn = real_step_fn             # the runtime comes back
    eng.run()
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(req.out_tokens),
                                      dense_oracle[b])
    assert eng.allocator.all_free


def test_drain_finishes_in_flight_and_bounds_on_grace(model_and_params):
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk, max_num_seqs=2)
    active = [eng.submit([3, 4, 5]), eng.submit([6, 7])]
    queued = [eng.submit([8, 9]), eng.submit([10, 11])]
    eng.step()                      # the two slots fill; two stay WAITING
    counts = eng.drain()            # unbounded grace: in-flight finishes
    for rid in active:
        assert eng.requests[rid].state is RequestState.FINISHED
    for rid in queued:
        assert eng.requests[rid].state is RequestState.REJECTED
        assert eng.requests[rid].finish_reason == "draining"
    assert counts["finished"] == 2 and counts["rejected"] == 2
    assert eng.allocator.all_free

    # bounded drain: an exhausted grace window expires the in-flight
    # stragglers with their blocks reclaimed (virtual clock: a zero
    # budget is already past when the loop first checks)
    eng2 = _engine(model_and_params, clock=clk, max_num_seqs=2)
    r0 = eng2.submit([3, 4, 5])
    eng2.step()
    eng2.drain(grace_s=0.0)
    straggler = eng2.requests[r0]
    assert straggler.state is RequestState.EXPIRED
    assert straggler.finish_reason == "drain_deadline"
    assert eng2.allocator.all_free


def test_drain_keeps_parked_in_flight_work(model_and_params):
    """Preempted / watchdog-replayed rows sit in the waiting list but are
    ADMITTED work: a drain must let them re-admit and finish (with their
    generated tokens), rejecting only never-admitted queue traffic."""
    eng = _engine(model_and_params, max_num_seqs=2)
    r0 = eng.submit([3, 4, 5])
    fresh = eng.submit([6, 7])     # admitted alongside r0 (2 slots)
    eng.step()
    eng.step()
    parked = eng.requests[r0]
    assert parked.out_tokens       # generated something already
    eng.scheduler.requeue_for_replay(parked)    # the watchdog park
    queued = eng.submit([8, 9])    # never admitted: slots are contended
    counts = eng.drain()
    assert parked.state is RequestState.FINISHED, (
        "drain rejected admitted in-flight work")
    assert len(parked.out_tokens) == MAX_NEW
    assert eng.requests[fresh].state is RequestState.FINISHED
    assert eng.requests[queued].state is RequestState.REJECTED
    assert counts["finished"] == 2 and counts["rejected"] == 1
    assert eng.allocator.all_free


def test_shed_never_victimizes_parked_in_flight_rows():
    """A parked (preempted, possibly pinned) request in the waiting list
    is not queue traffic: reject_oldest / by_deadline shed the NEWCOMER
    when the queue holds nothing but admitted work."""
    for policy in ("reject_oldest", "by_deadline"):
        clk = VirtualClock()
        s = _sched(clock=clk, max_num_seqs=1, max_waiting=1,
                   shed_policy=policy)
        hog = _hog_slot(s)
        parked = _req(0, deadline_s=1.0)      # least budget AND oldest
        s.add(parked)
        s.waiting.remove(parked)
        parked.was_admitted = True            # it ran once...
        parked.out_tokens = [42]
        parked.pinned = True
        s.waiting.append(parked)              # ...and was parked back
        newcomer = _req(1, deadline_s=500.0)
        out = s.add(newcomer)
        assert [o.rid for o in out] == [1], policy
        assert parked in s.waiting and not parked.finished, policy
        assert hog.slot is not None


def test_queue_ttl_is_an_admission_bound_only():
    """max_queue_s drops a request that cannot even START within the TTL;
    a request that WAS admitted, ran, and was parked back (preemption /
    watchdog replay) is in-flight work — a queue timer must never discard
    its generated tokens.  Only the deadline governs it from then on."""
    clk = VirtualClock()
    a = BlockAllocator(64)
    s = _sched(a, clock=clk, max_num_seqs=2, prefill_chunk=4)
    parked = _req(0, n_prompt=4, max_new=8, max_queue_s=5.0)
    s.add(parked)
    plan = s.schedule()
    s.finish_step(plan, {parked.slot: 42})
    clk.advance(10.0)
    s._preempt(parked)             # back to WAITING, tokens in hand
    clk.advance(100.0)             # parked FAR past the TTL
    s._expire_due(clk())
    assert parked.state is RequestState.WAITING     # admitted work stays
    assert parked.out_tokens == [42]
    # a never-admitted row with the same TTL drops once it ages out
    fresh = _req(1, n_prompt=4, max_new=8, max_queue_s=5.0)
    s.add(fresh)
    clk.advance(6.0)
    s._expire_due(clk())
    assert fresh.state is RequestState.EXPIRED
    assert fresh.finish_reason == "queue_ttl"
    assert parked.state is RequestState.WAITING
    assert a.all_free              # the parked row holds no blocks


# ---------------------------------------------------------------------------
# Satellite regressions: immediate reclaim + slot-reuse aliasing
# ---------------------------------------------------------------------------
def test_abort_mid_chunked_prefill_reclaims_blocks_immediately():
    """Aborting a request between chunked-prefill steps returns its
    partially-written KV blocks to the free list RIGHT THERE — the
    free-list count is back to full before any subsequent schedule()."""
    a = BlockAllocator(64)
    s = _sched(a, max_num_seqs=2, prefill_chunk=4, block_size=4,
               max_model_len=64)
    req = _req(0, n_prompt=10, max_new=4)
    s.add(req)
    plan = s.schedule()
    s.finish_step(plan, {})
    assert req.state is RequestState.PREFILL and req.num_computed == 4
    assert a.used_blocks > 0
    s.abort(req)                    # mid-chunk: 4 of 10 prompt tokens in
    assert a.all_free, "abort must reclaim partially-written blocks " \
        "immediately, not at the next schedule()"
    assert a.free_blocks == a.num_blocks - 1
    assert req.blocks == [] and req.slot is None
    assert s.schedule() is None     # and nothing resurrects the request


def test_abort_with_identical_twin_in_queue_does_not_alias(
        model_and_params):
    """Requests compare by identity: aborting an ACTIVE request whose
    field-identical twin waits in the queue must not remove the twin from
    the waiting list (the dataclass-eq aliasing bug class)."""
    eng = _engine(model_and_params, max_num_seqs=1)
    ra = eng.submit([5, 6, 7], max_new_tokens=4)
    rb = eng.submit([5, 6, 7], max_new_tokens=4)     # identical twin
    eng.step()                        # ra admitted, rb waiting
    assert eng.requests[ra].slot is not None
    eng.abort(ra)
    assert eng.requests[ra].state is RequestState.ABORTED
    assert eng.requests[rb].state is not RequestState.ABORTED
    assert eng.requests[rb] in eng.scheduler.waiting
    eng.run()
    assert eng.requests[rb].state is RequestState.FINISHED
    assert len(eng.requests[rb].out_tokens) >= 1
    assert eng.allocator.all_free


def test_back_to_back_abort_admit_reuses_slot_within_one_step(
        model_and_params, prompts, dense_oracle):
    """The scary slot-reuse case: abort an active request and admit a new
    one into the SAME slot before the next device step — the fresh
    request's output must be oracle-identical (no stale block table, no
    stale row state rides along)."""
    eng = _engine(model_and_params, max_num_seqs=1)
    ra = eng.submit(prompts[0, :LENS[0]])
    eng.step()
    eng.step()
    old_slot = eng.requests[ra].slot
    assert old_slot == 0
    eng.abort(ra)
    rb = eng.submit(prompts[1, :LENS[1]])
    eng.step()                        # rb admitted into slot 0 this step
    assert eng.requests[rb].slot == old_slot
    eng.run()
    np.testing.assert_array_equal(
        np.asarray(eng.requests[rb].out_tokens), dense_oracle[1])
    assert eng.allocator.all_free


def test_finish_step_skips_rows_that_went_terminal_mid_step():
    """A request aborted between schedule() and finish_step() (the
    watchdog/drain window) must not have its replay state advanced or its
    sampled token consumed by stale device results."""
    a = BlockAllocator(64)
    s = _sched(a, max_num_seqs=2, prefill_chunk=4)
    req = _req(0, n_prompt=2, max_new=4)
    s.add(req)
    plan = s.schedule()
    s.abort(req)                    # lands mid-step
    done = s.finish_step(plan, {0: 42, None: 99})
    assert done == []
    assert req.num_computed == 0 and req.out_tokens == []
    assert req.state is RequestState.ABORTED
    assert a.all_free


@pytest.mark.fault
def test_fault_serve_request_abort_at_prefill_chunk_boundary(
        model_and_params, prompts, dense_oracle):
    """The armed client-cancel fires while the oldest active request is
    MID-chunked-prefill (one chunk written, more pending): its
    partially-written blocks return to the free list immediately and the
    other requests' greedy output is untouched."""
    fi.configure_faults("serve_request_abort:2")
    try:
        eng = _engine(model_and_params, prefill_chunk=4)
        rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
        eng.step()                       # chunk 1 of every prompt
        victim = min(eng.scheduler.active, key=lambda r: r.arrival)
        assert 0 < victim.num_computed < len(victim.prompt), \
            "setup: the victim must be mid-chunked-prefill"
        held = len(victim.blocks)
        assert held > 0
        free_before = eng.allocator.free_blocks
        eng.step()                       # the fault aborts the victim here
        assert victim.state is RequestState.ABORTED
        assert victim.blocks == []
        # its blocks came back even though OTHER rows grew this step:
        # free count never dips below the pre-step level minus the other
        # rows' growth plus the reclaimed table
        assert eng.allocator.free_blocks >= free_before + held - 3 * 1
        eng.run()
    finally:
        fi.reset_faults()
    assert eng.allocator.all_free
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        if req is victim:
            continue
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(req.out_tokens),
                                      dense_oracle[b])


# ---------------------------------------------------------------------------
# Fault drills (L005): serve_deadline / serve_shed / serve_watchdog_stall
# ---------------------------------------------------------------------------
@pytest.mark.fault
def test_fault_serve_deadline_expires_oldest_active(model_and_params,
                                                    prompts, dense_oracle):
    """An injected deadline expiry at the step-boundary sweep: the oldest
    active request lands in EXPIRED (blocks reclaimed), every other
    request's greedy output is token-identical — never a crash."""
    fi.configure_faults("serve_deadline:3")
    try:
        eng = _engine(model_and_params)
        rids = [eng.submit(prompts[b, :LENS[b]]) for b in range(len(LENS))]
        eng.run()
    finally:
        fi.reset_faults()
    expired = [r for r in eng.requests.values()
               if r.state is RequestState.EXPIRED]
    assert len(expired) == 1
    assert expired[0].finish_reason == "deadline(injected)"
    assert expired[0].blocks == [] and expired[0].slot is None
    assert eng.allocator.all_free
    assert eng.scheduler.expired == 1
    for b, rid in enumerate(rids):
        req = eng.requests[rid]
        if req is expired[0]:
            continue
        assert req.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(req.out_tokens),
                                      dense_oracle[b])


@pytest.mark.fault
def test_fault_serve_shed_is_typed_rejection_never_raises(model_and_params):
    """An injected admission-control drop behaves exactly like a full
    queue: a typed RequestRejected outcome, state REJECTED, no blocks
    ever held, and the NEXT submission admits normally."""
    fi.configure_faults("serve_shed:1")
    try:
        eng = _engine(model_and_params)
        r0 = eng.submit([3, 4, 5])             # no exception out of submit
        assert eng.requests[r0].state is RequestState.REJECTED
        assert eng.requests[r0].finish_reason == "shed(injected)"
        assert eng.rejections == [RequestRejected(
            rid=r0, reason="shed(injected)", policy="reject_newest")]
        r1 = eng.submit([6, 7, 8])
        eng.run()
    finally:
        fi.reset_faults()
    assert eng.requests[r1].state is RequestState.FINISHED
    assert eng.requests[r0].blocks == []
    assert eng.allocator.all_free


@pytest.mark.fault
def test_fault_serve_watchdog_stall_replays_token_identical(
        model_and_params, prompts, dense_oracle):
    """An injected wedged step mid-run: the engine aborts the in-flight
    batch, reclaims every table, rebuilds pools, and replays the admitted
    requests pinned — final greedy output token-identical, nothing
    leaked, no crash."""
    fi.configure_faults("serve_watchdog_stall:4")
    try:
        eng = _engine(model_and_params, watchdog_s=30.0)
        out = eng.generate(prompts, np.asarray(LENS))
    finally:
        fi.reset_faults()
    np.testing.assert_array_equal(out, dense_oracle)
    assert eng.watchdog_recoveries == 1
    assert eng.stats()["watchdog_recoveries"] == 1
    assert any(r.pinned for r in eng.requests.values())
    assert eng.allocator.all_free
    for r in eng.requests.values():
        assert r.state is RequestState.FINISHED


# ---------------------------------------------------------------------------
# THE OVERLOAD DRILL (acceptance): 2x capacity + armed faults, zero crashes
# ---------------------------------------------------------------------------
def test_overload_drill_2x_capacity_with_faults(model_and_params):
    """Seeded 2x-capacity Poisson trace on a virtual clock with
    ``serve_block_alloc`` + ``serve_watchdog_stall`` armed: the engine
    never crashes, shedding/expiry actually engage, every terminal
    request's blocks are back on the free list (allocator count pinned),
    and every request that COMPLETES is greedy token-identical to
    ``generate()`` — including requests replayed through watchdog
    recovery."""
    model, params = model_and_params
    rng = np.random.default_rng(42)
    n_req, max_new = 24, 6
    lens = rng.integers(4, 14, n_req)
    S = int(lens.max())
    ids = np.zeros((n_req, S), np.int64)
    for b, n in enumerate(lens):
        ids[b, :n] = rng.integers(1, 255, n)
    oracle = np.asarray(generate(
        model, params, ids, prompt_lens=lens,
        config=GenerationConfig(max_new_tokens=max_new)))

    clk = VirtualClock()
    eng = DecodeEngine(
        model, params,
        ServingConfig(kv_block_size=8, max_num_seqs=4, max_model_len=32,
                      prefill_chunk=8, num_kv_blocks=13,
                      max_waiting=3, shed_policy="by_deadline",
                      max_preemptions=2, watchdog_s=1000.0),
        generation=GenerationConfig(max_new_tokens=max_new), clock=clk)

    # ~1 step per virtual second; a request needs ~2 prefill + 6 decode
    # steps and 4 run concurrently => capacity ~ 0.5 req/s.  2x capacity:
    service_rate = 0.5
    arrivals = np.cumsum(rng.exponential(1.0 / (2 * service_rate),
                                         size=n_req))
    deadlines = rng.uniform(6.0, 16.0, n_req)

    fi.configure_faults("serve_block_alloc:5,serve_watchdog_stall:11")
    try:
        submitted = 0
        rids = {}
        guard = 0
        while submitted < n_req or eng.scheduler.has_work():
            now = clk()
            while submitted < n_req and arrivals[submitted] <= now:
                rid = eng.submit(ids[submitted, :lens[submitted]],
                                 deadline_s=float(deadlines[submitted]),
                                 max_queue_s=5.0)
                rids[rid] = submitted
                submitted += 1
            eng.step()
            clk.advance(1.0)
            guard += 1
            assert guard < 2000, "overload drill failed to converge"
    finally:
        fi.reset_faults()

    # zero crashes by construction (we got here); now the invariants:
    assert eng.allocator.all_free, (
        f"leaked blocks: {eng.allocator.used_blocks} outstanding")
    stats = eng.stats()
    assert stats["watchdog_recoveries"] >= 1
    assert stats["preemptions"] >= 1
    assert stats["rejected"] >= 1, f"no shedding engaged: {stats}"
    assert stats["expired"] >= 1, f"no expiry engaged: {stats}"
    terminal = {RequestState.FINISHED, RequestState.ABORTED,
                RequestState.EXPIRED, RequestState.REJECTED}
    finished = 0
    for rid, b in rids.items():
        req = eng.requests[rid]
        assert req.state in terminal
        assert req.blocks == [] and req.slot is None
        if req.state is RequestState.FINISHED:
            finished += 1
            np.testing.assert_array_equal(
                np.asarray(req.out_tokens), oracle[b],
                err_msg=f"request {rid} (row {b}) diverged from generate()")
    assert finished >= 1
    # goodput accounting is consistent with the state machine
    outcomes = eng.outcome_counts()
    assert sum(outcomes.values()) == n_req
    assert outcomes.get("finished", 0) == finished
    assert eng.completed_in_deadline() <= finished


# ---------------------------------------------------------------------------
# Compile-once + census with the full lifecycle churn (satellite)
# ---------------------------------------------------------------------------
def test_lifecycle_states_keep_compile_once_and_census_clean(
        model_and_params):
    """EXPIRED / REJECTED / pinned / watchdog-replayed requests are pure
    host bookkeeping: one compiled program per step width survives the
    full churn, and the decode step still lowers with zero collectives
    and zero host callbacks."""
    clk = VirtualClock()
    eng = _engine(model_and_params, clock=clk, max_model_len=32,
                  num_kv_blocks=9, max_waiting=2, max_preemptions=1,
                  watchdog_s=50.0)
    rng = np.random.default_rng(7)
    fi.configure_faults("serve_watchdog_stall:6")
    try:
        for i in range(8):
            eng.submit([int(t) for t in rng.integers(1, 255, 4 + i)],
                       deadline_s=30.0 if i % 2 else None)
            eng.step()
            clk.advance(1.0)
        clk.advance(100.0)           # every live deadline expires
        eng.run()
    finally:
        fi.reset_faults()
    stats = eng.stats()
    assert stats["watchdog_recoveries"] >= 1
    assert stats["rejected"] >= 1 or stats["expired"] >= 1
    assert sorted(eng._steps) == [1, 8]
    for width, fn in eng._steps.items():
        assert_compiles_once(fn, f"serving step width={width}")
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


# ---------------------------------------------------------------------------
# Config knobs + outcome-rate helpers
# ---------------------------------------------------------------------------
def test_serving_robustness_config_validation():
    with pytest.raises(ValueError, match="shed_policy"):
        ServingConfig(shed_policy="drop_table")
    with pytest.raises(ValueError, match="max_waiting"):
        ServingConfig(max_waiting=0)
    with pytest.raises(ValueError, match="max_preemptions"):
        ServingConfig(max_preemptions=-1)
    with pytest.raises(ValueError, match="sjf_aging_steps"):
        ServingConfig(sjf_aging_steps=True)
    with pytest.raises(ValueError, match="watchdog_s"):
        ServingConfig(watchdog_s=0)
    with pytest.raises(ValueError, match="drain_grace_s"):
        ServingConfig(drain_grace_s=-2.5)
    cfg = ServingConfig(shed_policy="none", max_waiting="null",
                        watchdog_s="", drain_grace_s=1.5)
    assert cfg.shed_policy is None and cfg.max_waiting is None
    assert cfg.watchdog_s is None and cfg.drain_grace_s == 1.5


def test_serving_robustness_knobs_validated_at_config_load(tmp_path):
    from automodel_tpu.config.loader import load_yaml_config

    cases = [
        ("serving:\n  shed_policy: drop_table\n", "serving.shed_policy"),
        ("serving:\n  max_waiting: 0\n", "serving.max_waiting"),
        ("serving:\n  max_preemptions: -3\n", "serving.max_preemptions"),
        ("serving:\n  sjf_aging_steps: 1.5\n", "serving.sjf_aging_steps"),
        ("serving:\n  watchdog_s: -1\n", "serving.watchdog_s"),
        ("serving:\n  drain_grace_s: 0\n", "serving.drain_grace_s"),
    ]
    p = tmp_path / "bad.yaml"
    for text, field in cases:
        p.write_text(text)
        with pytest.raises(ValueError, match=field.replace(".", r"\.")):
            load_yaml_config(str(p))
    p.write_text("serving:\n  shed_policy: by_deadline\n"
                 "  max_waiting: 8\n  watchdog_s: 2.5\n")
    cfg = load_yaml_config(str(p))
    assert cfg.get("serving.shed_policy") == "by_deadline"


def test_serving_robustness_knobs_revalidated_after_cli_override():
    from automodel_tpu.config.arg_parser import parse_args_and_load_config

    yaml = "examples/serve/tiny_llama_serve.yaml"
    cfg = parse_args_and_load_config(
        ["--config", yaml, "--serving.shed_policy", "reject_oldest",
         "--serving.max_waiting", "4"])
    assert cfg.get("serving.shed_policy") == "reject_oldest"
    assert cfg.get("serving.max_waiting") == 4
    with pytest.raises(ValueError, match="serving.shed_policy"):
        parse_args_and_load_config(
            ["--config", yaml, "--serving.shed_policy", "drop_table"])
    with pytest.raises(ValueError, match="serving.watchdog_s"):
        parse_args_and_load_config(
            ["--config", yaml, "--serving.watchdog_s", "-1"])


def test_example_yaml_builds_robustness_config():
    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.serving import build_serving_config

    cfg = load_yaml_config("examples/serve/tiny_llama_serve.yaml")
    scfg = build_serving_config(cfg)
    assert scfg.max_waiting is None and scfg.shed_policy is None
    assert scfg.watchdog_s is None and scfg.max_preemptions is None


def test_serve_outcome_rate_helpers():
    from automodel_tpu.training.timers import (
        SERVE_TIMERS,
        serve_expired_rate,
        serve_goodput_fraction,
        serve_shed_rate,
    )

    outcomes = {"finished": 6, "rejected": 2, "expired": 1, "aborted": 1}
    assert serve_shed_rate(outcomes) == pytest.approx(0.2)
    assert serve_expired_rate(outcomes) == pytest.approx(0.1)
    assert serve_goodput_fraction(5, outcomes) == pytest.approx(0.5)
    assert serve_shed_rate({}) == 0.0 and serve_expired_rate({}) == 0.0
    assert serve_goodput_fraction(0, {}) == 1.0
    assert SERVE_TIMERS == ("serve_step", "serve_drain", "serve_recovery")
