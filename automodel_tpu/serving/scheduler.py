"""Continuous-batching scheduler: per-request state machines over static
step slots.

Pure host logic — no jax imports, no device traffic — so the state machine
is unit-testable in microseconds and the jitted step only ever sees the
static-shape buffers the engine assembles from a :class:`StepPlan`.

The request lifecycle::

    WAITING --admit--> PREFILL --prompt done--> DECODE --eos/max--> FINISHED
       ^                  |                        |
       +---- preempt -----+------------------------+
                                       (abort -> ABORTED,
                                        deadline/TTL -> EXPIRED,
                                        load shed   -> REJECTED)

One unifying invariant drives every transition: a request's *pending*
tokens are ``(prompt + out_tokens)[num_computed:]`` — the tokens not yet
written to the KV cache.  Prefill steps consume up to ``prefill_chunk`` of
them, decode steps exactly one; whenever a step empties the pending list,
the model's sampled token for that row is appended (mid-prompt samples are
discarded).  Preemption (KV pool exhaustion, the ``serve_block_alloc``
fault point) frees a victim's blocks and resets ``num_computed`` to 0 —
the vLLM "recompute" policy: on re-admission the prompt AND the tokens
generated so far re-prefill, which under greedy decoding reproduces the
identical continuation, so a preempted request is slower, never wrong.

Look-ahead (the engine dispatches step N+1 before it fetches step N): the
per-step contract is split where the token is needed.  :meth:`Scheduler.
advance` runs at DISPATCH and does what needs no token (``num_computed``,
the COW source's release, ``Request.in_flight``: samples dispatched and not
yet delivered); :meth:`Scheduler.deliver` runs after the FETCH and does the
rest (append, eos / length, commit, retire).  While a sample is in flight
the row's next token is not on the host: ``schedule()`` plans it as a ``fed``
row (one placeholder id; the step program takes the token from the previous
step's output on the device), and leaves out a request whose delivered plus
in-flight samples already reach ``max_new_tokens`` — a finish by LENGTH is
known without the token.  A finish by EOS is learnt one step late: the row
that step already dispatched for it is dropped at delivery
(``discarded_rows``); its writes land past the sequence's end in blocks that
are freed, and the device runs its programs in order, so whoever is given
those blocks next writes them after.  A request leaves ``active`` only at
delivery, so :meth:`Scheduler.has_work` stays true while a sample is in
flight.  The pending invariant becomes: ``num_computed`` may run ahead of
``seq_len`` by the samples in flight less one.

Robustness layer (the serving-under-fire contract):

* **Deadlines & TTLs** — ``Request.deadline_s`` is an end-to-end wall
  budget from submission; ``max_queue_s`` bounds time spent WAITING.
  Both are checked at STEP BOUNDARIES (``schedule()``): an exceeded
  request transitions to the terminal ``EXPIRED`` state with its blocks
  reclaimed through the same path an abort takes — distinct from
  ``ABORTED`` so operators can tell "caller cancelled" from "we were too
  slow".  Admission never starts a request whose remaining budget cannot
  cover even its prompt's minimum prefill time (``ceil(prompt /
  prefill_chunk)`` steps at the observed EWMA step cost) — it expires
  immediately (reason ``budget``) instead of wasting pool space on a
  guaranteed miss.
* **Admission control / load shedding** — ``max_waiting`` bounds the
  queue; an over-full queue sheds per ``shed_policy``
  (``serving.shed_policy``): ``reject_newest`` (default: the newcomer
  bounces), ``reject_oldest`` (head-drop: freshest traffic wins), or
  ``by_deadline`` (the request with the least remaining budget — the one
  most likely to miss anyway — is dropped; no-deadline requests count as
  infinite budget and shed newest-first among themselves).  Shedding is
  a typed :class:`RequestRejected` outcome returned from :meth:`add`,
  NEVER an exception out of the engine loop.
* **Preemption-storm breaker** — a request preempted
  ``max_preemptions`` times is **pinned**: never victimized again (all
  policies' victim selection skips pinned rows), so sustained overload
  degrades to queueing instead of recompute livelock.  A pinned
  requester that cannot grow its own table still parks itself — that
  frees its blocks for others, so progress is preserved.
* **Starvation-free sjf** — the ``sjf`` key ages with queue time:
  ``effective = work / (1 + waited_ticks / sjf_aging_steps)``, tie-broken
  by remaining deadline budget then arrival.  A long job's effective
  priority improves every scheduler tick it waits, so sustained
  short-job arrivals can delay it, never starve it (tier-1 pinned).

Scheduling policies (``serving.scheduler_policy``):

* ``fcfs`` — admission and preemption-victim order by arrival: oldest
  admits first, youngest unpinned is preempted first (a preempted elder
  re-admits ahead of the request that displaced it).
* ``sjf``  — shortest pending work first with aging (above): better p50
  under mixed lengths without the textbook starvation failure.

Speculative decoding (``serving.speculative: ngram``): on pure-decode
steps a host-side proposer (``serving/speculative.py``) drafts up to
``spec_k`` tokens per sampling row from the row's own prompt+generated
history; the engine writes pending token + drafts in ONE step at width
``spec_k + 1`` and hands :meth:`Scheduler.deliver` the greedy argmax at EVERY
written position.  The longest draft prefix matching that chain is
accepted plus the bonus token — token-identical to plain greedy by
construction.  The pending invariant absorbs it because acceptance
advances ``num_computed`` past exactly the accepted draft tokens (they
are already in the KV cache); the bonus token is appended but NOT
counted computed, so it is the next step's pending token like any plain
decode.  Rejected draft positions sit past ``num_computed`` in private
(never committed, never shared) blocks — dead until overwritten.  Block
commit runs BEFORE acceptance on a ``num_computed`` that excludes every
draft, so an unaccepted token can never enter the prefix index.

Prefix caching (``serving.prefix_caching: on``): admission consults the
:class:`~automodel_tpu.serving.kv_cache.PrefixIndex` — a hit seeds the
request's block table with shared block ids and starts ``num_computed``
at the cached length, so chunked prefill covers only the cold tail.  A
fully-cached sequence forks its last block COPY-ON-WRITE (a private block
the jitted step copies the shared slots into, before any write).  Every
release path (finish, abort, expiry, preemption, watchdog/fleet replay)
already routes through ``allocator.free`` — now a decref — so a shared
block survives any one holder's death.  Concurrent identical prompts
(a GRPO group) are handled by DEFERRAL: a cold request whose next
uncached block is already being computed by an admitted twin waits one
tick instead of paying a duplicate prefill — the group converges to ~1
prompt prefill (the group-level rollout fork).

A cache of several BLOCK GROUPS (``serving/kv_cache.BlockGroup``: a model
with window and full layers in one stack): a request holds one block table
a group — ``Request.blocks`` in the first, ``Request.group_blocks[name]``
in each further one — and is admitted or grown only if EVERY group can
serve it; what one group lacks is ``OutOfBlocks`` for the request, and
preemption frees the victim's tables in all groups.  A group with a window
RELEASES the leading blocks of a row that lie wholly behind the window of
the row's first query of the step about to run (``Request.released``
counts them; the null page takes their place in the table), before the
step's own growth is allocated, so a row holds at most
``window_span_blocks`` of them and a released block serves another row at
once.  A cache of one group is the same code over a list of one.
"""

from __future__ import annotations

import dataclasses
import enum
import math
import time
from typing import Callable, Dict, List, Optional, Sequence

from automodel_tpu.ops.paged_attention import (
    window_first_block,
    window_span_blocks,
)
from automodel_tpu.serving.kv_cache import (
    BlockAllocator,
    BlockGroup,
    OutOfBlocks,
    PrefixIndex,
    blocks_needed,
)
from automodel_tpu.serving.speculative import DEFAULT_SPEC_K
from automodel_tpu.utils.fault_injection import InjectedFault, fault_point

# ``serving.scheduler_policy`` config domain (enum-validated at config
# load like cp_layout / moe.dispatch — see loader._enum_fields).
SCHEDULER_POLICIES = ("fcfs", "sjf")
DEFAULT_SCHEDULER_POLICY = "fcfs"

# ``serving.shed_policy`` config domain: what a FULL waiting queue drops.
SHED_POLICIES = ("reject_newest", "reject_oldest", "by_deadline")
DEFAULT_SHED_POLICY = "reject_newest"

# Queue ticks of waiting that halve an sjf job's effective length (the
# aging rate; see the module docstring).  One tick == one schedule() call.
DEFAULT_SJF_AGING_STEPS = 32


def normalize_scheduler_policy(v):
    from automodel_tpu.config.loader import normalize_null_spelling

    return normalize_null_spelling(v)


def validate_scheduler_policy(v: Optional[str]) -> Optional[str]:
    if v is None:
        return None
    if v not in SCHEDULER_POLICIES:
        raise ValueError(
            f"serving.scheduler_policy must be one of "
            f"{list(SCHEDULER_POLICIES)} (or null for the default), got "
            f"{v!r}")
    return v


def normalize_shed_policy(v):
    from automodel_tpu.config.loader import normalize_null_spelling

    return normalize_null_spelling(v)


def validate_shed_policy(v: Optional[str]) -> Optional[str]:
    if v is None:
        return None
    if v not in SHED_POLICIES:
        raise ValueError(
            f"serving.shed_policy must be one of {list(SHED_POLICIES)} "
            f"(or null for the default), got {v!r}")
    return v


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"
    ABORTED = "aborted"
    # Terminal robustness states — distinct so telemetry/operators can tell
    # "caller cancelled" (ABORTED) from "deadline/TTL ran out" (EXPIRED)
    # from "admission control dropped it" (REJECTED).
    EXPIRED = "expired"
    REJECTED = "rejected"


# Requests compare by IDENTITY (eq=False), never by field value: two
# requests with identical prompts are still distinct units of work, and
# value equality silently corrupts ``req in waiting`` / ``waiting.remove``
# bookkeeping (the slot-reuse aliasing bug class — tier-1 pinned).
@dataclasses.dataclass(eq=False)
class Request:
    """One serving request and its cache bookkeeping."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    state: RequestState = RequestState.WAITING
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    # a cache of several block groups: the tables of the groups after the
    # first, by group name, and per group the leading blocks a window has
    # released (their table entries hold the null page)
    group_blocks: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    released: Dict[Optional[str], int] = dataclasses.field(
        default_factory=dict)
    num_computed: int = 0          # tokens written to the KV cache
    slot: Optional[int] = None     # step-buffer row while active
    arrival: int = 0               # admission-order tiebreak
    preemptions: int = 0
    in_flight: int = 0             # samples dispatched, not yet delivered
    # -- multi-tenant serving ----------------------------------------------
    # adapter slot this request decodes under (0 = base model); rides the
    # step buffers as the [B] int32 routing vector and namespaces the
    # request's prefix-cache hash chain
    adapter_id: int = 0
    # -- robustness layer --------------------------------------------------
    deadline_s: Optional[float] = None   # end-to-end budget from submit
    max_queue_s: Optional[float] = None  # WAITING-time TTL
    submit_time: float = 0.0             # scheduler-clock stamp at add()
    submit_tick: int = 0                 # schedule()-tick stamp at add()
    pinned: bool = False                 # never victimized once set
    finish_reason: Optional[str] = None  # why a terminal state was entered
    finish_time: Optional[float] = None  # clock stamp at the terminal state
    admit_time: Optional[float] = None   # clock stamp at the FIRST slot
    admit_tick: int = 0                  # schedule()-tick stamp beside it
    first_token_time: Optional[float] = None  # clock stamp, first out token
    # Parked in-flight rows (preempted / watchdog-replayed) re-enter the
    # waiting list but are NOT queue traffic: shedding, drain rejection
    # and the queue TTL all treat them as admitted work — only the
    # deadline (and pool pressure) governs them after first admission.
    was_admitted: bool = False           # ever held a step slot
    # -- prefix caching ----------------------------------------------------
    # A pending COW fork: the step copies block cow_src -> cow_dst before
    # writing; the src ref is HELD until the copy rode a step (or the
    # request released), so the shared source can never be reclaimed and
    # rewritten underneath the fork.
    cow_src: Optional[int] = None
    cow_dst: Optional[int] = None
    chain_key: Optional[str] = None      # hash-chain parent of the next commit
    committed_blocks: int = 0            # leading blocks already indexed
    # uncached chain keys this admitted request will commit (the deferral
    # signal concurrent identical prompts wait on)
    inflight_keys: List[str] = dataclasses.field(default_factory=list)

    @property
    def seq(self) -> List[int]:
        return self.prompt + self.out_tokens

    @property
    def seq_len(self) -> int:
        return len(self.prompt) + len(self.out_tokens)

    @property
    def pending(self) -> List[int]:
        """``seq[num_computed:]`` without building ``seq``: the scheduler
        asks this of every active row every step, and a decode row's
        pending token is its last output, not a copy of its whole context
        (64 rows of 5k tokens were 4 ms a step of list building)."""
        n, p = self.num_computed, len(self.prompt)
        if n >= p:
            return self.out_tokens[n - p:]
        return self.prompt[n:] + self.out_tokens

    @property
    def finished(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.ABORTED,
                              RequestState.EXPIRED, RequestState.REJECTED)

    def remaining_budget(self, now: float) -> float:
        """Seconds of deadline budget left (inf without a deadline)."""
        if self.deadline_s is None:
            return math.inf
        return self.deadline_s - (now - self.submit_time)


def _us(seconds: float) -> int:
    return int(round(seconds * 1e6))


@dataclasses.dataclass(frozen=True)
class RequestRejected:
    """The typed load-shed outcome: admission control dropped ``rid``.

    Returned from :meth:`Scheduler.add` / recorded by the engine — never
    raised, so an overloaded engine loop keeps stepping instead of
    unwinding (the serving-under-fire contract)."""

    rid: int
    reason: str                 # queue_full | draining | shed(injected)
    policy: Optional[str] = None


@dataclasses.dataclass
class RowWork:
    """One step-buffer row's work: ``tokens`` written at positions
    ``start_pos..start_pos+len(tokens)-1``; ``samples_next`` marks the row
    whose sampled token extends the request (pending emptied)."""

    req: Request
    tokens: List[int]
    start_pos: int
    samples_next: bool
    # (src, dst) whole-block COW copy the step must run BEFORE this row's
    # writes; None for the common no-fork case
    cow: Optional[tuple] = None
    # speculative draft tokens written (and verified) AFTER ``tokens`` at
    # positions start_pos+len(tokens).. — deliberately NOT part of
    # ``tokens``: drafts are a guess about the future, never pending work,
    # and ``num_computed`` only ever advances past the accepted ones
    draft: List[int] = dataclasses.field(default_factory=list)
    # the row's one token is the previous step's sample, still on the
    # device: ``tokens`` holds ``FED_TOKEN`` in its place and the step
    # program takes the real one from that step's output
    fed: bool = False
    # ``req.preemptions`` as the plan saw it: a delivery that finds another
    # count belongs to a life of the request that was preempted since
    preemptions: int = 0


# what a ``fed`` row carries in ``tokens`` (never read by the model)
FED_TOKEN = 0


@dataclasses.dataclass
class StepPlan:
    rows: List[Optional[RowWork]]      # len == max_num_seqs, None = idle
    # 1 (pure decode), spec_k+1 (pure decode, speculation on — ALWAYS,
    # even when every draft came back empty: draft length is data, not
    # shape) or prefill_chunk (any row still prefilling)
    step_width: int
    tick: int = 0                      # the schedule() call that made it

    @property
    def active(self) -> List[RowWork]:
        return [r for r in self.rows if r is not None]


def _seen(start: int, n: int, window: Optional[int]) -> int:
    """Keys the queries at positions ``start .. start + n - 1`` see, summed:
    ``p + 1`` each, or ``min(p + 1, window)``."""
    lo, hi = start + 1, start + n           # what each sees without a window
    if window is None or hi <= window:
        return (lo + hi) * n // 2
    if lo > window:
        return window * n
    return (lo + window) * (window - lo + 1) // 2 + window * (hi - window)


class Scheduler:
    """Admission + step assembly + preemption over ``max_num_seqs`` slots."""

    def __init__(self, allocator: BlockAllocator, *, max_num_seqs: int,
                 prefill_chunk: int, block_size: Optional[int],
                 max_model_len: int,
                 policy: str = DEFAULT_SCHEDULER_POLICY,
                 max_waiting: Optional[int] = None,
                 shed_policy: str = DEFAULT_SHED_POLICY,
                 max_preemptions: Optional[int] = None,
                 sjf_aging_steps: int = DEFAULT_SJF_AGING_STEPS,
                 prefix_index: Optional[PrefixIndex] = None,
                 spec_proposer: Optional[Callable] = None,
                 spec_k: int = DEFAULT_SPEC_K,
                 tenant_quota: Optional[int] = None,
                 multi_tenant: bool = False,
                 clock: Callable[[], float] = time.monotonic,
                 event: Optional[Callable[..., None]] = None,
                 groups: Optional[Sequence[BlockGroup]] = None):
        policy = validate_scheduler_policy(normalize_scheduler_policy(policy))
        shed_policy = validate_shed_policy(
            normalize_shed_policy(shed_policy))
        self.allocator = allocator
        # the cache's block groups; ``allocator`` is the first group's
        self.groups: List[BlockGroup] = (
            list(groups) if groups else [BlockGroup(None, allocator)])
        if self.groups[0].allocator is not allocator:
            raise ValueError("the first block group's allocator must be "
                             "the scheduler's own")
        # blocks a window released while their request ran, by group name
        self.blocks_released: Dict[Optional[str], int] = {
            g.name: 0 for g in self.groups if g.window is not None}
        self.max_num_seqs = max_num_seqs
        self.prefill_chunk = prefill_chunk
        # None: the cache keeps per-SEQUENCE state (``kv_cache.
        # StatePlaneView``) and a token costs no block — admission is then
        # bounded by the step slots alone and the allocator never binds
        self.block_size = block_size
        self.max_model_len = max_model_len
        self.policy = policy or DEFAULT_SCHEDULER_POLICY
        self.max_waiting = max_waiting
        self.shed_policy = shed_policy or DEFAULT_SHED_POLICY
        self.max_preemptions = max_preemptions
        self.sjf_aging_steps = sjf_aging_steps or DEFAULT_SJF_AGING_STEPS
        self.clock = clock
        # request stamps for the trace (``Timers.event``, handed in by the
        # engine: this module imports no jax); integers, on ``clock``
        self._event = event or (lambda name, **stats: None)
        self.draining = False
        self.waiting: List[Request] = []
        self.slots: List[Optional[Request]] = [None] * max_num_seqs
        self._arrivals = 0
        self._ticks = 0                # schedule() calls (the aging clock)
        self._step_time_ewma: Optional[float] = None
        self.preemptions = 0
        self.admissions = 0
        self.expired = 0
        self.rejected = 0
        self.pins = 0
        # -- prefix caching (counters live even with the index off, so
        # engine/fleet stats read one shape either way) -------------------
        self.prefix_index = prefix_index
        self.prefix_tokens_reused = 0    # prompt tokens NOT re-prefilled
        self.prompt_tokens = 0           # all submitted prompt tokens
        self.cow_forks = 0
        self.cow_fork_failures = 0
        self.prefix_deferrals = 0
        # chain key -> count of admitted requests about to commit it (the
        # deferral signal for concurrent identical prompts)
        self._inflight_keys: Dict[str, int] = {}
        # -- multi-tenant serving -----------------------------------------
        # ``tenant_quota`` caps CONCURRENT slot-holders per adapter id;
        # ``multi_tenant`` gates the sjf tenant fair-share term (off, the
        # sjf key is bit-identical to the single-tenant scheduler)
        self.tenant_quota = tenant_quota
        self.multi_tenant = multi_tenant
        self.tenant_quota_deferrals = 0
        # adapter id -> {"submitted","admitted","finished","tokens"}
        self.per_tenant: Dict[int, Dict[str, int]] = {}
        # -- speculative decoding (serving/speculative.py) ----------------
        # proposer None == off; pure-decode steps then keep width 1 and
        # every spec branch below is dead code (spec-off bit-unchanged)
        self.spec_proposer = spec_proposer
        self.spec_k = spec_k
        self._spec_width = (spec_k + 1) if spec_proposer is not None else 1
        self.spec_tokens_proposed = 0    # drafts that reached a verify step
        self.spec_tokens_accepted = 0
        self.spec_draft_faults = 0
        self.spec_verify_failures = 0
        self.tokens_appended = 0         # out_tokens grown, all rows
        # rows a step computed for nothing: delivered to a request that had
        # finished (an EOS learnt one step late, an abort) or been preempted
        self.discarded_rows = 0
        # Accepted-tokens-per-sampling-row EWMA: the admission budget
        # guard prices prefill in STEPS, and speculation makes one step
        # worth >1 token — dividing the priced step count by this keeps
        # admission from spuriously rejecting under speculation.  Spec-off
        # every sampling row appends exactly one token, so the EWMA stays
        # exactly 1.0 and the guard's arithmetic is bit-unchanged.
        self._tokens_per_row_ewma = 1.0

    # -- intake ------------------------------------------------------------
    def add(self, req: Request) -> List[RequestRejected]:
        """Queue one request.  Returns the typed :class:`RequestRejected`
        outcomes this admission caused — empty when ``req`` simply joined
        the queue; under ``reject_oldest`` the victim may be a DIFFERENT
        (older) request.  Impossible requests (can never fit the pool /
        model length) still raise ``ValueError``: that is a caller bug,
        not load."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"max_new_tokens {req.max_new_tokens} exceeds "
                f"serving.max_model_len {self.max_model_len}")
        for g in self.groups[1:]:
            if self._most_blocks(g, total) > g.allocator.num_blocks - 1:
                raise ValueError(
                    f"request {req.rid} needs {self._most_blocks(g, total)} "
                    f"KV blocks of group {g.name!r} but its pool has "
                    f"{g.allocator.num_blocks - 1} — raise "
                    "serving.num_kv_blocks / max_model_len")
        worst = self._most_blocks(self.groups[0], total)
        if self.prefix_index is not None:
            # A prefix hit means the leading cached blocks are SHARED, not
            # consumed: discount them from the worst case (keeping a
            # one-block margin for the COW fork) so a request whose prompt
            # is fully cached is not rejected for a pool it will barely
            # touch.  The pool-pressure machinery (preemption/parking)
            # still governs actual growth.
            cached = self.prefix_index.peek(
                self.prefix_index.chain_keys(req.prompt, req.adapter_id))
            worst -= max(0, cached - 1)
        if worst > self.allocator.num_blocks - 1:
            raise ValueError(
                f"request {req.rid} needs {worst} KV blocks but the "
                f"pool has {self.allocator.num_blocks - 1} — raise "
                "serving.num_kv_blocks / max_model_len")
        self.prompt_tokens += len(req.prompt)
        self._tenant(req)["submitted"] += 1
        req.arrival = self._arrivals
        self._arrivals += 1
        req.submit_time = self.clock()
        req.submit_tick = self._ticks
        req.state = RequestState.WAITING
        if self.draining:
            return [self._reject(req, "draining")]
        # The drilled load-shed site: an armed ``serve_shed`` behaves
        # exactly like a full waiting queue — the contract is a typed
        # rejection, never an exception out of the engine loop.
        try:
            fault_point("serve_shed")
        except InjectedFault:
            return [self._reject(req, "shed(injected)")]
        out: List[RequestRejected] = []
        if self.max_waiting is not None:
            now = req.submit_time
            while len(self.waiting) >= self.max_waiting:
                victim = self._shed_victim(req, now)
                out.append(self._reject(victim, "queue_full"))
                if victim is req:
                    return out
        self.waiting.append(req)
        return out

    def _shed_victim(self, newcomer: Request, now: float) -> Request:
        # Parked in-flight rows (preempted / watchdog-replayed) are never
        # shed candidates: they are admitted work — rejecting them would
        # discard generated tokens and re-victimize pinned requests.  When
        # the queue holds nothing BUT parked rows, the newcomer bounces.
        fresh = [r for r in self.waiting if not r.was_admitted]
        if self.shed_policy == "reject_oldest":
            if not fresh:
                return newcomer
            return min(fresh, key=lambda r: r.arrival)
        if self.shed_policy == "by_deadline":
            # drop the request most likely to miss anyway: least remaining
            # budget first; no-deadline requests (inf budget) shed
            # newest-first among themselves
            return min(fresh + [newcomer],
                       key=lambda r: (r.remaining_budget(now), -r.arrival))
        return newcomer                                  # reject_newest

    def _reject(self, req: Request, reason: str) -> RequestRejected:
        if req in self.waiting:
            self.waiting.remove(req)
        self._terminal(req, RequestState.REJECTED, reason)
        self.rejected += 1
        return RequestRejected(rid=req.rid, reason=reason,
                               policy=self.shed_policy)

    def abort(self, req: Request) -> None:
        """Cancel anywhere in the lifecycle: frees the block table
        IMMEDIATELY (mid-chunked-prefill included — partially-written KV
        blocks return to the free list right here, never deferred to the
        next ``schedule()``), vacates the slot — the
        ``serve_request_abort`` contract."""
        if req.finished:
            return
        self._release(req)
        self._terminal(req, RequestState.ABORTED, "abort")

    def expire(self, req: Request, reason: str = "deadline") -> None:
        """Deadline/TTL cancellation: same reclaim path as an abort but the
        terminal state is EXPIRED — "we were too slow", not "caller
        cancelled"."""
        if req.finished:
            return
        self._release(req)
        self._terminal(req, RequestState.EXPIRED, reason)
        self.expired += 1

    def _terminal(self, req: Request, state: RequestState,
                  reason: str) -> None:
        """Every way a request ends passes here: state, reason, stamp, and
        the ``serve_finish_request`` event (``decode_us``: first token to
        the end; 0 for a request that never produced one)."""
        req.state = state
        req.finish_reason = reason
        req.finish_time = self.clock()
        first = req.first_token_time
        self._event(
            "serve_finish_request", rid=req.rid,
            decode_us=_us(req.finish_time - first) if first is not None else 0,
            out_tokens=len(req.out_tokens), state=state.value)

    def _release(self, req: Request) -> None:
        """Vacate slot + decref the whole block table (and any pending COW
        source ref) back to the allocator."""
        if req in self.waiting:
            self.waiting.remove(req)
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None
        self._drop_chain_state(req)
        self._free_tables(req)

    def _drop_chain_state(self, req: Request) -> None:
        """Forget a request's prefix-chain bookkeeping: release the held
        COW-source ref and the in-flight commit claims.  The block TABLE
        is the caller's to free — this never touches ``req.blocks``."""
        if req.cow_src is not None:
            self.allocator.free([req.cow_src])
        req.cow_src = None
        req.cow_dst = None
        req.chain_key = None
        req.committed_blocks = 0
        self._unregister_inflight(req)

    def requeue_for_replay(self, req: Request) -> None:
        """Watchdog recovery: park an admitted request back to WAITING with
        its blocks reclaimed and ``num_computed`` reset — the recompute
        replay re-prefills prompt + generated-so-far, so greedy output
        stays token-identical.  The replayed request is PINNED (never
        re-victimized) so recovery cannot stack preemptions on top of the
        stall it just absorbed."""
        if req.finished:
            return
        self._release(req)
        req.num_computed = 0
        req.in_flight = 0
        req.state = RequestState.WAITING
        req.pinned = True
        if req not in self.waiting:
            self.waiting.append(req)

    def adopt_replay(self, req: Request) -> None:
        """Adopt an admitted request harvested from ANOTHER scheduler
        (fleet replica loss — ``serving/fleet.py``): same recompute-replay
        parking as :meth:`requeue_for_replay`, but the row also gets THIS
        scheduler's arrival/tick stamps so aging and step-relative
        bookkeeping stay monotone.  ``submit_time`` is deliberately KEPT —
        the fleet shares one clock, and a replayed request's deadline/TTL
        budget is end-to-end, not per-engine.  The row arrives with no
        slot/blocks (the dead engine's harvest already released them) and
        stays ``was_admitted``+pinned, so shed/drain/TTL never discard it."""
        if req.finished:
            return
        req.arrival = self._arrivals
        self._arrivals += 1
        req.submit_tick = self._ticks
        req.slot = None
        req.blocks, req.group_blocks, req.released = [], {}, {}
        req.num_computed = 0
        req.in_flight = 0
        # the dead engine's chain state died with its pools: the refs were
        # released by the harvest, and THIS scheduler's index re-seeds on
        # re-admission
        req.cow_src = None
        req.cow_dst = None
        req.chain_key = None
        req.committed_blocks = 0
        req.inflight_keys = []
        req.state = RequestState.WAITING
        req.pinned = True
        if req not in self.waiting:
            self.waiting.append(req)

    @property
    def active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    def note_step_time(self, seconds: float) -> None:
        """Feed one observed device-step wall time into the EWMA the
        admission budget check prices prefill steps with."""
        if seconds <= 0:
            return
        if self._step_time_ewma is None:
            self._step_time_ewma = seconds
        else:
            self._step_time_ewma = 0.5 * self._step_time_ewma + 0.5 * seconds

    # -- internals ---------------------------------------------------------
    def _tenant(self, req: Request) -> Dict[str, int]:
        d = self.per_tenant.get(req.adapter_id)
        if d is None:
            d = {"submitted": 0, "admitted": 0, "finished": 0, "tokens": 0}
            self.per_tenant[req.adapter_id] = d
        return d

    def _tenant_active(self, adapter_id: int) -> int:
        return sum(1 for r in self.active if r.adapter_id == adapter_id)

    def _policy_key(self, req: Request, now: float):
        if self.policy == "sjf":
            work = (len(req.pending) + req.max_new_tokens
                    - len(req.out_tokens))
            waited = self._ticks - req.submit_tick
            aged = work / (1.0 + waited / float(self.sjf_aging_steps))
            if self.multi_tenant:
                # tenant fair-share: a tenant already holding k slots sees
                # its next request's effective work scaled by (1 + k), so
                # under contention idle tenants admit first.  Uniform
                # traffic (all one tenant) scales every key by the same
                # factor — ordering, and base-only behavior, unchanged.
                aged *= 1.0 + self._tenant_active(req.adapter_id)
            return (aged, req.remaining_budget(now), req.arrival)
        return req.arrival                                   # fcfs

    def _blocks_for(self, tokens: int) -> int:
        """Blocks that ``tokens`` cached positions take: none where the
        cache is per-sequence state."""
        if self.block_size is None:
            return 0
        return blocks_needed(tokens, self.block_size)

    def _most_blocks(self, group: BlockGroup, tokens: int) -> int:
        """The most blocks of ``group`` a request of ``tokens`` positions
        holds at once: a window keeps what a step's queries can see."""
        n = self._blocks_for(tokens)
        if group.window is None or self.block_size is None:
            return n
        return min(n, window_span_blocks(group.window, self.prefill_chunk,
                                         self.block_size))

    def _tables(self, req: Request):
        """``(group, table)`` of every block group: ``req.blocks`` in the
        first, ``req.group_blocks[name]`` in each further one."""
        first = (self.groups[0], req.blocks)
        if len(self.groups) == 1:       # every step asks, of every row
            return (first,)
        return [first] + [(g, req.group_blocks.setdefault(g.name, []))
                          for g in self.groups[1:]]

    def _free_tables(self, req: Request) -> None:
        """Decref every live block of ``req`` in every group (an entry a
        window released holds the null page and is nobody's)."""
        for g, table in self._tables(req):
            if g.window is not None:
                table = [b for b in table if b]
            if table:
                g.allocator.free(table)
        req.blocks = []
        req.group_blocks = {}
        req.released = {}

    def _release_behind_window(self, req: Request) -> None:
        """Release, in every group with a window, the leading blocks that
        lie wholly behind the window of the row's next query (position
        ``num_computed``) and so of every later one."""
        if not self.blocks_released:
            return                      # no group has a window
        for g, table in self._tables(req):
            if g.window is None:
                continue
            done = req.released.get(g.name, 0)
            dead = min(window_first_block(req.num_computed, g.window,
                                          self.block_size), len(table))
            if dead > done:
                g.allocator.free(table[done:dead])
                table[done:dead] = [0] * (dead - done)
                req.released[g.name] = dead
                self.blocks_released[g.name] += dead - done

    @property
    def all_free(self) -> bool:
        """The leak oracle over every block group."""
        return all(g.allocator.all_free for g in self.groups)

    def keys_read(self, plan: "StepPlan") -> Dict[Optional[str], int]:
        """Per block group, the keys one of its layers must read in
        ``plan``'s step: over the active rows, the context, or what of it
        the group's window still shows."""
        ctx = [w.start_pos + len(w.tokens) for w in plan.active]
        return {g.name: sum(c if g.window is None else min(c, g.window)
                            for c in ctx) for g in self.groups}

    def pairs_read(self, plan: "StepPlan") -> Dict[Optional[str], int]:
        """Per block group, the (query, key) pairs one of its layers must
        compute in ``plan``'s step: over the active rows and their new
        positions, the keys each one sees (all before it and itself, or
        what of them the group's window shows)."""
        return {g.name: sum(_seen(w.start_pos, len(w.tokens), g.window)
                            for w in plan.active) for g in self.groups}

    def _allocate(self, req: Request, new_total: int) -> None:
        """Grow every group's table of ``req`` to ``new_total`` positions,
        all groups or none (:class:`OutOfBlocks` from the group that
        lacks, nothing handed out)."""
        blocks = self._blocks_for(new_total)
        wants = [(g, table, blocks - len(table))
                 for g, table in self._tables(req)]
        if not any(need > 0 for _, _, need in wants):
            return
        # The drilled KV-exhaustion site: an armed ``serve_block_alloc``
        # fires here exactly like a genuinely empty free list, and the
        # caller's preemption path must absorb both identically.
        fault_point("serve_block_alloc")
        for g, _, need in wants[1:]:
            if need > g.allocator.free_blocks:
                g.allocator.allocate(need)      # raises, and counts it
        for g, table, need in wants:
            if need > 0:
                table.extend(g.allocator.allocate(need))

    def _preempt(self, victim: Request) -> None:
        assert victim.slot is not None
        self.slots[victim.slot] = None
        victim.slot = None
        self._drop_chain_state(victim)
        self._free_tables(victim)
        victim.num_computed = 0          # recompute policy (see docstring)
        victim.in_flight = 0             # its delivery will find it stale
        victim.state = RequestState.WAITING
        victim.preemptions += 1
        self.preemptions += 1
        if (self.max_preemptions is not None and not victim.pinned
                and victim.preemptions >= self.max_preemptions):
            # the preemption-storm breaker: from here on this request is
            # never re-victimized, so recompute cannot livelock
            victim.pinned = True
            self.pins += 1
        self.waiting.append(victim)

    def _ensure_blocks(self, req: Request, new_total: int) -> bool:
        """Grow ``req``'s block table to cover ``new_total`` positions,
        preempting strictly-younger UNPINNED active requests (youngest
        first) while the pool is exhausted; parks ``req`` itself when no
        victim remains.  Returns False when ``req`` was preempted.  What a
        window no longer shows is released first: it may be all the step
        needs."""
        self._release_behind_window(req)
        need = self._blocks_for(new_total) - len(req.blocks)
        while True:
            try:
                self._allocate(req, new_total)
                return True
            except (OutOfBlocks, InjectedFault) as e:
                younger = [r for r in self.active
                           if r is not req and r.arrival > req.arrival
                           and not r.pinned]
                if younger:
                    self._preempt(max(younger, key=lambda r: r.arrival))
                    continue
                if (len(self.active) > 1 or req.blocks
                        or any(req.group_blocks.values())
                        or isinstance(e, InjectedFault)):
                    # an injected alloc failure is always absorbed as a
                    # preemption (the drilled contract: never a crash);
                    # genuine exhaustion only raises in the provably
                    # impossible solo-request-no-blocks state below.  A
                    # pinned requester still parks ITSELF — that is not a
                    # victimization, and holding a half-grown table would
                    # deadlock the pool.
                    self._preempt(req)
                    return False
                raise OutOfBlocks(
                    f"request {req.rid} alone cannot fit: needs {need} more "
                    f"blocks, pool has {self.allocator.num_blocks - 1} "
                    "total — raise serving.num_kv_blocks")

    def _min_prefill_s(self, req: Request) -> Optional[float]:
        """Lower bound on wall time to prefill ``req``'s pending tokens —
        ``ceil(pending / prefill_chunk)`` steps at the EWMA step cost
        (None before any step has been observed)."""
        if self._step_time_ewma is None:
            return None
        steps = blocks_needed(len(req.pending), self.prefill_chunk)
        # normalize by accepted-tokens-per-row: under speculation the EWMA
        # step cost is a WIDE (spec_k+1) step worth >1 token of progress,
        # so pricing prefill at the raw step cost would overcharge and
        # spuriously expire admissible requests.  Spec-off the divisor is
        # exactly 1.0 (x / 1.0 is bitwise x — behavior unchanged).
        return steps * self._step_time_ewma / self._tokens_per_row_ewma

    def _expire_due(self, now: float) -> None:
        """The step-boundary deadline sweep (active AND waiting rows),
        plus queue-TTL enforcement on waiting rows."""
        # The drilled deadline site: an armed ``serve_deadline`` models
        # the oldest active request's deadline firing right now —
        # terminal EXPIRED, blocks reclaimed, every other row unaffected.
        try:
            fault_point("serve_deadline")
        except InjectedFault:
            victims = self.active
            if victims:
                self.expire(min(victims, key=lambda r: r.arrival),
                            reason="deadline(injected)")
        for req in list(self.active):
            if req.remaining_budget(now) <= 0:
                self.expire(req, reason="deadline")
        for req in list(self.waiting):
            if req.remaining_budget(now) <= 0:
                self.expire(req, reason="deadline")
            elif (not req.was_admitted and req.max_queue_s is not None
                    and now - req.submit_time > req.max_queue_s):
                # the TTL is an ADMISSION bound ("drop me if I can't even
                # start within X"): a request that was admitted, ran, and
                # was parked back is in-flight work — discarding its
                # generated tokens on a queue timer would be a silent
                # data loss; only its deadline governs it now
                self.expire(req, reason="queue_ttl")

    # -- prefix caching ----------------------------------------------------
    def _try_prefix_seed(self, req: Request) -> bool:
        """Consult the prefix index for ``req`` at the admission boundary:
        a hit seeds the block table with shared ids and fast-forwards
        ``num_computed`` (chunked prefill covers only the cold tail); a
        fully-cached sequence forks its last block copy-on-write.  Returns
        True when admission should be DEFERRED this tick — the request's
        next uncached block is already being computed by an admitted twin
        (a GRPO group's followers wait for the leader's commits instead of
        paying G duplicate prefills)."""
        idx = self.prefix_index
        if idx is None or req.blocks or req.num_computed:
            return False         # cache off, or a replay already seeded/ran
        tokens = req.seq
        keys = idx.chain_keys(tokens, req.adapter_id)
        if not keys:
            return False
        cached = idx.peek(keys)
        if cached < len(keys) and keys[cached] in self._inflight_keys:
            self.prefix_deferrals += 1
            return True
        if cached == 0:
            return False
        # The drilled lookup site: an armed ``kv_prefix_lookup`` degrades
        # to a cold prefill — byte-identical output, just no reuse.
        try:
            fault_point("kv_prefix_lookup")
        except InjectedFault:
            idx.lookups += 1
            idx.misses += 1
            return False
        chain = idx.acquire(keys)
        matched = len(chain) * self.block_size
        if matched > len(tokens) - 1:
            # the chain covers the WHOLE sequence: the last block must be
            # writable (the next decode token lands in it, or its final
            # slot is the sampled-next position) — fork it copy-on-write.
            # The drilled fork site: an armed ``kv_cow_fork`` (or genuine
            # exhaustion) drops the chain and falls back to a cold
            # prefill — the shared source block is never touched.
            src = chain[-1]
            try:
                fault_point("kv_cow_fork")
                dst = self.allocator.allocate(1)[0]
            except (OutOfBlocks, InjectedFault):
                self.allocator.free(chain)
                self.cow_fork_failures += 1
                return False
            req.cow_src = src          # ref held until the copy rode a step
            req.cow_dst = dst
            chain = chain[:-1] + [dst]
            matched -= 1               # dst's last slot is still cold
            self.cow_forks += 1
            req.committed_blocks = len(chain) - 1
            req.chain_key = keys[len(chain) - 2] if len(chain) >= 2 else None
        else:
            req.committed_blocks = len(chain)
            req.chain_key = keys[len(chain) - 1]
        req.blocks = list(chain)
        req.num_computed = matched
        return False

    def _unseed(self, req: Request) -> None:
        """Back out a prefix seed when admission bounced AFTER seeding:
        refs return to the allocator and the request is cold again."""
        self._drop_chain_state(req)
        self._free_tables(req)
        req.num_computed = 0

    def _register_inflight(self, req: Request) -> None:
        """Claim the uncached chain keys this admitted request will commit
        as its prefill progresses — concurrent identical prompts defer on
        these instead of duplicating the work."""
        idx = self.prefix_index
        if idx is None:
            return
        keys = idx.chain_keys(req.seq, req.adapter_id)
        req.inflight_keys = [k for k in keys[req.committed_blocks:]
                             if not idx.has_key(k)]
        for k in req.inflight_keys:
            self._inflight_keys[k] = self._inflight_keys.get(k, 0) + 1

    def _unregister_inflight(self, req: Request) -> None:
        for k in req.inflight_keys:
            n = self._inflight_keys.get(k, 0) - 1
            if n <= 0:
                self._inflight_keys.pop(k, None)
            else:
                self._inflight_keys[k] = n
        req.inflight_keys = []

    def _commit_full(self, req: Request) -> None:
        """Index every newly-FULL block of ``req`` (prompt AND decode
        output — multi-turn reuse and preemption replay both hit them).
        First writer wins on key collisions; committed keys leave the
        in-flight claim so deferred twins admit next tick."""
        idx = self.prefix_index
        if idx is None:
            return
        bs = self.block_size
        seq = req.seq
        # a position whose token is still in flight cannot be hashed yet
        full = min(min(req.num_computed, len(seq)) // bs, len(req.blocks))
        while req.committed_blocks < full:
            i = req.committed_blocks
            # block 0 commits under the request's TENANT root, not the
            # bare None parent — otherwise a cold non-base request would
            # index its first block where base traffic can hit it (the
            # cross-tenant KV leak chain_keys() namespacing guards against)
            parent = (req.chain_key if req.committed_blocks
                      else idx.root_key(req.adapter_id))
            key = idx.commit(parent, seq[i * bs:(i + 1) * bs],
                             req.blocks[i])
            req.chain_key = key
            req.committed_blocks += 1
            if req.inflight_keys and req.inflight_keys[0] == key:
                req.inflight_keys.pop(0)
                n = self._inflight_keys.get(key, 0) - 1
                if n <= 0:
                    self._inflight_keys.pop(key, None)
                else:
                    self._inflight_keys[key] = n

    def _admit(self, now: float) -> None:
        for req in sorted(self.waiting,
                          key=lambda r: self._policy_key(r, now)):
            free_slots = [i for i, r in enumerate(self.slots) if r is None]
            if not free_slots:
                return
            if (self.tenant_quota is not None
                    and self._tenant_active(req.adapter_id)
                    >= self.tenant_quota):
                # per-tenant admission quota: this tenant already holds its
                # share of slots — the request WAITS (no rejection, no
                # expiry) and other tenants' rows admit past it
                self.tenant_quota_deferrals += 1
                continue
            if self._try_prefix_seed(req):
                continue         # deferred: an admitted twin is prefilling
            min_prefill = self._min_prefill_s(req)
            if (min_prefill is not None
                    and req.remaining_budget(now) < min_prefill):
                # a guaranteed deadline miss never occupies a slot: expire
                # at the admission boundary instead of wasting pool space
                # (a seeded chain is released through the expire path)
                self.expire(req, reason="budget")
                continue
            first_chunk = min(len(req.pending), self.prefill_chunk)
            if self.block_size is not None and any(
                    g.allocator.free_blocks * self.block_size < first_chunk
                    for g in self.groups):
                self._unseed(req)
                continue         # in-flight admission waits for frees
            self.waiting.remove(req)
            req.slot = free_slots[0]
            self.slots[req.slot] = req
            req.state = RequestState.PREFILL
            if not req.was_admitted:
                req.admit_time, req.admit_tick = now, self._ticks
                self._event("serve_admit", rid=req.rid,
                            queue_us=_us(now - req.submit_time))
            req.was_admitted = True
            self.admissions += 1
            self._tenant(req)["admitted"] += 1
            self._register_inflight(req)
            self.prefix_tokens_reused += req.num_computed

    # -- speculative decoding ----------------------------------------------
    def _propose_draft(self, req: Request, k_max: int) -> List[int]:
        """Host-side draft proposal for one sampling DECODE row: at most
        ``min(spec_k, k_max, tokens-the-request-can-still-emit - 1)``
        tokens from the proposer (the ``- 1`` reserves the bonus token, and
        also bounds every draft's write position below ``prompt +
        max_new_tokens <= max_model_len``).  Stateless: recompute replay,
        watchdog rebuild and fleet adoption re-draft from ``req.seq``
        alone, so there is no draft state to flush or migrate."""
        k_cap = min(self.spec_k, k_max,
                    req.max_new_tokens - len(req.out_tokens) - 1)
        if k_cap <= 0:
            return []
        # The drilled proposer-failure site: an armed ``spec_draft``
        # degrades THIS row to plain decode for the step (empty draft,
        # same verify width) — byte-identical output, just no speedup.
        try:
            fault_point("spec_draft")
        except InjectedFault:
            self.spec_draft_faults += 1
            return []
        return [int(t) for t in self.spec_proposer(req.seq, k_cap)][:k_cap]

    # -- the per-step contract --------------------------------------------
    def schedule(self, now: Optional[float] = None) -> Optional[StepPlan]:
        """Expire what ran out of time, admit what fits, grow block tables
        (preempting under pressure), and emit this step's
        :class:`StepPlan` — or None when idle."""
        if now is None:
            now = self.clock()
        self._ticks += 1
        self._expire_due(now)
        self._admit(now)
        if not self.active:
            return None
        # Pure-decode steps run at the SPEC width whenever speculation is
        # on (spec_k+1; 1 when off) — even for rows whose proposer came
        # back empty — so acceptance/rejection/draft-length churn is data
        # inside one compiled program, never a new shape.
        any_prefill = any(len(r.pending) > 1 for r in self.active)
        width = self.prefill_chunk if any_prefill else self._spec_width
        speculate = self.spec_proposer is not None and not any_prefill
        rows: List[Optional[RowWork]] = [None] * self.max_num_seqs
        awaited = False
        for req in list(self.active):
            if req.slot is None:
                continue       # preempted by an earlier row's allocation
            fed = req.in_flight > 0
            if fed and (len(req.out_tokens) + req.in_flight
                        >= req.max_new_tokens):
                awaited = True     # its last sample is in flight: no row
                continue
            # a fed row's pending token is the sample in flight: one
            # position, at num_computed, and it samples the one after
            tokens = [FED_TOKEN] if fed else req.pending[:width]
            t = len(tokens)
            samples_next = fed or req.num_computed + t == req.seq_len
            draft = (self._propose_draft(req, width - t)
                     if speculate and samples_next else [])
            if not self._ensure_blocks(req, req.num_computed + t
                                       + len(draft)):
                continue                       # preempted back to WAITING
            rows[req.slot] = RowWork(
                req=req, tokens=tokens, start_pos=req.num_computed,
                samples_next=samples_next, draft=draft,
                cow=((req.cow_src, req.cow_dst)
                     if req.cow_dst is not None else None),
                fed=fed, preemptions=req.preemptions)
        for i, w in enumerate(rows):
            if w is not None and w.req.slot != i:
                # a LATER row's allocation preempted this already-planned
                # victim (slot order can diverge from arrival order after a
                # finish + re-admission): its blocks are freed and its
                # num_computed reset, so the stale RowWork must not run
                rows[i] = None
        if not any(r is not None for r in rows):
            if awaited:
                return None    # nothing to run until the delivery retires them
            return self.schedule(now) if self.has_work() else None
        return StepPlan(rows=rows, step_width=width, tick=self._ticks)

    def _stale(self, work: RowWork) -> bool:
        """The row's request reached a terminal state, lost its slot or was
        preempted (and perhaps re-admitted) since the plan was made: its
        blocks were reclaimed and its replay state must not be moved by
        what that plan computed."""
        req = work.req
        return (req.finished or req.slot is None
                or req.preemptions != work.preemptions)

    def advance(self, plan: StepPlan) -> None:
        """The half of a step that needs no token, run when the plan is
        DISPATCHED: ``num_computed`` moves past the tokens written, a COW
        source whose copy rides this step is released (the device runs its
        programs in order, so whoever is given the block next writes it
        after the copy), and a sampling row counts one sample in flight."""
        for work in plan.active:
            req = work.req
            if self._stale(work):
                continue
            req.num_computed += len(work.tokens)
            if work.cow is not None and req.cow_src is not None:
                # the COW copy rides this step: the private dst will hold
                # the shared slots, so the source ref can be released
                self.allocator.free([req.cow_src])
                req.cow_src = None
                req.cow_dst = None
            if work.samples_next:
                req.in_flight += 1

    def deliver(self, plan: StepPlan,
                sampled: Dict[int, Sequence[int]]) -> List[Request]:
        """The half of a step that needs its tokens, run after the FETCH:
        append the sampled tokens where the pending list emptied, retire
        finished requests (freeing their blocks).  ``sampled`` maps slot ->
        the row's greedy/sampled chain: entry 0 is the token after the last
        pending token (plain decode's one sample); entries ``1..d`` are
        the argmax AT the row's ``d`` draft positions — the verify read.
        The longest draft prefix matching the chain is accepted, plus the
        bonus token after it; ``num_computed`` advances past accepted
        drafts ONLY (their KV is valid), never the bonus token and never
        a rejected position — rejected slots are dead KV past the
        high-water mark, overwritten by whatever comes next.  Stale rows
        (:meth:`_stale`: an abort or expiry issued since ``schedule()``, an
        EOS the look-ahead learnt one step late, a preemption) are dropped
        and counted in ``discarded_rows``."""
        done: List[Request] = []
        # The drilled verify-failure site: an armed ``spec_verify`` models
        # the whole verify step's draft results being unusable — EVERY
        # draft this step is discarded with no partial acceptance (m=0),
        # each sampling row keeps only its plain-decode token (chain[0],
        # valid regardless of drafts), and KV state is clean because
        # nothing past ``num_computed`` is ever committed or shared.
        verify_failed = False
        if any(w.draft for w in plan.active):
            try:
                fault_point("spec_verify")
            except InjectedFault:
                verify_failed = True
                self.spec_verify_failures += 1
        sampling_rows = 0
        appended_total = 0
        for work in plan.active:
            req = work.req
            if self._stale(work):
                self.discarded_rows += 1
                continue
            # Commit BEFORE acceptance and before the append: what is
            # indexed covers no draft token and no token still in flight,
            # so an unaccepted draft can never reach the prefix index even
            # transiently (accepted ones commit next step, once they are
            # provably part of the sequence).
            self._commit_full(req)
            if not work.samples_next:
                continue
            req.in_flight -= 1
            raw = sampled[req.slot]
            # a bare int is the no-draft chain of one (plain decode
            # callers — and the pre-speculation contract — pass scalars)
            chain = ([int(t) for t in raw]
                     if isinstance(raw, (list, tuple)) else [int(raw)])
            m = 0
            if work.draft:
                self.spec_tokens_proposed += len(work.draft)
                if not verify_failed:
                    while (m < len(work.draft)
                           and work.draft[m] == chain[m]):
                        m += 1
                self.spec_tokens_accepted += m
            appended = 0
            finish_reason = None
            for tok in chain[:m + 1]:
                req.out_tokens.append(tok)
                appended += 1
                if (req.eos_token_id is not None
                        and tok == req.eos_token_id):
                    finish_reason = "eos"
                    break
                if len(req.out_tokens) >= req.max_new_tokens:
                    finish_reason = "length"
                    break
            # accepted drafts already sit in the KV cache; the bonus token
            # (position m in the chain) does not — it is next step's
            # pending token, exactly like plain decode's sampled token
            req.num_computed += min(appended, m)
            sampling_rows += 1
            appended_total += appended
            self.tokens_appended += appended
            self._tenant(req)["tokens"] += appended
            if req.first_token_time is None:
                req.first_token_time = t = self.clock()
                admit = req.admit_time if req.admit_time is not None else t
                self._event(
                    "serve_first_token", rid=req.rid,
                    queue_us=_us(admit - req.submit_time),
                    prefill_us=_us(t - admit),
                    prefill_steps=plan.tick - req.admit_tick + 1,
                    prompt_len=len(req.prompt))
            if finish_reason is not None:
                self.slots[req.slot] = None
                req.slot = None
                self._drop_chain_state(req)
                self._free_tables(req)
                self._terminal(req, RequestState.FINISHED, finish_reason)
                self._tenant(req)["finished"] += 1
                done.append(req)
            else:
                req.state = RequestState.DECODE
        if sampling_rows:
            # the admission guard's tokens-per-row EWMA (see __init__):
            # spec-off the mean is exactly 1.0 every update, so the EWMA
            # is the constant 1.0 and the guard is bit-unchanged
            mean = appended_total / sampling_rows
            self._tokens_per_row_ewma = (
                0.5 * self._tokens_per_row_ewma + 0.5 * mean)
        return done

    def finish_step(self, plan: StepPlan,
                    sampled: Dict[int, Sequence[int]]) -> List[Request]:
        """Apply one executed plan at once: :meth:`advance`, then
        :meth:`deliver` (the order of an engine that fetches a step before
        it plans the next)."""
        self.advance(plan)
        return self.deliver(plan, sampled)
