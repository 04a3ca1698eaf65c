"""Elastic serving: a replicated decode fleet that survives slice loss.

One :class:`~automodel_tpu.serving.engine.DecodeEngine` serves one slice.
Production traffic needs N of them — and needs "which engine owns this
request" to be first-class routed state, because slices die: after PRs
9/11 *training* survives slice loss and grow-back, while a single-engine
serving deployment still loses every in-flight request with its slice.
:class:`FleetRouter` closes that gap host-side, composing three pieces
the repo already has:

* **Routing + fleet-level admission** — requests are built by the router
  (it owns the rid space; engines adopt them through
  ``DecodeEngine.submit_request``) and routed by ``serving.router_policy``:
  ``round_robin`` cycles the live replicas, ``least_loaded`` picks the
  replica with the fewest resident requests, ``by_deadline`` sends
  deadline-carrying traffic to the least-loaded replica while best-effort
  traffic round-robins.  Every replica shares ONE injectable clock, so
  deadlines/TTLs stay comparable wherever a request lands and each
  engine's step-boundary sweep is fleet-wide by construction.  When every
  live replica's waiting queue is bounded-full (``serving.max_waiting``),
  the router sheds at the FLEET level: a typed
  :class:`~automodel_tpu.serving.scheduler.RequestRejected` (reason
  ``fleet_full``), never an exception — the PR-14 contract, one level up.
* **Replica loss -> cross-replica replay** — :meth:`FleetRouter.poll_health`
  renders the loss verdict: the ``fleet_replica_loss`` fault point drills
  it single-process, and an attached :class:`ElasticCoordinator` maps a
  real ``SliceLostError`` to the replica serving that slice (the SAME
  classification rules as training — the coordinator only converts
  heartbeat-deadline expiry into a loss, so a transient RPC error
  propagates instead of killing a healthy replica).  The dead replica's
  requests are harvested (``DecodeEngine.harvest_for_replay`` — every
  block table released, so a dead replica's allocator still ends
  ``all_free``) and transplanted: ADMITTED rows park on a survivor via
  ``Scheduler.adopt_replay`` — pinned, ``num_computed`` reset, generated
  tokens kept, original ``submit_time`` kept — and the recompute replay
  re-prefills prompt + tokens-so-far, so greedy output through a replica
  loss is token-identical to an uninterrupted ``generate()`` (the PR-14
  watchdog guarantee, now across engines).  Never-admitted rows re-route
  like fresh traffic, subject to the fleet shed.
* **Grow-back** — a returning replica (``note_return``; on a live pool
  the coordinator's probation feeds this) must pass
  ``serving.fleet_probation_polls`` consecutive :meth:`poll_health` calls
  before admission.  Admission (drilled by ``fleet_replica_admit``) warms
  a FRESH engine from a live peer: the survivor's current decode params
  are pushed through the PR-11 replica transport pointed at live params
  (``checkpoint/replication.push_live_params`` — same serialize/catalog/
  sha256 protocol as checkpoint replication), fetched digest-verified,
  and handed to the new engine through ``engine.update_params()``.
  Survivor traffic never pauses; an admission failure is a typed
  :class:`~automodel_tpu.utils.elastic.ReplicaAdmitError` recorded in
  ``events`` and the fleet keeps serving shrunk.  A lost replica's
  live-params advertisement is retracted on the loss
  (``drop_live_params``), so a stale catalog can never warm a newcomer
  from a dead replica.

Pure host logic around the engines (no jax in the routing path — the one
``device_get`` in admission is the warm-up serialization).  Drills:
``fleet_route`` / ``fleet_replica_loss`` / ``fleet_replica_admit``
(``utils/fault_injection.py``), tier-1 in
``tests/unit_tests/test_fleet.py``; ops surface in ``tools/serve.py
--replicas/--drill-loss-at`` and the bench ``elastic_serve`` leg.
"""

from __future__ import annotations

import itertools
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from automodel_tpu.generation.generate import GenerationConfig
from automodel_tpu.serving.engine import DecodeEngine, ServingConfig
from automodel_tpu.serving.scheduler import (
    Request,
    RequestRejected,
    RequestState,
)
from automodel_tpu.utils.elastic import (
    ReplicaAdmitError,
    ReplicaLostError,
    ReplicaReturnedError,
    SliceLostError,
)
from automodel_tpu.utils.fault_injection import InjectedFault, fault_point

logger = logging.getLogger(__name__)

# ``serving.router_policy`` config domain (enum-validated at config load
# like scheduler_policy/shed_policy — see loader._enum_fields).
ROUTER_POLICIES = ("round_robin", "least_loaded", "by_deadline")
DEFAULT_ROUTER_POLICY = "round_robin"

# A returning replica must survive this many consecutive poll_health()
# calls before admission (``serving.fleet_probation_polls``) — the serving
# analogue of elastic.readmit_probation_polls, and the same flap rule: a
# poll where the replica is not announcing resets the streak to zero.
DEFAULT_FLEET_PROBATION_POLLS = 3

# Env override for which replica a raise-mode ``fleet_replica_loss`` drill
# loses (default: the highest-id live replica, mirroring LOST_SLICE_ENV).
LOST_REPLICA_ENV = "AUTOMODEL_LOST_REPLICA"


def normalize_router_policy(v):
    from automodel_tpu.config.loader import normalize_null_spelling

    return normalize_null_spelling(v)


def validate_router_policy(v: Optional[str]) -> Optional[str]:
    if v is None:
        return None
    if v not in ROUTER_POLICIES:
        raise ValueError(
            f"serving.router_policy must be one of {list(ROUTER_POLICIES)} "
            f"(or null for the default), got {v!r}")
    return v


class Replica:
    """One fleet member: an engine plus its liveness + routing telemetry."""

    def __init__(self, replica_id: int, engine: DecodeEngine):
        self.replica_id = replica_id
        self.engine = engine
        self.alive = True
        self.losses = 0          # times this id was lost
        self.admissions = 0      # times this id was re-admitted
        self.routed = 0          # fresh requests routed here

    @property
    def load(self) -> int:
        """Resident requests (waiting + active) — the least_loaded key."""
        s = self.engine.scheduler
        return len(s.waiting) + len(s.active)


class FleetRouter:
    """Host-side router over per-slice :class:`DecodeEngine` replicas.

    All replicas share one model/params (so cross-replica greedy replay is
    token-identical) and ONE clock (so deadlines are comparable across
    schedulers).  The router owns the rid space: requests are built here
    and adopted by engines, which is what lets a request move between
    engines after a loss without colliding with another engine's ids.
    """

    def __init__(self, model, params,
                 config: Optional[ServingConfig] = None,
                 generation: Optional[GenerationConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 timers=None, coordinator=None, param_sharding=None,
                 sample_seed: int = 0):
        self.config = config or ServingConfig()
        self.generation = generation or GenerationConfig()
        self.clock = clock
        self.timers = timers
        # Optional ElasticCoordinator: maps real slice-health verdicts to
        # replicas.  Duck-typed (poll/ready_to_readmit/admit) so tests can
        # drive classification without a multi-host mesh.
        self.coordinator = coordinator
        self.policy = (self.config.router_policy or DEFAULT_ROUTER_POLICY)
        self.probation_polls = (self.config.fleet_probation_polls
                                or DEFAULT_FLEET_PROBATION_POLLS)
        # fresh-engine spec for grow-back admissions: the healed slice
        # relaunches with whatever (stale) params it had — update_params
        # with the live peer tree is what makes it current
        self._model = model
        self._init_params = params
        self._param_sharding = param_sharding
        self._sample_seed = sample_seed
        n = self.config.replicas or 1
        self.replicas = [
            Replica(i, DecodeEngine(
                model, params, self.config, generation=self.generation,
                clock=clock, timers=timers, param_sharding=param_sharding,
                sample_seed=sample_seed))
            for i in range(n)]
        self.requests: Dict[int, Request] = {}
        self.rejections: List[RequestRejected] = []
        self.events: List[Any] = []    # typed loss/readmit/admit-fail events
        self._rids = itertools.count()
        self._rr = itertools.count()   # round-robin cursor
        self._probation: Dict[int, int] = {}
        self._returning: set = set()
        self.health_polls = 0
        self.replica_losses = 0
        self.replays = 0
        self.readmissions = 0
        self.fleet_rejected = 0

    # -- topology ----------------------------------------------------------
    @property
    def alive_replicas(self) -> List[Replica]:
        return [r for r in self.replicas if r.alive]

    def _replica_for_slice(self, slice_id: int) -> Optional[Replica]:
        """Replica serving ``slice_id`` — replica i IS slice i's engine."""
        if 0 <= int(slice_id) < len(self.replicas):
            return self.replicas[int(slice_id)]
        return None

    def _drilled_lost_replica(self) -> Optional[Replica]:
        env = os.environ.get(LOST_REPLICA_ENV)
        if env is not None:
            rep = self.replicas[int(env)]
            return rep if rep.alive else None
        alive = self.alive_replicas
        return alive[-1] if alive else None

    # -- intake + routing --------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = "default",
               deadline_s: Optional[float] = None,
               max_queue_s: Optional[float] = None,
               adapter_id: int = 0) -> int:
        """Build one request and route it; returns its fleet-wide rid.
        Same intake contract as ``DecodeEngine.submit`` — a load drop is a
        typed rejection in ``self.rejections``, never an exception.
        ``adapter_id`` rides the request across any replica move (replay
        re-prefills under the SAME adapter slot — every replica serves the
        same slot registry, see :meth:`load_adapter`)."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("cannot serve an empty prompt")
        if eos_token_id == "default":
            eos_token_id = self.generation.eos_token_id
        if adapter_id != 0:
            alive = self.alive_replicas
            if not alive or alive[0].engine.adapter_slots is None \
                    or not alive[0].engine.adapter_slots.is_loaded(adapter_id):
                raise ValueError(
                    f"adapter_id={adapter_id} is not loaded on the fleet — "
                    "load it first (FleetRouter.load_adapter)")
        rid = next(self._rids)
        req = Request(
            rid=rid, prompt=prompt,
            max_new_tokens=(self.generation.max_new_tokens
                            if max_new_tokens is None else max_new_tokens),
            eos_token_id=eos_token_id,
            deadline_s=deadline_s, max_queue_s=max_queue_s,
            adapter_id=int(adapter_id))
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.requests[rid] = req
        self._route(req)
        return rid

    # -- multi-tenant adapters ---------------------------------------------
    def load_adapter(self, slot: int, adapters, *, name=None,
                     scale: float = 1.0) -> Dict[int, Any]:
        """Hot-swap ``slot`` on EVERY live replica (dead replicas pick the
        registry up at admission by cloning a live peer's slots).  All-or-
        nothing is per replica: a replica that fails verification keeps
        its old adapter and the error propagates after no slab on it was
        touched."""
        out = {}
        for r in self.alive_replicas:
            out[r.replica_id] = r.engine.load_adapter(
                slot, adapters, name=name, scale=scale)
        return out

    def remove_adapter(self, slot: int) -> None:
        for r in self.alive_replicas:
            r.engine.remove_adapter(slot)

    def _queue_room(self, replica: Replica) -> bool:
        """Mirror of ``Scheduler.add``'s shed trigger: a replica whose
        waiting list has reached ``max_waiting`` is bounded-full."""
        mw = self.config.max_waiting
        if mw is None:
            return True
        return len(replica.engine.scheduler.waiting) < mw

    def _pick(self, open_: List[Replica], req: Request) -> Replica:
        if self.policy == "least_loaded" or (
                self.policy == "by_deadline" and req.deadline_s is not None):
            return min(open_, key=lambda r: (r.load, r.replica_id))
        # round_robin — and by_deadline's best-effort (no-deadline) traffic
        ranked = sorted(open_, key=lambda r: r.replica_id)
        return ranked[next(self._rr) % len(ranked)]

    def _route(self, req: Request, preserve_submit_time: bool = False) -> None:
        """Route one WAITING request to a live replica with queue room —
        or shed at the fleet level, typed.  ``preserve_submit_time`` keeps
        the original submission stamp when re-routing a dead replica's
        never-admitted rows (their deadline/TTL clocks must not restart)."""
        # The drilled routing failure: a router that cannot render a
        # placement decision (lookup/transport failure) must produce a
        # typed rejection the client can retry on — never a crash.
        try:
            fault_point("fleet_route")
        except InjectedFault:
            self._reject_fleet(req, "route(injected)")
            return
        alive = self.alive_replicas
        if not alive:
            self._reject_fleet(req, "no_replicas")
            return
        open_ = [r for r in alive if self._queue_room(r)]
        if not open_:
            # EVERY live replica is bounded-full: the fleet-level shed
            self._reject_fleet(req, "fleet_full")
            return
        target = self._pick(open_, req)
        orig_submit = req.submit_time
        rejected = target.engine.submit_request(req)
        if preserve_submit_time:
            req.submit_time = orig_submit
        target.routed += 1
        self.rejections.extend(rejected)

    def _reject_fleet(self, req: Request, reason: str) -> None:
        req.state = RequestState.REJECTED
        req.finish_reason = reason
        req.finish_time = self.clock()
        self.fleet_rejected += 1
        self.rejections.append(
            RequestRejected(rid=req.rid, reason=reason, policy=self.policy))

    def abort(self, rid: int) -> None:
        req = self.requests.get(rid)
        if req is None or req.finished:
            return
        for rep in self.replicas:
            if rid in rep.engine.requests:
                rep.engine.abort(rid)
                return

    # -- the fleet loop ----------------------------------------------------
    def step(self) -> List[Request]:
        """One step on every live replica; returns the requests that
        finished fleet-wide.  Dead replicas are skipped — their work was
        already transplanted at the loss."""
        done: List[Request] = []
        for rep in self.replicas:
            if rep.alive:
                done.extend(rep.engine.step())
        return done

    def has_work(self) -> bool:
        return any(r.alive and r.engine.scheduler.has_work()
                   for r in self.replicas)

    def run(self, max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Drive until every routed request reaches a terminal state;
        returns rid -> generated tokens (same stall bound as
        ``DecodeEngine.run``)."""
        from automodel_tpu.serving.kv_cache import blocks_needed

        if max_steps is None:
            budget = sum(
                blocks_needed(len(r.prompt), self.config.prefill_chunk)
                + r.max_new_tokens + 1
                for r in self.requests.values() if not r.finished)
            max_steps = 64 + 8 * budget
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"fleet made no progress within {max_steps} steps — "
                    "scheduler stall (file a bug with the request trace)")
        return {rid: list(r.out_tokens) for rid, r in self.requests.items()}

    def drain(self, grace_s=None) -> Dict[str, int]:
        """Graceful fleet drain: every live replica drains (admitted work
        finishes within the grace window, fresh queue traffic rejects),
        then every replica's live-params advertisement is retracted — a
        torn-down fleet must leave no catalog behind."""
        for rep in self.replicas:
            if rep.alive:
                if grace_s is None:
                    rep.engine.drain()
                else:
                    rep.engine.drain(grace_s)
        self.teardown()
        return self.outcome_counts()

    def teardown(self) -> None:
        """Retract every replica's live-params advertisement (fleet
        shutdown / test cleanup) — an advertisement must never outlive the
        fleet that would answer it."""
        from automodel_tpu.checkpoint.replication import drop_live_params

        for rep in self.replicas:
            drop_live_params(rep.replica_id)

    # -- health: loss + grow-back ------------------------------------------
    def poll_health(self, step: int = -1) -> Optional[Any]:
        """One fleet health sweep; returns the typed event it handled (a
        :class:`ReplicaLostError` / :class:`ReplicaReturnedError` /
        :class:`ReplicaAdmitError`, also appended to ``events``) or None.

        Losses are ABSORBED — the fleet routes around them — so unlike the
        training coordinator this never raises a loss verdict.  What DOES
        propagate is a non-timeout coordination failure out of an attached
        coordinator's poll: the same classification rule as training, so a
        transient RPC error can never shrink away a healthy replica."""
        self.health_polls += 1
        event: Optional[Any] = None
        # The drilled replica-loss verdict (single-process fleets): the
        # serving analogue of ``slice_loss``.
        try:
            fault_point("fleet_replica_loss")
        except InjectedFault as e:
            victim = self._drilled_lost_replica()
            if victim is not None:
                event = self._lose_replica(
                    victim, f"injected replica loss ({e})", step)
        if self.coordinator is not None:
            try:
                self.coordinator.poll(step)
            except SliceLostError as e:
                rep = self._replica_for_slice(e.slice_id)
                if rep is not None and rep.alive:
                    event = self._lose_replica(rep, str(e), step)
            # anything else out of poll() propagates: only the
            # coordinator's own timeout classification may kill a replica
            sid = self.coordinator.ready_to_readmit()
            if sid is not None:
                rep = self._replica_for_slice(sid)
                if rep is not None and not rep.alive:
                    # the coordinator's probation already served: admit now
                    self.coordinator.admit(sid, step)
                    event = self._admit_replica(rep.replica_id,
                                                step) or event
        # fleet-local probation (the coordinator-less drill path)
        for rid in [r.replica_id for r in self.replicas if not r.alive]:
            if rid in self._returning:
                self._probation[rid] = self._probation.get(rid, 0) + 1
            else:
                self._probation.pop(rid, None)   # flap: streak restarts
        for rid in sorted(self._probation):
            if self._probation[rid] >= self.probation_polls:
                event = self._admit_replica(rid, step) or event
        return event

    def note_return(self, replica_id: int) -> None:
        """Mark a dead replica as announcing again — each subsequent
        :meth:`poll_health` advances its probation streak (the serving
        analogue of ``ElasticCoordinator.announce_return``; real pools
        drive this from the coordinator's return beats)."""
        rep = self.replicas[int(replica_id)]
        if not rep.alive:
            self._returning.add(rep.replica_id)

    def note_flap(self, replica_id: int) -> None:
        """The returning replica vanished again: probation restarts from
        zero at the next poll (flapping never shortens probation)."""
        self._returning.discard(int(replica_id))
        self._probation.pop(int(replica_id), None)

    def _lose_replica(self, replica: Replica, reason: str,
                      step: int) -> ReplicaLostError:
        """Handle one replica loss: retract its live-params advertisement,
        harvest its requests (allocator drains to ``all_free``), replay
        admitted rows on survivors, re-route fresh rows."""
        from automodel_tpu.checkpoint.replication import drop_live_params

        replica.alive = False
        replica.losses += 1
        self.replica_losses += 1
        self._probation.pop(replica.replica_id, None)
        self._returning.discard(replica.replica_id)
        # a dead replica's params must never warm a future admission
        drop_live_params(replica.replica_id)
        harvested = replica.engine.harvest_for_replay()
        event = ReplicaLostError(replica.replica_id, reason, step)
        self.events.append(event)
        admitted = [r for r in harvested if r.was_admitted]
        fresh = [r for r in harvested if not r.was_admitted]
        logger.warning(
            "fleet: replica %d lost (%s) — replaying %d admitted "
            "request(s) on survivors, re-routing %d queued",
            replica.replica_id, reason, len(admitted), len(fresh))
        survivors = self.alive_replicas
        for req in admitted:
            if not survivors:
                # no engine can ever finish this work: terminal, typed
                req.state = RequestState.EXPIRED
                req.finish_reason = "replica_lost"
                req.finish_time = self.clock()
                continue
            target = min(survivors, key=lambda r: (r.load, r.replica_id))
            target.engine.adopt_for_replay(req)
            self.replays += 1
        for req in fresh:
            self._route(req, preserve_submit_time=True)
        return event

    def _admit_replica(self, replica_id: int,
                       step: int) -> Optional[ReplicaReturnedError]:
        """Admit a healed replica: warm a fresh engine from a live peer's
        decode params (digest-verified through the replica transport) and
        open it to traffic.  Any failure — including the drilled
        ``fleet_replica_admit`` — is a typed :class:`ReplicaAdmitError`:
        probation restarts and the fleet keeps serving shrunk."""
        import jax
        import jax.numpy as jnp

        from automodel_tpu.checkpoint.replication import (
            fetch_live_params,
            push_live_params,
        )

        replica = self.replicas[int(replica_id)]
        try:
            # The drilled admission failure: warm-up transport / relaunch
            # handshake breaking mid-admission.
            fault_point("fleet_replica_admit")
            peer = next((r for r in self.alive_replicas), None)
            if peer is None:
                raise ReplicaAdmitError(
                    replica_id, "no live peer to warm from", step)
            # live-params push: the peer's CURRENT decode params through
            # the checkpoint-replication catalog/digest protocol
            host_tree = jax.device_get(peer.engine.params)  # lint: disable=L004 (once-per-admission warm-up serialization, not a step-loop sync)
            push_live_params(replica_id=peer.replica_id, params=host_tree,
                             version=peer.engine.weight_syncs)
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                peer.engine.params)
            tree = fetch_live_params(abstract=abstract,
                                     replica_id=peer.replica_id,
                                     version=peer.engine.weight_syncs)
            if tree is None:
                raise ReplicaAdmitError(
                    replica_id,
                    f"live-params fetch from replica {peer.replica_id} "
                    "failed digest verification", step)
            # the healed slice relaunches with its STALE params; the
            # handoff through update_params is what makes it current
            engine = DecodeEngine(
                self._model, self._init_params, self.config,
                generation=self.generation, clock=self.clock,
                timers=self.timers, param_sharding=self._param_sharding,
                sample_seed=self._sample_seed)
            engine.update_params(jax.tree.map(jnp.asarray, tree))
            if peer.engine.adapter_slots is not None:
                # the admitted engine must serve the same tenants as its
                # warm source: clone the peer's slot registry + slabs
                engine.adapter_slots.clone_from(peer.engine.adapter_slots)
            # the warm-up timeline's last leg: compile the fresh engine's
            # step widths NOW, while it still has no traffic — admission
            # pays the compiles, not the first unlucky request routed
            # here (survivors keep serving throughout)
            engine.generate(np.asarray([[1]]),
                            config=GenerationConfig(
                                max_new_tokens=1,
                                eos_token_id=self.generation.eos_token_id))
        except (InjectedFault, ReplicaAdmitError) as e:
            self._probation.pop(int(replica_id), None)
            self._returning.discard(int(replica_id))
            ev = (e if isinstance(e, ReplicaAdmitError)
                  else ReplicaAdmitError(
                      replica_id, f"injected admit failure ({e})", step))
            self.events.append(ev)
            logger.warning(
                "fleet: replica %d admission failed (%s) — serving "
                "continues on %d live replica(s)", replica_id, ev,
                len(self.alive_replicas))
            return None
        replica.engine = engine
        replica.alive = True
        replica.admissions += 1
        self.readmissions += 1
        self._probation.pop(int(replica_id), None)
        self._returning.discard(int(replica_id))
        ev = ReplicaReturnedError(
            replica.replica_id,
            f"passed fleet probation ({self.probation_polls} polls); "
            f"warmed from replica {peer.replica_id}'s live params "
            "(digest-verified)", step)
        self.events.append(ev)
        logger.info("fleet: %s", ev)
        return ev

    # -- telemetry ---------------------------------------------------------
    def all_free(self) -> bool:
        """Every replica's allocator — live AND dead — fully drained: the
        fleet-wide leak oracle the drills assert."""
        return all(r.engine.all_free for r in self.replicas)

    def outcome_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for req in self.requests.values():
            counts[req.state.value] = counts.get(req.state.value, 0) + 1
        return counts

    def completed_in_deadline(self) -> int:
        """Fleet-wide goodput numerator (same rule as the engine's)."""
        n = 0
        for req in self.requests.values():
            if req.state is not RequestState.FINISHED:
                continue
            if (req.deadline_s is None or req.finish_time is None
                    or req.finish_time - req.submit_time <= req.deadline_s):
                n += 1
        return n

    def stats(self) -> Dict[str, Any]:
        # fleet-wide per-tenant aggregation: sum each adapter id's
        # counters across replicas (a replayed request counts on every
        # engine that admitted it — the replay cost is real work)
        per_tenant: Dict[int, Dict[str, int]] = {}
        for r in self.replicas:
            for tid, d in r.engine.scheduler.per_tenant.items():
                agg = per_tenant.setdefault(
                    tid, {"submitted": 0, "admitted": 0, "finished": 0,
                          "tokens": 0})
                for k, v in d.items():
                    agg[k] = agg.get(k, 0) + v
        return {
            "replicas": len(self.replicas),
            "per_tenant": {k: per_tenant[k] for k in sorted(per_tenant)},
            "alive": len(self.alive_replicas),
            "router_policy": self.policy,
            "health_polls": self.health_polls,
            "replica_losses": self.replica_losses,
            "replays": self.replays,
            "readmissions": self.readmissions,
            "fleet_rejected": self.fleet_rejected,
            "routed": {r.replica_id: r.routed for r in self.replicas},
            "per_replica": {
                r.replica_id: {
                    "alive": r.alive,
                    "steps": r.engine.steps_run,
                    "ahead_steps": r.engine.ahead_steps,
                    "discarded_rows": r.engine.scheduler.discarded_rows,
                    "tokens_generated": r.engine.tokens_generated,
                    "compiled_widths": sorted(r.engine._steps),
                    "kv_blocks_free": r.engine.allocator.free_blocks,
                    "prefill_tokens_saved":
                        r.engine.scheduler.prefix_tokens_reused,
                    "spec_tokens_accepted":
                        r.engine.scheduler.spec_tokens_accepted,
                } for r in self.replicas},
            "prefill_tokens_saved": sum(
                r.engine.scheduler.prefix_tokens_reused
                for r in self.replicas),
            "spec_tokens_proposed": sum(
                r.engine.scheduler.spec_tokens_proposed
                for r in self.replicas),
            "spec_tokens_accepted": sum(
                r.engine.scheduler.spec_tokens_accepted
                for r in self.replicas),
            "accept_rate": (
                sum(r.engine.scheduler.spec_tokens_accepted
                    for r in self.replicas)
                / max(1, sum(r.engine.scheduler.spec_tokens_proposed
                             for r in self.replicas))),
            "outcomes": self.outcome_counts(),
        }
