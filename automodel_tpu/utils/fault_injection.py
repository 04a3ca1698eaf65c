"""Deterministic fault-injection harness for crash-safety testing.

Named ``fault_point("...")`` call sites mark the places where a preemption
kill or an I/O failure would be most damaging (the checkpoint save path
threads them through ``recipes/base_recipe.py`` and
``checkpoint/checkpointing.py``).  In production every ``fault_point`` is a
dict lookup that misses — effectively free.  Under test, a spec arms a point
to fire on its N-th hit, either raising :class:`InjectedFault` (in-process
tests) or hard-exiting the process (subprocess kill simulation — no cleanup,
no ``atexit``, exactly like a TPU-pool preemption SIGKILL).

Spec grammar (config API or the ``AUTOMODEL_FAULT`` env var)::

    AUTOMODEL_FAULT="ckpt_pre_commit:1"          # raise on 1st hit
    AUTOMODEL_FAULT="ckpt_pre_rename:2:kill"     # os._exit on 2nd hit
    AUTOMODEL_FAULT="a:1,b:3"                    # multiple points

Each entry is ``name[:count][:mode]`` — ``count`` defaults to 1 (fire on the
first hit), ``mode`` is ``raise`` (default) or ``kill``/``exit``.  A point
fires exactly once, on exactly the ``count``-th hit: deterministic by
construction, no randomness anywhere.

Registered checkpoint-path points (see ``BaseRecipe.save_checkpoint``):

    ckpt_pre_save     before the staging directory is prepared
    ckpt_async_snapshot
                      on the TRAINING thread, after joining any previous
                      in-flight save and before the device->host snapshot
                      of an asynchronous save (checkpoint.async_save) —
                      fires as a raised exception in the training loop
    ckpt_async_commit on the background COMMITTER thread, right after
                      staging is prepared and before any state is written —
                      an async-save failure mid-background-write: leaves
                      only the .tmp staging dir, surfaces at the next join
                      point (next save / preemption save / teardown)
    ckpt_collective_save
                      inside the COLLECTIVE phase (before the
                      save_model/save_optimizer writers) — exercises the
                      try/vote wrap that keeps a failing host from
                      stranding peers at the commit barrier
    ckpt_pre_commit   after all state is written, before the manifest
    ckpt_pre_rename   after the manifest, before the atomic rename
    ckpt_post_commit  after the rename, before retention GC

    Under asynchronous saves every point from ckpt_async_commit onward is
    hit on the committer thread; ``fault_point`` is thread-safe and the
    recipe converts the raise into a ``CheckpointSaveError`` at the next
    join point.

Input-pipeline points (see ``datasets/prefetch.py``):

    input_producer    in the background prefetch thread, before each batch
                      is produced — fires as a raised exception in the
                      TRAINING loop within one step (forwarded through the
                      queue; the consumer never hangs on a dead producer)

Kernel-substrate points (see ``ops/kernel_lib/autotune.py``):

    kernel_autotune_cache
                      at the top of the block-size autotune cache READ —
                      a corrupt/unreadable cache file.  The contract under
                      drill: warn once, degrade to the hand-tuned block
                      defaults, NEVER fail recipe setup (the fault is
                      swallowed by the load path's degradation handler,
                      not surfaced).

Elastic multi-slice points (see ``utils/elastic.py``):

    elastic_heartbeat in ``ElasticCoordinator.poll``, before this host
                      publishes its heartbeat — ``:kill`` here is a host
                      dying BETWEEN heartbeats (the canonical preemption),
                      including mid-async-commit when armed to fire while
                      a background checkpoint is still writing: recovery
                      must resume from the PREVIOUS committed step.
    slice_loss        in ``ElasticCoordinator.poll``, at the slice-health
                      verdict — ``raise`` mode is converted by the
                      coordinator into a SliceLostError for the drilled
                      slice (in-process recovery: shrink + rescale +
                      restore); ``:kill`` hard-exits, modelling the hosts
                      of the lost slice vanishing (recovery = relaunch at
                      dcn_dp-1 resuming from the last committed step).
    elastic_readmit   in ``ElasticCoordinator._note_returning`` (each poll
                      while any slice is retired) — ``raise`` mode marks
                      the drilled RETIRED slice's heartbeats as visible
                      again, starting its probation streak (the grow-back
                      drill's trigger; the contract is probation +
                      admission at the next committed-checkpoint
                      boundary); ``:kill`` is this host dying while
                      tracking a re-admission — the pool stays shrunk and
                      the relaunch resumes from the last committed step.

Checkpoint-replication points (see ``checkpoint/replication.py``):

    ckpt_replica_push on the async COMMITTER thread at the top of the
                      peer-replica push, strictly AFTER the commit landed
                      — ``raise`` mode contract: the save STANDS, the
                      push is skipped with a warning, and the next
                      restore takes the storage path; ``:kill`` models a
                      host dying right after its commit (relaunch resumes
                      from that committed step, replica store empty).
    ckpt_replica_restore
                      inside the per-shard fetch/verify loop of a
                      peer-RAM restore — a corrupt/truncated replica
                      shard mid-fetch.  Contract: the restore silently
                      falls back to the storage path with a warning,
                      byte-identical state, ``restore_source=storage``.

Serving-engine points (see ``serving/scheduler.py`` / ``serving/engine.py``):

    serve_block_alloc in ``Scheduler._allocate``, at the top of every KV
                      block grab — an armed fault behaves exactly like a
                      genuinely exhausted pool.  Contract: the requesting
                      row is PREEMPTED back to WAITING with its blocks
                      freed (recompute policy — greedy output stays
                      token-identical), never a crash; younger active
                      requests are victimized first.
    serve_request_abort
                      in ``DecodeEngine.step``, before the plan is built —
                      models a client cancelling mid-decode.  Contract:
                      the oldest active request is aborted, its whole
                      block table returns to the free list immediately,
                      and every other request's output is unaffected.
    serve_deadline    in ``Scheduler._expire_due``, the step-boundary
                      deadline sweep — models the oldest ACTIVE request's
                      deadline firing right now.  Contract: the victim
                      transitions to the terminal EXPIRED state (distinct
                      from ABORTED) with its whole block table reclaimed,
                      and every other request's greedy output is
                      unaffected — never a crash, never a leaked block.
    serve_shed        in ``Scheduler.add`` — models admission control
                      dropping the incoming request exactly like a full
                      waiting queue.  Contract: a typed RequestRejected
                      outcome (state REJECTED, no blocks ever held),
                      NEVER an exception out of the engine loop.
    serve_watchdog_stall
                      in ``DecodeEngine.step``, at the device-step
                      dispatch — stands in for a wedged step (the runtime
                      surfacing a timeout/cancellation after
                      ``serving.watchdog_s`` without slot progress).
                      Contract: the engine aborts the in-flight batch,
                      rebuilds the pools, reclaims every block table, and
                      replays the admitted requests from their last
                      computed token (pinned; greedy output stays
                      token-identical through the recovery).
    kv_prefix_lookup  in ``Scheduler._try_prefix_seed``, before the prefix
                      index is consulted at admission — a corrupt/unusable
                      index lookup.  Contract: the request degrades to a
                      COLD prefill, byte-identical greedy output, no
                      shared block touched, ``all_free`` after terminal
                      states — the cache is an optimization, never a
                      correctness dependency.
    kv_cow_fork       in ``Scheduler._try_prefix_seed``, at the private-
                      block grab of a copy-on-write fork — fork allocation
                      failing on a fully-cached sequence.  Contract: the
                      acquired chain's refs are returned (the shared
                      source block is NEVER corrupted or reclaimed out
                      from under other holders), the request falls back to
                      a cold prefill token-identically, and the failure is
                      counted (``cow_fork_failures``).
    spec_draft        in ``Scheduler._propose_draft``, before the
                      speculative proposer runs — the draft source failing
                      for one row.  Contract: THAT row rides the verify
                      step with an empty draft (plain decode, byte-
                      identical greedy output, just no speedup), every
                      other row's drafts are unaffected, and the failure
                      is counted (``spec_draft_faults``).
    spec_verify       in ``Scheduler.deliver``, before draft
                      acceptance on a step that carried any draft — the
                      verify results being unusable.  Contract: every
                      draft of the step is DISCARDED with no partial
                      acceptance (each sampling row keeps only its plain-
                      decode token, which is valid independent of drafts),
                      KV state stays clean (nothing past ``num_computed``
                      is ever committed or shared, so rejected positions
                      are dead slots), greedy output stays token-
                      identical, and the failure is counted
                      (``spec_verify_failures``).

Serving-fleet points (see ``serving/fleet.py``):

    fleet_route       in ``FleetRouter._route``, before a placement
                      decision is rendered — a router that cannot place
                      the request (replica lookup / transport failure).
                      Contract: a typed RequestRejected outcome (reason
                      ``route(injected)``, state REJECTED, no engine ever
                      saw the request), NEVER an exception out of
                      ``submit`` — clients retry on the typed signal.
    fleet_replica_loss
                      in ``FleetRouter.poll_health`` — a replica's slice
                      declared lost (the serving analogue of
                      ``slice_loss``; AUTOMODEL_LOST_REPLICA picks the
                      victim, default the highest-id live replica).
                      Contract: survivors' traffic is untouched, the dead
                      replica's live-params advertisement is retracted,
                      its admitted requests replay on survivors greedy
                      token-identical from their kept tokens, queued rows
                      re-route (or shed typed at the fleet level), and
                      EVERY allocator — dead replica included — ends
                      ``all_free``.
    fleet_replica_admit
                      in ``FleetRouter._admit_replica``, at the top of a
                      grow-back admission — the warm-up transport or
                      relaunch handshake breaking mid-admission.
                      Contract: a typed ReplicaAdmitError in the fleet's
                      ``events`` log, the replica stays dead with its
                      probation restarted, and the shrunk fleet keeps
                      serving — never a crash, never a half-admitted
                      replica receiving traffic.

Multi-tenant adapter points (see ``serving/adapters.py``):

    adapter_load      in ``AdapterSlots.load``, at the top of a load into
                      an EMPTY slot — the adapter transport/verification
                      failing.  Contract: a typed AdapterLoadError, no
                      slab byte written (the slot keeps serving the zero
                      adapter, i.e. rejects at submit), every other
                      slot's traffic is unaffected, and the failure is
                      counted (``load_failures``).
    adapter_swap      in ``AdapterSlots.load``, at the top of a hot-swap
                      of an OCCUPIED slot — the swap breaking mid-batch.
                      Contract: a typed AdapterLoadError, the slot keeps
                      serving its OLD adapter, and in-flight requests —
                      on this slot and every other — finish token-
                      identically (the commit is atomic: all new slab
                      arrays are built before any reference flips).

Post-training rollout points (see ``post_training/rollout.py``):

    rollout_weight_sync
                      in ``RolloutWorker.sync_weights``, at the top of
                      the live-params handoff into the decode engine —
                      a failed device-to-device transfer.  Contract: the
                      engine keeps its PREVIOUS weights, nothing was
                      submitted, a typed RolloutError surfaces, training
                      state is untouched and the next rollout re-syncs
                      cleanly.
    rollout_engine_step
                      in the rollout drive loop, before each engine step
                      — a device-step failure / runtime cancellation
                      mid-generation.  Contract: every in-flight request
                      of the rollout is ABORTED through the serving abort
                      path (block tables reclaimed immediately —
                      ``allocator.all_free`` afterwards), the typed
                      RolloutError surfaces, training state is untouched,
                      and the next rollout starts clean.
    reward_fn         in ``post_training/rollout.compute_rewards`` — an
                      external reward service failing.  Contract: the
                      completed rollout is DISCARDED typed (its blocks
                      were already freed at finish); training state is
                      untouched.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, Optional

FAULT_ENV = "AUTOMODEL_FAULT"
_KILL_EXIT_CODE = 113  # distinctive, so subprocess tests can assert on it

# The registry of every named crash site in the codebase (documented above).
# ``fault_point("x")`` call sites are checked against this set by the repo
# linter (``analysis/lint.py`` rule L005), which also requires each name to
# be exercised by at least one ``pytest.mark.fault`` test — registering a
# point here without a drill is itself a lint finding.  Arbitrary names in
# test SPECS stay legal (tests arm synthetic points); only call sites in
# the package must be registered.
KNOWN_FAULT_POINTS = frozenset({
    "ckpt_pre_save",
    "ckpt_async_snapshot",
    "ckpt_async_commit",
    "ckpt_collective_save",
    "ckpt_pre_commit",
    "ckpt_pre_rename",
    "ckpt_post_commit",
    "input_producer",
    "kernel_autotune_cache",
    "elastic_heartbeat",
    "slice_loss",
    "elastic_readmit",
    "ckpt_replica_push",
    "ckpt_replica_restore",
    "serve_block_alloc",
    "serve_request_abort",
    "serve_deadline",
    "serve_shed",
    "serve_watchdog_stall",
    "kv_prefix_lookup",
    "kv_cow_fork",
    "spec_draft",
    "spec_verify",
    "fleet_route",
    "fleet_replica_loss",
    "fleet_replica_admit",
    "adapter_load",
    "adapter_swap",
    "rollout_weight_sync",
    "rollout_engine_step",
    "reward_fn",
})


class InjectedFault(RuntimeError):
    """Raised by an armed fault point (``mode=raise``)."""


@dataclasses.dataclass
class FaultPoint:
    """One armed crash site: fires once, on the ``trigger_at``-th hit."""

    name: str
    trigger_at: int = 1
    mode: str = "raise"  # "raise" | "kill"
    hits: int = 0
    fired: bool = False


_lock = threading.Lock()
_registry: Dict[str, FaultPoint] = {}
_env_loaded = False


def parse_fault_spec(spec: str) -> Dict[str, FaultPoint]:
    """``"name[:count][:mode],..."`` -> name -> :class:`FaultPoint`."""
    points: Dict[str, FaultPoint] = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        name = parts[0]
        if not name:
            raise ValueError(f"fault spec entry {entry!r} has no point name")
        trigger_at = int(parts[1]) if len(parts) > 1 and parts[1] else 1
        if trigger_at < 1:
            raise ValueError(
                f"fault spec {entry!r}: count must be >= 1 (1-based hits)")
        mode = parts[2].lower() if len(parts) > 2 and parts[2] else "raise"
        if mode == "exit":
            mode = "kill"
        if mode not in ("raise", "kill"):
            raise ValueError(
                f"fault spec {entry!r}: mode must be raise|kill, got {mode!r}")
        points[name] = FaultPoint(name=name, trigger_at=trigger_at, mode=mode)
    return points


def configure_faults(spec: Optional[str]) -> None:
    """Arm the registry from a spec string (replaces any prior config);
    ``None``/empty disarms everything.  Marks the env as consumed so a stale
    ``AUTOMODEL_FAULT`` cannot resurrect points after an explicit call."""
    global _env_loaded
    with _lock:
        _registry.clear()
        _env_loaded = True
        if spec:
            _registry.update(parse_fault_spec(spec))


def reset_faults() -> None:
    """Disarm everything (test teardown)."""
    configure_faults(None)


def _ensure_env_loaded() -> None:
    global _env_loaded
    if _env_loaded:
        return
    with _lock:
        if _env_loaded:
            return
        _env_loaded = True
        spec = os.environ.get(FAULT_ENV)
        if spec:
            _registry.update(parse_fault_spec(spec))


def fault_point(name: str) -> None:
    """Mark a named crash site.  No-op unless a spec armed ``name``."""
    _ensure_env_loaded()
    if not _registry:
        return
    with _lock:
        fp = _registry.get(name)
        if fp is None:
            return
        fp.hits += 1
        should_fire = not fp.fired and fp.hits == fp.trigger_at
        if should_fire:
            fp.fired = True
        mode = fp.mode
        hits = fp.hits
    if not should_fire:
        return
    if mode == "kill":
        # Simulate a hard preemption kill: no unwinding, no atexit, no
        # buffered-file flush — the checkpoint commit protocol must make
        # this indistinguishable from pulling the plug.
        os._exit(_KILL_EXIT_CODE)
    raise InjectedFault(f"injected fault at {name!r} (hit {hits})")


def fault_counts() -> Dict[str, int]:
    """Observed hit counts per armed point (test assertions)."""
    with _lock:
        return {name: fp.hits for name, fp in _registry.items()}
