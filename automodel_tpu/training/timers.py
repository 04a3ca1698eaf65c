"""Named timers and trace spans — the framework's one tracing primitive.

``Timers.record(name)`` (and ``timers(name).start()/stop()``) accumulates
host seconds on ``time.perf_counter`` AND opens a
``jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)``.  With no
profiler session the annotation is a flag test; with one
(``profiling.trace_dir`` in training, ``--trace 1`` in the benchmark) the
span lands on the ``/host:CPU`` plane, on the clock the device planes use,
on the line of the thread that recorded it — nesting by time on a thread IS
the parent link.  ``Timers.event(name, **stats)`` is a zero-length span for
stamps that carry integers (a request id, a count).  The profiler session is
the one switch: no registry, exporter, config key or environment variable.

Device work is async: an un-barriered timer measures dispatch, a barriered
one (``jax.block_until_ready`` on a trivial device op) real step latency.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np


# Hot-loop timers whose sum is the device idle attributable to the INPUT
# side of the pipeline: ``data_wait`` (host time blocked pulling the next
# grad-acc group — a queue pop under the async input pipeline, the full
# tokenize/collate/stack cost without it) and ``data_staging`` (host time
# issuing the batch's H2D placement on the SYNCHRONOUS path).  Overlap-aware
# by construction: work the async pipeline moved under device compute stops
# showing up here — the producer thread's collate time never hits these
# timers, and the double buffer's lookahead staging is recorded separately
# as ``data_staging_overlap`` (it runs while the previous step computes, so
# it is not device idle).
INPUT_TIMERS = ("data_wait", "data_staging")


def input_idle_fraction(elapsed: Dict[str, float], window: float) -> float:
    """Steady-state input idle: (data_wait + data_staging) as a fraction of
    a wall-clock window (the benchmark's ``input_wait_share.train`` reads
    the same two spans off a trace); drop it toward 0 by raising
    ``dataloader.prefetch_depth``."""
    if window <= 0:
        return 0.0
    idle = sum(elapsed.get(name, 0.0) for name in INPUT_TIMERS)
    return min(idle / window, 1.0)


# Checkpoint-path timers (recipes/base_recipe.py): ``ckpt_stall`` is the
# time the TRAINING LOOP was blocked by a save — under ``checkpoint.
# async_save`` just the device->host snapshot plus any join on a previous
# in-flight commit; inline (sync) saves charge the whole protocol here.
# ``ckpt_background`` is the committer thread's wall time for the staged
# write/vote/manifest/rename/GC protocol — it overlaps training, so it is
# NOT loop stall (the two timers are recorded from different threads).
CKPT_TIMERS = ("ckpt_stall", "ckpt_background")


def ckpt_stall_fraction(elapsed: Dict[str, float], window: float) -> float:
    """Fraction of a wall-clock window the loop spent blocked on
    checkpointing — the number asynchronous saves exist to drive toward 0
    (logged each profiling interval)."""
    if window <= 0:
        return 0.0
    return min(elapsed.get("ckpt_stall", 0.0) / window, 1.0)


# Elastic-recovery timers (utils/elastic.py + recipes/base_recipe.py):
# ``elastic_detect`` is the wall time from a slice actually dying to the
# coordinator's verdict (heartbeat/poll latency); ``elastic_rebuild`` covers
# the mesh shrink + plan/step rebuild + restore from the last committed
# checkpoint; ``elastic_replay`` is the re-training of steps that were lost
# between that checkpoint and the failure.  None of these produce training
# progress — their sum over a window is the goodput loss a slice failure
# cost.
ELASTIC_TIMERS = ("elastic_detect", "elastic_rebuild", "elastic_replay")


def goodput_fraction(elapsed: Dict[str, float], window: float) -> float:
    """Fraction of a wall-clock window spent making FORWARD progress:
    1 - (detection + rebuild + replay time) / window.  The elastic bench
    secondary reports this next to ``recovery_time_s`` — the two numbers
    MaxText-style goodput accounting tracks for multi-slice runs."""
    if window <= 0:
        return 1.0
    lost = sum(elapsed.get(name, 0.0) for name in ELASTIC_TIMERS)
    return max(0.0, min(1.0, 1.0 - lost / window))


def recovery_time_s(elapsed: Dict[str, float]) -> float:
    """Total seconds one recovery consumed (detect + rebuild + replay) —
    the bounded-recovery-time number the elastic acceptance bar pins."""
    return sum(elapsed.get(name, 0.0) for name in ELASTIC_TIMERS)


# Restore-path timers (recipes/base_recipe.py::load_checkpoint): every
# checkpoint restore is credited to exactly one of these by its SOURCE —
# ``ckpt_restore_peer_ram`` when the params/opt payload came out of a
# neighbor slice's in-memory replica (checkpoint/replication.py),
# ``ckpt_restore_storage`` when it was read from the checkpoint directory.
# Restore time dominates ``recovery_time_s`` at 70B scale, and the peer
# path exists to move it from blob-store latency to host-RAM bandwidth —
# the split is the honest way to see whether it did.
RESTORE_TIMERS = ("ckpt_restore_peer_ram", "ckpt_restore_storage")


def restore_time_by_source(elapsed: Dict[str, float]) -> Dict[str, float]:
    """``{"peer_ram": s, "storage": s}`` — the restore-latency split the
    elastic bench secondary reports next to ``recovery_time_s``."""
    return {name[len("ckpt_restore_"):]: elapsed.get(name, 0.0)
            for name in RESTORE_TIMERS}


# Serving timers + outcome accounting (serving/engine.py, tools/serve.py):
# ``serve_step`` is the wall time of every ``engine.step()``, ``serve_drain``
# the graceful-drain window after SIGTERM/SIGINT, ``serve_recovery`` the
# host time watchdog recoveries spent reclaiming tables and rebuilding
# pools.  SERVE_PHASE_TIMERS split ``serve_step`` where the work happens:
# scheduler (expiry, admission, preemption), buffer assembly, the call
# into the jitted step (its span carries the step's row/position counts),
# the one host sync (one step behind the dispatch where the engine looks
# ahead), and the scheduler's ``deliver``.  The outcome-rate
# helpers below read ``DecodeEngine.outcome_counts()``-shaped dicts
# (state-name -> request count) — the four numbers the serving acceptance
# bar pins under a 2x-capacity overload trace.
SERVE_TIMERS = ("serve_step", "serve_drain", "serve_recovery")
SERVE_PHASE_TIMERS = ("serve_schedule", "serve_assemble", "serve_dispatch",
                      "serve_fetch", "serve_finish")


def serve_shed_rate(outcomes: Dict[str, int]) -> float:
    """Fraction of submitted requests admission control REJECTED (load
    shedding + drain rejections) — rises with overload by design: a shed
    request cost nothing but a queue check."""
    total = sum(outcomes.values())
    return outcomes.get("rejected", 0) / total if total else 0.0


def serve_expired_rate(outcomes: Dict[str, int]) -> float:
    """Fraction of submitted requests that ran out of deadline/TTL budget
    after being accepted (terminal EXPIRED) — the number that should stay
    LOW even under overload: admission control exists to convert
    would-be expiries into cheap rejections."""
    total = sum(outcomes.values())
    return outcomes.get("expired", 0) / total if total else 0.0


def serve_goodput_fraction(completed_in_deadline: int,
                           outcomes: Dict[str, int]) -> float:
    """Completed-before-deadline fraction of ALL submitted requests — the
    serving analogue of the elastic goodput number: work that arrived,
    was admitted, finished, and met its budget."""
    total = sum(outcomes.values())
    return completed_in_deadline / total if total else 1.0


# Pipeline-parallel bubble accounting (training/pipeline.py): every
# optimizer step's microbatch loop runs ``k + warmup`` slots per
# grad-accumulation microbatch, of which ``warmup`` (the fill) plus the
# mirror-image drain in the backward are idle on any given stage.
def pp_bubble_fraction(pp_size: int, num_microbatches: int,
                       schedule: str = "1f1b") -> float:
    """Warmup+cooldown idle fraction of the pipelined step's wall time.

    Schedule-derived and exact for equal-cost microbatches: a stage is busy
    for ``k`` of the ``k + stride*(pp-1)`` slots of each pipeline pass
    (fwd and bwd passes have the same shape under AD, so the per-step
    fraction equals the per-pass fraction).  ``stride`` is 1 for ``gpipe``
    and 2 for ``1f1b`` (the double-buffered boundary trades one extra
    warmup/cooldown slot pair per stage for permute/compute overlap).
    Logged per profiling window when pp > 1 and reported by the bench
    ``pipeline`` secondary; drive it toward 0 by raising
    ``pipeline.num_microbatches``.
    """
    if pp_size <= 1:
        return 0.0
    from automodel_tpu.training.pipeline import schedule_slots

    num_slots, warmup, _ = schedule_slots(pp_size, num_microbatches,
                                          schedule)
    return warmup / num_slots


@dataclasses.dataclass
class ProfilingConfig:
    """``profiling:`` YAML section — wires :class:`Timers` into the hot loop.

    Reference parity: the recipe-driven timer cadence of
    ``nemo_automodel/components/training/timers.py:433-538`` plus an nsys-like
    windowed trace (``jax.profiler`` xplane dump).

    ``barrier=True`` blocks on each step's device results before stopping the
    ``step_e2e`` timer — true per-step latency, at the cost of the pipelined
    dispatch overlap (measurement mode, not the training default).
    """

    enabled: bool = False
    log_interval: int = 10
    barrier: bool = False
    trace_dir: Optional[str] = None
    trace_start_step: int = 1
    trace_stop_step: int = 3


def build_profiling_config(cfg) -> ProfilingConfig:
    """ProfilingConfig from a ConfigNode/dict (None -> disabled)."""
    if cfg is None:
        return ProfilingConfig()
    raw = cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg)
    fields = {f.name for f in dataclasses.fields(ProfilingConfig)}
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"unknown profiling keys: {sorted(unknown)}")
    out = ProfilingConfig(**{k: v for k, v in raw.items()})
    if "enabled" not in raw:
        out.enabled = True  # presence of the section turns profiling on
    return out


# Every span and event of the program is named SPAN_PREFIX + its timer's
# key; benchmark/program_trace.py repeats the constant (a test pins it).
SPAN_PREFIX = "automodel/"


class _Timer:
    # The accumulator state is lock-guarded: the async-checkpoint committer
    # records ``ckpt_background`` from its own thread while the training
    # loop's profiling interval reads/resets the same Timers instance —
    # unguarded, elapsed() can see stop() clear _start between its check
    # and its subtraction (TypeError), and a concurrent += vs = 0.0 loses
    # or double-counts the commit time.

    def __init__(self, name: str):
        self.name = name
        self._start: Optional[float] = None
        self._span = None
        self._elapsed = 0.0
        self._lock = threading.Lock()

    def start(self, barrier: bool = False, **stats) -> None:
        if barrier:
            _device_barrier()
        span = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name, **stats)
        with self._lock:
            assert self._start is None, f"timer {self.name} already started"
            self._span = span
            span.__enter__()
            self._start = time.perf_counter()

    def _close(self, record: bool) -> None:
        with self._lock:
            if self._start is None:
                assert not record, f"timer {self.name} not started"
                return
            if record:
                self._elapsed += time.perf_counter() - self._start
            self._start = None
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self, barrier: bool = False) -> None:
        if barrier:
            _device_barrier()
        self._close(record=True)

    def elapsed(self, reset: bool = True) -> float:
        # A running timer is read without stopping: the partial interval is
        # included, and on reset the running span is re-based to now so the
        # partial interval is not reported twice.
        with self._lock:
            out = self._elapsed
            now = time.perf_counter()
            if self._start is not None:
                out += now - self._start
                if reset:
                    self._start = now
            if reset:
                self._elapsed = 0.0
            return out

    def add(self, seconds: float) -> None:
        """Credit an externally-measured interval (e.g. the elastic
        detector's poll-gap latency — wall time that elapsed before any
        timer could be running).  No span: it has no place on the clock."""
        if seconds <= 0:
            return
        with self._lock:
            self._elapsed += seconds

    def discard(self) -> None:
        """Abandon a running interval without recording it (e.g. a data-wait
        that ended in StopIteration)."""
        self._close(record=False)


def _device_barrier() -> None:
    # local_devices: jax.devices()[0] is unaddressable on processes > 0.
    # device_get: the fetched value cannot exist before the device is done.
    jax.device_get(  # lint: disable=L004 (this IS the barrier: a timer sync point, only reachable in profiling.barrier measurement runs)
        jax.device_put(np.zeros(()), jax.local_devices()[0]))


class Timers:
    """``with timers.record("dispatch", step=n): ...`` or
    ``timers("x").start(); ...; timers("x").stop()``"""

    def __init__(self):
        self._timers: Dict[str, _Timer] = {}
        # registry lock: the async-checkpoint committer creates/records its
        # timer from a background thread while the loop iterates the dict
        self._registry_lock = threading.Lock()

    def __call__(self, name: str) -> _Timer:
        with self._registry_lock:
            if name not in self._timers:
                self._timers[name] = _Timer(name)
            return self._timers[name]

    @contextlib.contextmanager
    def record(self, name: str, barrier: bool = False, **stats):
        t = self(name)
        t.start(barrier=barrier, **stats)
        try:
            yield t
        finally:
            t.stop(barrier=barrier)

    @staticmethod
    def event(name: str, **stats) -> None:
        """A stamp, not an interval: a zero-length span that carries
        ``stats`` into the trace.  Nothing is kept without a session."""
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats):
            pass

    def get_elapsed(self, names: Optional[List[str]] = None,
                    reset: bool = True, normalizer: float = 1.0) -> Dict[str, float]:
        with self._registry_lock:
            if names is None:
                names = list(self._timers)
            timers = [(n, self._timers[n]) for n in names
                      if n in self._timers]
        return {n: t.elapsed(reset=reset) / normalizer for n, t in timers}
