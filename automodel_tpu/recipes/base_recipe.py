"""Checkpoint-aware trainer base.

Reference parity: ``nemo_automodel/recipes/base_recipe.py:90-363`` —
``__setattr__`` auto-tracks any attribute exposing ``state_dict``/
``load_state_dict`` (plus ConfigNode) into ``_state_tracked``, excluding
names containing val/eval/test; ``save_checkpoint`` writes model weights,
optimizer+scheduler, config.yaml, and pickles the rest on process 0;
``load_checkpoint`` finds the latest ``epoch_*_step_*`` directory.

The model itself is functional (structure + ``self.params`` pytree), so
unlike the reference there is no nn.Module special-casing: ``save_checkpoint``
saves ``self.params`` via the checkpoint subsystem and every tracked host
object via its ``state_dict``.

Asynchronous saves (``checkpoint.async_save``, the default; see
docs/guides/checkpointing.md "Asynchronous saves"): ``save_checkpoint``
blocks only for a device->host SNAPSHOT of params/opt state plus the
host-side state dicts, then a single background committer thread runs the
entire crash-safe protocol — stage ``.tmp`` -> write -> ``ckpt:
host_writes_ok`` vote -> manifest -> atomic rename -> retention GC —
against the snapshot while training continues.  Invariants:

* at most ONE save in flight: a new save, a preemption grace-window save,
  an end-of-training save, or :meth:`teardown` first JOINS the previous
  one and surfaces its error (``CheckpointSaveError``);
* every multihost vote/barrier of a background save runs under the
  dedicated ``ckpt_async`` collective namespace (KV-store RPCs, never
  device collectives — ``utils/dist_utils.CollectiveNamespace``), so it
  cannot interleave with training-loop collectives;
* a crash mid-background-write still leaves only a ``.tmp`` staging dir
  that resume ignores — committed-ness remains the final directory name;
* the snapshot pins the dataloader's last-CONSUMED batch state
  (``consumed_state_dict``), so an async mid-epoch save resumes
  stitch-exact under the prefetching input pipeline.
"""

from __future__ import annotations

import contextlib
import copy
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from automodel_tpu.checkpoint import checkpointing as ckpt
from automodel_tpu.config.loader import ConfigNode, dump_yaml_config
from automodel_tpu.utils.fault_injection import fault_point

logger = logging.getLogger(__name__)

_SKIP_SUBSTRINGS = ("val", "eval", "test")


def has_load_restore_state(obj: Any) -> bool:
    return hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict")


class _SaveJob:
    """Everything one save needs, captured at the save boundary.

    The inline (sync) path carries LIVE objects — state dicts are read at
    write time, exactly the pre-async behavior.  The async path carries a
    HOST SNAPSHOT: numpy params/opt trees and materialized (deep-copied)
    state dicts, so the background committer never touches live training
    state and a batch consumed after the boundary cannot leak in.
    """

    def __init__(self, *, epoch: int, step: int, final: str, cfg,
                 model=None, params=None, opt_state=None, scheduler=None,
                 peft_config=None, host_state=(), resumed_from=None,
                 coordinator=None, is_async: bool = False):
        self.epoch, self.step, self.final, self.cfg = epoch, step, final, cfg
        self.model, self.params, self.opt_state = model, params, opt_state
        self.scheduler, self.peft_config = scheduler, peft_config
        self.host_state: List[Tuple[str, Any]] = list(host_state)
        self.resumed_from = resumed_from
        self.coordinator = coordinator
        self.is_async = is_async


class BaseRecipe:
    def __init__(self):
        object.__setattr__(self, "_state_tracked", {})
        object.__setattr__(self, "_inflight_save", None)

    def __setattr__(self, key: str, value: Any) -> None:
        if not key.startswith("_") and not any(
                s in key.lower() for s in _SKIP_SUBSTRINGS):
            if has_load_restore_state(value) or isinstance(value, ConfigNode):
                self._state_tracked[key] = value
        object.__setattr__(self, key, value)

    # -- shared setup hooks --------------------------------------------------
    def _setup_compile_cache(self, cfg: Optional[ConfigNode]) -> None:
        """Place the persistent XLA compile cache by the one rule in
        ``utils/compile_utils.py`` (``JAX_COMPILATION_CACHE_DIR`` if set,
        else the fixed in-checkout directory); a ``compile:`` YAML section
        can only disable it or tune the persist threshold.  The first
        dispatch's wall time is logged by the recipes so cache hits are
        visible in the run log."""
        from automodel_tpu.utils.compile_utils import (
            apply_compile_config,
            build_compile_config,
        )

        apply_compile_config(build_compile_config(
            cfg.get("compile") if cfg is not None else None))

    def _setup_kernel_autotune(self, cfg: Optional[ConfigNode], *,
                               model=None, seq_len=None,
                               local_batch: int = 1, cp: int = 1) -> None:
        """Wire the Pallas block-size autotuner from the ``kernels:`` YAML
        section (``ops/kernel_lib/autotune.py``; call AFTER
        :meth:`_setup_compile_cache` so the cache lands alongside the XLA
        compile cache by default)::

            kernels:
              autotune: on          # off (default) | on | force
              autotune_cache: /path/pallas_autotune_v1.json   # optional

        With ``on``/``force`` and a model, the block-shape sweep for this
        run's (kernel, shape) keys executes HERE — before the first train
        step traces — so a cold run pays the sweep once at setup and a
        warm cache makes it free.  A corrupt cache degrades to hand-tuned
        defaults (never fails setup); multihost runs never sweep (winners
        must be identical on every host — pre-warm via tools/autotune.py).
        """
        from automodel_tpu.ops.kernel_lib import autotune

        kcfg = cfg.get("kernels") if cfg is not None else None
        mode = kcfg.get("autotune") if kcfg is not None else None
        cache_path = kcfg.get("autotune_cache") if kcfg is not None else None
        tuner = autotune.configure_autotune(mode, cache_path)
        if tuner.mode == "off" or model is None:
            return
        requests = autotune.training_sweep_requests(
            model, seq_len=seq_len, local_batch=local_batch, cp=cp)
        if requests:
            report = tuner.sweep_requests(requests)
            logger.info("kernel autotune sweep: %s", report)

    # -- timers (optional: _TinyRecipe-style harnesses have none) ------------
    def _record_timer(self, name: str):
        timers = getattr(self, "timers", None)
        if timers is None:
            return contextlib.nullcontext()
        return timers.record(name)

    # -- elastic recovery ----------------------------------------------------
    def _rebuild_parallelism(self, mesh_manager) -> None:
        """Rebuild plan + step functions for a NEW mesh (elastic shrink or
        grow-back).

        Recipes register ``self._parallelism_builder`` — a callable
        ``mesh_manager -> (plan, step_fns)`` capturing their model /
        optimizer / loss / masking choices — at setup; this hook applies it
        and swaps in ABSTRACT (ShapeDtypeStruct) params/opt-state carrying
        the new shardings, ready for the mesh-reshape checkpoint restore.
        """
        builder = getattr(self, "_parallelism_builder", None)
        if builder is None:
            raise NotImplementedError(
                f"{type(self).__name__} cannot rebuild after a slice loss: "
                "set self._parallelism_builder = (mesh_manager -> "
                "(plan, step_fns)) during setup")
        plan, fns = builder(mesh_manager)
        self.plan, self.step_fns = plan, fns
        self.param_sharding = plan.param_sharding
        abs_params = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(self.model.init, jax.random.key(0)),
            plan.param_sharding)
        self.params = abs_params
        abs_opt = jax.eval_shape(fns.init_opt_state, abs_params)
        self.opt_state = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abs_opt, fns.opt_state_sharding)

    def recover_from_slice_loss(self, event) -> Dict[str, Any]:
        """Slice loss -> running again, with NO operator action.  Thin
        compatibility wrapper over :meth:`reconfigure` (an int ``event`` is
        a bare lost-slice id)."""
        from automodel_tpu.utils.elastic import SliceLostError

        if not isinstance(event, SliceLostError):
            event = SliceLostError(int(event), "caller-reported loss")
        return self.reconfigure(event)

    def reconfigure(self, event) -> Dict[str, Any]:
        """The ONE topology-change path, shared by slice LOSS and slice
        GAIN (grow-back):

        1. **Resize**: rebuild the mesh — ``shrink_slices`` at
           ``dcn_dp - 1`` for a :class:`~automodel_tpu.utils.elastic.
           SliceLostError`, ``grow_slices`` at ``dcn_dp + 1`` for a
           :class:`~automodel_tpu.utils.elastic.SliceReturnedError` (the
           retired slice's devices were remembered by the shrink) — and
           rebuild the plan/step functions on it
           (:meth:`_rebuild_parallelism`).
        2. **Restore**: resume params/optimizer/host state from the last
           COMMITTED checkpoint.  Peer RAM first: when the in-memory
           replica a neighbor slice holds matches the checkpoint step, the
           restore is a digest-verified RAM fetch instead of a storage
           read (``checkpoint/replication.py``; ``restore_source`` in the
           returned info says which path ran).  An in-flight background
           save is joined with its error demoted to a log — its snapshot
           predates the event and may never commit; committed-ness remains
           the only currency.  A LOSS also drops the dead slice's replica
           store (its RAM died with it).  Gain callers admit at a
           commit boundary, so their restore loses zero steps.
        3. **Rescale**: apply the documented deterministic rule
           CHECKPOINT-regime -> new-topology
           (``utils/elastic.rescale_between``): a shrink multiplies
           grad-accumulation by ``old/gcd(old,new)``, a grow divides by
           the same factor (the exact inverse), so tokens-per-optimizer-
           step — and therefore the LR schedule and per-token LR — are
           unchanged whenever the counts divide; any residual batch ratio
           folds into a linear LR scale, keeping per-token LR exact.  A
           shrink -> grow-back sequence therefore lands back on the
           original hyperparameter regime.

        Wall time is charged to the ``elastic_rebuild`` timer (goodput
        accounting, ``training/timers.py``).  Returns a summary dict
        ``{event, lost_slice | returned_slice, new_dcn_dp, restored_from,
        restored_step, accum_factor, accum_divisor, lr_scale,
        restore_source}``.
        """
        from automodel_tpu.checkpoint import replication
        from automodel_tpu.utils.elastic import (
            SliceReturnedError,
            rescale_between,
        )

        gained = isinstance(event, SliceReturnedError)
        with self._record_timer("elastic_rebuild"):
            # the in-flight snapshot predates the event; never let its
            # failure mask the recovery (committed state is the fallback)
            self.join_pending_save(raise_error=False)
            old_mm = self.mesh_manager
            if gained:
                new_mm = old_mm.grow_slices(event.slice_id)
            else:
                # shrink FIRST: a slice loss at dcn_dp=1 must surface the
                # designed full-pool-loss error, not a rescale-domain
                # ValueError.  The dead slice's replica store dies with it
                # — identified by its DEVICE IDS, not its current index
                # (store keys are push-time indices; stacked losses with
                # no push in between renumber past them).
                lost_devs = [d.id
                             for d in old_mm.slice_devices(event.slice_id)]
                new_mm = old_mm.shrink_slices(event.slice_id)
                replication.drop_slice(event.slice_id, devices=lost_devs)
            self.mesh_manager = new_mm
            self._rebuild_parallelism(new_mm)
            # shardings changed: re-probe async-save feasibility next save
            object.__setattr__(self, "_async_snapshot_ok", None)
            restored = self.load_checkpoint()
            if restored is None:
                raise ckpt.CheckpointSaveError(
                    f"slice {event.slice_id} "
                    f"{'returned' if gained else 'lost'} but no committed "
                    "checkpoint exists to resume from — enable "
                    "checkpointing for elastic runs")
            # Rescale AFTER restore, from the regime the CHECKPOINT was
            # saved under (elastic_state rode the restore): the LR fields
            # just rewound to checkpoint values, so pairing them with a
            # checkpoint-relative factor keeps the two consistent even when
            # a SECOND topology change lands before any new checkpoint —
            # an incremental old-mesh-relative factor would compound across
            # recoveries while the LR rewound.
            es = getattr(self, "elastic_state", None)
            ckpt_slices = es.dcn_dp if es is not None else old_mm.dcn_dp_size
            sched = getattr(self, "step_scheduler", None)
            ckpt_accum = (es.grad_acc_steps if es is not None
                          else getattr(sched, "grad_acc_steps", 1))
            rescale = rescale_between(ckpt_slices, new_mm.dcn_dp_size)
            new_accum, residual_lr = rescale.target_accum(ckpt_accum)
            if sched is not None and hasattr(sched, "grad_acc_steps"):
                sched.grad_acc_steps = new_accum
            lr_scale = rescale.lr_scale * residual_lr
            lr_sched = getattr(self, "lr_scheduler", None)
            if lr_sched is not None and lr_scale != 1.0:
                for attr in ("init_lr", "max_lr", "min_lr"):
                    setattr(lr_sched, attr,
                            getattr(lr_sched, attr) * lr_scale)
                lr_sched.step(0)  # refresh current_lr under the new scale
            if es is not None:
                # the NEXT checkpoint must record the post-event regime
                es.dcn_dp = new_mm.dcn_dp_size
                es.grad_acc_steps = (new_accum if sched is None
                                     else getattr(sched, "grad_acc_steps",
                                                  new_accum))
        restore_source = getattr(self, "_restore_source", "storage")
        info = {
            "event": "slice_gain" if gained else "slice_loss",
            ("returned_slice" if gained else "lost_slice"): event.slice_id,
            "new_dcn_dp": new_mm.dcn_dp_size,
            "restored_from": restored,
            "restored_step": getattr(getattr(self, "step_scheduler", None),
                                     "step", None),
            "accum_factor": rescale.accum_factor,
            "accum_divisor": rescale.accum_divisor,
            "grad_acc_steps": new_accum,
            "lr_scale": lr_scale,
            "restore_source": restore_source,
        }
        logger.warning(
            "elastic %s: slice %d %s -> mesh rebuilt at dcn_dp=%d, "
            "grad_acc %d -> %d, lr x%.4g, resumed from %s "
            "(restore_source=%s)",
            "grow-back" if gained else "recovery", event.slice_id,
            "returned" if gained else "lost", new_mm.dcn_dp_size,
            ckpt_accum, new_accum, lr_scale, restored, restore_source)
        return info

    # -- save ----------------------------------------------------------------
    def save_checkpoint(self, epoch: int, step: int) -> str:
        """Crash-safe save: stage -> write -> barrier -> manifest -> rename.

        Every writer targets ``<final>.tmp``; after all collective saves
        finish, process 0 writes ``manifest.json`` and atomically renames
        the staging dir (``checkpointing.commit_checkpoint``), so the final
        name exists iff the checkpoint is complete.  A kill at any point
        before the rename leaves only a ``.tmp`` dir that resume ignores
        and the next save at the same step clears.  After a successful
        commit, retention GC prunes superseded checkpoints per
        ``keep_last_k``/``keep_every_n_steps`` (never the resume source).

        With ``checkpoint.async_save`` (default) only the device->host
        snapshot happens here — the protocol above runs on the background
        committer and this returns the final path the commit will land at;
        a commit failure surfaces at the next join point (next save, the
        preemption save, :meth:`teardown`, or end of training).  The time
        this method blocks the loop is recorded as the ``ckpt_stall``
        timer; the committer's wall time as ``ckpt_background``.
        """
        cfg: ckpt.CheckpointingConfig = getattr(
            self, "checkpoint_config", None) or ckpt.CheckpointingConfig()
        if not cfg.enabled:
            return ""
        with self._record_timer("ckpt_stall"):
            # at most one save in flight: joining here also surfaces a
            # previous background commit's failure before new state is risked
            self.join_pending_save()
            fault_point("ckpt_pre_save")
            final = os.path.join(
                cfg.checkpoint_dir, ckpt.checkpoint_dir_name(epoch, step))
            if not cfg.async_save or not self._async_snapshot_feasible():
                job = self._build_live_save_job(epoch, step, final, cfg)
                return self._run_commit_protocol(job)
            fault_point("ckpt_async_snapshot")
            job = self._build_snapshot_save_job(epoch, step, final, cfg)
            holder = {"final": final, "error": None}
            thread = threading.Thread(
                target=self._commit_in_background, args=(job, holder),
                name="automodel-ckpt-committer", daemon=False)
            holder["thread"] = thread
            object.__setattr__(self, "_inflight_save", holder)
            thread.start()
        logger.info(
            "Checkpoint %s dispatched to the background committer "
            "(snapshot taken; training resumes)", final)
        return final

    def _async_snapshot_feasible(self) -> bool:
        """Async saves snapshot the FULL params/opt state into host memory.
        Single-process, replicated, and HSDP replica-complete shardings can
        do that from local shards; state genuinely sharded ACROSS hosts
        (multi-host FSDP) would need a full-tree gather onto every host —
        an OOM at exactly the scales async saves target, and it would also
        defeat the per-host-shard Orbax write.  Such runs keep the inline
        save (pre-async behavior, warned once).  Shardings never change
        between saves, so the probe result is cached.

        The local probe is VOTED across hosts: shard coverage is a
        per-host property (an HSDP replica axis may land inside one host
        but straddle another), and a host that went async would wait on
        KV-store barriers while an inline host waits on device
        collectives — primitives that can never match.  All hosts reach
        this probe together (same save boundary, same config), so the
        vote is a safe training-thread collective."""
        ok = getattr(self, "_async_snapshot_ok", None)
        if ok is None:
            from automodel_tpu.utils.dist_utils import all_hosts_ok

            ok = all_hosts_ok(
                ckpt.snapshot_is_host_complete(getattr(self, "params", None))
                and ckpt.snapshot_is_host_complete(
                    getattr(self, "opt_state", None)),
                "ckpt:async_feasible")
            if not ok:
                logger.warning(
                    "checkpoint.async_save disabled for this run: params/"
                    "optimizer state is sharded across hosts, so a host "
                    "snapshot would gather the full tree onto every host; "
                    "saves stay inline (crash-safe protocol unchanged)")
            object.__setattr__(self, "_async_snapshot_ok", ok)
        return ok

    def _ckpt_coordinator(self):
        """The dedicated collective namespace for background commits —
        lazily built once per recipe so its barrier sequence numbers stay
        aligned across hosts (every host runs the same save sequence)."""
        coord = getattr(self, "_ckpt_coord", None)
        if coord is None:
            from automodel_tpu.utils.dist_utils import CollectiveNamespace

            coord = CollectiveNamespace("ckpt_async")
            object.__setattr__(self, "_ckpt_coord", coord)
        return coord

    def _tracked_host_state(self) -> List[Tuple[str, Any]]:
        return [(key, obj) for key, obj in self._state_tracked.items()
                if key not in ("lr_scheduler",)]  # saved with the optimizer

    def _build_live_save_job(self, epoch, step, final, cfg) -> _SaveJob:
        return _SaveJob(
            epoch=epoch, step=step, final=final, cfg=cfg,
            model=getattr(self, "model", None),
            params=getattr(self, "params", None),
            opt_state=getattr(self, "opt_state", None),
            scheduler=getattr(self, "lr_scheduler", None),
            peft_config=getattr(self, "peft_config", None),
            host_state=self._tracked_host_state(),
            resumed_from=getattr(self, "_resumed_from", None))

    def _build_snapshot_save_job(self, epoch, step, final, cfg) -> _SaveJob:
        """The blocking half of an async save: one batched device->host
        fetch of params/opt state (cross-host-sharded leaves consolidated
        here, on the training thread — the committer must never run a
        device collective) plus deep copies of every host-side state dict.
        The dataloader contributes its last-CONSUMED-batch snapshot
        (``consumed_state_dict``), pinning async resume to exactly the
        batches trained on — queued/staged prefetch lookahead is invisible
        to the committer by construction."""
        params = getattr(self, "params", None)
        opt_state = getattr(self, "opt_state", None)
        scheduler = getattr(self, "lr_scheduler", None)
        host_state: List[Tuple[str, Any]] = []
        for key, obj in self._tracked_host_state():
            if isinstance(obj, ConfigNode):
                host_state.append((key, copy.deepcopy(obj)))
            elif hasattr(obj, "consumed_state_dict"):
                host_state.append(
                    (key, copy.deepcopy(obj.consumed_state_dict())))
            elif hasattr(obj, "state_dict"):
                host_state.append((key, copy.deepcopy(obj.state_dict())))
            else:
                host_state.append((key, copy.deepcopy(obj)))
        # ONE snapshot call for both trees: the batched device->host fetch
        # pays its round-trip latency once, not once per tree
        snap = ckpt.snapshot_to_host({"params": params, "opt": opt_state})
        return _SaveJob(
            epoch=epoch, step=step, final=final, cfg=cfg,
            model=getattr(self, "model", None),
            params=snap["params"],
            opt_state=snap["opt"],
            scheduler=(None if scheduler is None
                       else copy.deepcopy(scheduler.state_dict())),
            peft_config=getattr(self, "peft_config", None),
            host_state=host_state,
            resumed_from=getattr(self, "_resumed_from", None),
            coordinator=self._ckpt_coordinator(), is_async=True)

    def _commit_in_background(self, job: _SaveJob, holder: Dict) -> None:
        try:
            with self._record_timer("ckpt_background"):
                self._run_commit_protocol(job)
        except BaseException as e:  # surfaced at the next join point
            holder["error"] = e
            logger.exception(
                "background checkpoint commit of %s failed", job.final)

    def join_pending_save(self, raise_error: bool = True) -> Optional[str]:
        """Wait for the in-flight background save, if any; its final path.

        A commit failure re-raises here as :class:`~automodel_tpu.
        checkpoint.checkpointing.CheckpointSaveError` (original failure
        chained) — the async path's error surface.  ``raise_error=False``
        logs instead (teardown while another exception is already
        propagating must not mask it)."""
        holder = getattr(self, "_inflight_save", None)
        if holder is None:
            return None
        holder["thread"].join()
        object.__setattr__(self, "_inflight_save", None)
        err = holder.get("error")
        if err is None:
            return holder["final"]
        if not raise_error:
            logger.error(
                "suppressing background checkpoint failure of %s during "
                "teardown: %s", holder["final"], err)
            return None
        if isinstance(err, ckpt.CheckpointSaveError):
            raise err
        raise ckpt.CheckpointSaveError(
            f"asynchronous checkpoint commit of {holder['final']} failed "
            "in the background committer") from err

    def teardown(self, raise_error: bool = True) -> None:
        """Join-on-teardown: the background committer (non-daemon) must have
        exited — commit landed or error surfaced — before the recipe is
        released; also unwinds the input pipeline's producer thread."""
        self.join_pending_save(raise_error=raise_error)
        loader = getattr(self, "dataloader", None)
        if loader is not None and hasattr(loader, "close"):
            loader.close()

    def _run_commit_protocol(self, job: _SaveJob) -> str:
        """The crash-safe commit protocol, shared verbatim by the inline
        path (training thread, device collectives) and the background
        committer (host snapshot, ``ckpt_async`` KV-namespace collectives —
        ``job.coordinator``)."""
        path = ckpt.prepare_staging(  # collective
            job.final, job.cfg, coordinator=job.coordinator)
        if job.is_async:
            # Armed under AUTOMODEL_FAULT=ckpt_async_commit (tests): a
            # failure at the start of the background write — staging
            # exists, nothing committed; surfaces at the next join point.
            fault_point("ckpt_async_commit")
        try:
            return self._commit_into_staging(job, path)
        except BaseException:
            # any abort leaves staging for inspection but must drop the
            # manifest hash hints recorded for it (pop-on-use never ran);
            # a retry at the same step re-records its own
            ckpt._purge_file_hashes(path)
            raise

    def _commit_into_staging(self, job: _SaveJob, path: str) -> str:
        cfg, final, coord = job.cfg, job.final, job.coordinator
        is_main = jax.process_index() == 0

        # COLLECTIVE writers (model weights, optimizer) under the same
        # try/vote discipline as the host-side writes below: an exception
        # raised here on ONE host would skip that host's
        # ``ckpt:host_writes_ok`` vote while its peers — whose collective
        # save calls completed locally — sit in the vote barrier forever.
        # Catching and voting turns one failing host into a lockstep abort
        # on every host.  (The vote itself is the first collective the
        # failing host still participates in.)
        host_err = None
        try:
            fault_point("ckpt_collective_save")
            # model weights (collective; host-snapshot numpy under async)
            if job.params is not None:
                ckpt.save_model(job.model, job.params,
                                os.path.join(path, "model"), cfg,
                                peft_config=job.peft_config,
                                coordinator=coord)
            # optimizer + LR scheduler (collective)
            if job.opt_state is not None:
                ckpt.save_optimizer(
                    job.opt_state, os.path.join(path, "optim"),
                    scheduler=job.scheduler, config=cfg, coordinator=coord)
        except Exception as e:
            host_err = e
            logger.exception(
                "collective checkpoint writes failed for %s", final)
        # host-side statefuls + config on process 0.  Failures here (retries
        # exhausted) are caught and put to a collective vote instead of
        # raised: raising past commit_checkpoint's barrier would leave every
        # peer host hanging in it, turning one bad disk into a silently hung
        # pool.  All hosts abort (or commit) in lockstep.
        if is_main and host_err is None:
            try:
                for key, obj in job.host_state:
                    if isinstance(obj, ConfigNode):
                        ckpt.retry_io(
                            dump_yaml_config, obj,
                            os.path.join(path, "config.yaml"),
                            retries=cfg.io_retries,
                            backoff=cfg.io_retry_backoff, desc="config.yaml")
                    else:
                        # Async-input contract: a prefetching dataloader's
                        # live state runs ahead of training (queued +
                        # staged lookahead), so the save path explicitly
                        # requests the last-CONSUMED-batch snapshot when an
                        # object distinguishes the two (datasets/prefetch
                        # .py) — resume then replays nothing and skips
                        # nothing.  Snapshot jobs already hold plain dicts
                        # (materialized at the save boundary); save_stateful
                        # pickles those as-is.
                        if hasattr(obj, "consumed_state_dict"):
                            obj = obj.consumed_state_dict()
                        ckpt.save_stateful(path, key, obj, cfg)
            except Exception as e:
                host_err = e
                logger.exception(
                    "host-side checkpoint writes failed for %s", final)
        fault_point("ckpt_pre_commit")
        all_hosts_ok, _ = ckpt._sync_fns(coord)
        if not all_hosts_ok(host_err is None, "ckpt:host_writes_ok"):
            note = f"; staging left at {path} for inspection"
            if host_err is not None:
                raise ckpt.CheckpointSaveError(
                    f"aborting commit of {final}: checkpoint writes failed "
                    f"on this host{note}") from host_err
            raise ckpt.CheckpointSaveError(
                f"aborting commit of {final}: a peer host failed its "
                f"writes{note}")
        ckpt.commit_checkpoint(path, final, epoch=job.epoch, step=job.step,
                               config=cfg, coordinator=coord)
        fault_point("ckpt_post_commit")
        # Peer-to-peer in-memory replication (checkpoint/replication.py):
        # the committer already holds the HOST snapshot, so pushing it to
        # the ring-neighbor slice's RAM store costs one serialize pass and
        # zero device traffic.  Strictly AFTER the commit (a replica may
        # only ever advertise committed state) and guarded — the save has
        # landed, a replication failure must never un-land it.
        if job.is_async and cfg.replicate_to_peers and job.params is not None:
            try:
                from automodel_tpu.checkpoint import replication

                replication.push_replica(
                    epoch=job.epoch, step=job.step,
                    trees={"params": job.params, "opt": job.opt_state},
                    mesh_manager=getattr(self, "mesh_manager", None),
                    checkpoint_dir=cfg.checkpoint_dir, ckpt_path=final)
            except Exception:
                logger.warning(
                    "peer replica push for %s failed; the commit stands "
                    "and the next restore takes the storage path",
                    final, exc_info=True)
        if is_main:
            deleted = ckpt.gc_checkpoints(
                cfg.checkpoint_dir, keep_last_k=cfg.keep_last_k,
                keep_every_n_steps=cfg.keep_every_n_steps,
                protect=(job.resumed_from,), config=cfg)
            if deleted:
                logger.info("Checkpoint GC removed %d superseded dir(s): %s",
                            len(deleted),
                            ", ".join(os.path.basename(d) for d in deleted))
        logger.info("Committed checkpoint %s%s", final,
                    " (background)" if job.is_async else "")
        return final

    # -- load ----------------------------------------------------------------
    def load_checkpoint(self, restore_from: Optional[str] = None) -> Optional[str]:
        """Resume from ``restore_from`` (explicit) or the newest committed
        checkpoint.  The manifest is verified BEFORE any state is touched,
        so a corrupt/uncommitted dir fails with an error naming it instead
        of a half-restored recipe; discovery already skips such dirs."""
        # an in-flight background save must land (or surface its failure)
        # before resume scans the checkpoint root
        self.join_pending_save()
        cfg: ckpt.CheckpointingConfig = getattr(
            self, "checkpoint_config", None) or ckpt.CheckpointingConfig()
        restore_from = restore_from or cfg.restore_from
        path = restore_from or ckpt.find_latest_checkpoint(cfg.checkpoint_dir)
        if path is None:
            return None
        if not os.path.isdir(path):
            if restore_from:
                raise FileNotFoundError(
                    f"checkpoint.restore_from={restore_from!r} does not exist")
            return None
        # Integrity gate: explicit restore_from targets get the same
        # commit-manifest validation as discovered ones (a .tmp staging dir
        # or a truncated pickle fails here, loudly).  Only process 0 pays
        # the deep sha256 re-hash — N hosts re-reading identical bytes off
        # a shared filesystem adds no integrity, just Nx resume-time load;
        # everyone still checks existence + sizes.  The verdict is VOTED so
        # a checksum failure seen only by process 0 aborts every host in
        # lockstep rather than stranding peers in the collective restore.
        from automodel_tpu.utils.dist_utils import all_hosts_ok

        verr = None
        manifest = None
        try:
            manifest = ckpt.verify_manifest(path,
                                            deep=jax.process_index() == 0)
        except ckpt.CheckpointIntegrityError as e:
            verr = e
        if not all_hosts_ok(verr is None, "ckpt:verified"):
            if verr is not None:
                raise verr
            raise ckpt.CheckpointIntegrityError(
                f"checkpoint {path} failed integrity verification on a "
                "peer host")

        # Peer-RAM fast restore (checkpoint/replication.py): when a
        # neighbor slice's in-memory replica matches this checkpoint's
        # step, the params/opt payload is fetched digest-verified from RAM
        # and the storage read is skipped.  Any miss/corruption falls back
        # to storage per shard set — restore correctness never depends on
        # replication.  ``restore_source`` + the ckpt_restore_* timers
        # record which path ran (bench/goodput surface).
        t_restore0 = time.perf_counter()
        object.__setattr__(self, "_restore_source", "storage")
        peer = self._try_peer_restore(manifest, cfg, path)

        if getattr(self, "params", None) is not None:
            if getattr(self, "peft_config", None) is not None:
                from automodel_tpu.peft.lora import load_adapters

                self.params = load_adapters(
                    self.model, self.params, os.path.join(path, "model"),
                    shardings=getattr(self, "param_sharding", None))
            elif peer is not None:
                self.params = self._place_restored(
                    peer["params"], getattr(self, "param_sharding", None))
            else:
                self.params = ckpt.load_model(
                    self.model, os.path.join(path, "model"), cfg,
                    shardings=getattr(self, "param_sharding", None))
        if getattr(self, "opt_state", None) is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=getattr(x, "sharding", None)),
                self.opt_state)
            if peer is not None:
                self.opt_state = self._place_restored(peer["opt"], abstract)
                # the LR scheduler stateful is tiny and storage-read even
                # on the peer path (replicas carry only the array payload)
                sched = getattr(self, "lr_scheduler", None)
                if sched is not None and ckpt.has_stateful(
                        os.path.join(path, "optim"), "lr_scheduler"):
                    ckpt.load_stateful(os.path.join(path, "optim"),
                                       "lr_scheduler", sched, cfg)
            else:
                self.opt_state = ckpt.load_optimizer(
                    os.path.join(path, "optim"), abstract,
                    scheduler=getattr(self, "lr_scheduler", None),
                    config=cfg)
        if peer is not None:
            object.__setattr__(self, "_restore_source", "peer_ram")
        timers = getattr(self, "timers", None)
        if timers is not None:
            timers(f"ckpt_restore_{self._restore_source}").add(
                time.perf_counter() - t_restore0)
        events = getattr(self, "_restore_events", None)
        if events is None:
            events = []
            object.__setattr__(self, "_restore_events", events)
        events.append((self._restore_source,
                       time.perf_counter() - t_restore0))
        for key, obj in self._state_tracked.items():
            if key in ("lr_scheduler",) or isinstance(obj, ConfigNode):
                continue
            if ckpt.has_stateful(path, key):
                ckpt.load_stateful(path, key, obj, cfg)
        # retention GC must never delete the checkpoint we resumed from
        # (it is the only committed state this run can fall back to)
        self._resumed_from = os.path.abspath(path)
        logger.info("Restored checkpoint from %s (restore_source=%s)",
                    path, getattr(self, "_restore_source", "storage"))
        return path

    def _try_peer_restore(self, manifest, cfg,
                          path: str) -> Optional[Dict[str, Any]]:
        """The peer-RAM attempt of a restore: ``{"params": ..., "opt":
        ...}`` numpy trees for the manifest's step, or None when the
        storage path must run (no matching replica, PEFT adapters,
        multi-host store locality, any verification failure).  Never
        raises — replication is a latency layer, not a correctness
        dependency."""
        if (manifest is None or not getattr(cfg, "replicate_to_peers", True)
                or getattr(self, "peft_config", None) is not None
                or getattr(self, "params", None) is None):
            return None
        if jax.process_count() > 1:
            # replica stores are per-process; a peer's RAM is not
            # addressable from here (no bulk transport in this container —
            # see checkpoint/replication.py scope note)
            return None
        try:
            from automodel_tpu.checkpoint import replication

            abstract = {
                "params": jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.params),
                "opt": (None if getattr(self, "opt_state", None) is None
                        else jax.tree.map(
                            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            self.opt_state)),
            }
            return replication.restore_from_peers(
                step=manifest["step"], abstract=abstract, ckpt_path=path)
        except Exception:
            logger.warning(
                "peer-RAM restore attempt failed; falling back to the "
                "storage path", exc_info=True)
            return None

    @staticmethod
    def _place_restored(np_tree: Any, spec_tree: Any) -> Any:
        """Place a peer-restored host tree onto devices.  ``spec_tree`` is
        a matching tree of shardings OR of ``ShapeDtypeStruct``s whose
        ``.sharding`` may be set (None -> default placement)."""
        if spec_tree is None:
            return jax.tree.map(jax.device_put, np_tree)

        def place(leaf, spec):
            sh = getattr(spec, "sharding", spec)
            return (jax.device_put(leaf, sh) if sh is not None
                    else jax.device_put(leaf))

        return jax.tree.map(place, np_tree, spec_tree)
