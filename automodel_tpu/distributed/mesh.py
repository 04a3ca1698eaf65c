"""Device mesh construction: the TPU-native replacement for DeviceMesh/FSDP2.

Where the reference builds a 4-D ``torch.distributed`` DeviceMesh and flattens
submeshes (``nemo_automodel/components/distributed/fsdp2.py:117-221``), the TPU
design is a single ``jax.sharding.Mesh`` with axes
``('dcn_dp', 'pp', 'dp_replicate', 'dp_shard', 'cp', 'tp')`` (``pp`` is the
reserved size-1 pipeline seam — see the design note below).  "Flattened"
submeshes are not separate objects in JAX — a PartitionSpec may name a *tuple*
of axes, so the reference's ``dp``/``dp_shard_cp``/``dp_cp`` flattened views
become the axis tuples returned by :data:`DP_AXES`, :data:`FSDP_AXES`,
:data:`LOSS_AXES`.

Multi-slice (``dcn_dp``): the OUTERMOST axis is hierarchical data
parallelism across TPU slices.  Parameters are replicated across it (no
param spec ever names it), so the only cross-slice traffic is the per-step
gradient all-reduce — one small collective over DCN — while the dense FSDP
all-gathers / reduce-scatters and TP/CP collectives stay on the inner ICI
axes.  On a real pool each ``dcn_dp`` block is one slice (devices grouped
by ``slice_index``); on CPU/dryrun the device list is partitioned into
``dcn_dp`` contiguous EMULATED slices so elastic drills run on the virtual
8-device mesh.  HSDP guidance (scaling-book): replicate-like axes are
outermost so they land on DCN between slices; shard/cp/tp axes ride ICI.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

# Canonical axis names, outermost (DCN) to innermost (ICI).
#
# ``pp`` is the pipeline-parallel axis (the seam the seed reserved; real
# since the 1F1B schedule landed — ``training/train_step.py`` +
# ``training/pipeline.py``).  The design, exactly as the seam documented:
#
# * The layer stack is a ``[L, ...]`` pytree scanned by one body — stage
#   splitting shards the LEADING layer dim over ``pp`` (``shardings.
#   default_rules(pipeline_parallel=True)``: ``"layers" -> (pp,)``), so
#   each stage owns a contiguous ``L/pp`` slab and the per-layer scan
#   becomes each stage's local scan.  Checkpoints keep the global
#   ``[L, ...]`` shape, so restores reshard across pp layouts like any
#   other mesh change.
# * Schedule: the microbatch loop in the pipelined train step; stage
#   compute is vmapped over the stage dim (``spmd_axis_name="pp"`` keeps
#   FSDP/TP/SP activation rules applying unchanged inside a stage) and
#   boundary activations (fwd) / activation-grads (bwd) move between
#   neighbor stages via ``jax.lax.ppermute`` under ``shard_map``.
# * Placement: ``pp`` sits OUTERMOST below ``dcn_dp`` (above the
#   replicate axis) — stage boundaries are point-to-point transfers, the
#   only traffic pattern that tolerates DCN latency; dense collectives
#   stay on the inner ICI axes.
# * Batches never shard over ``pp`` (every stage sees the full microbatch
#   stream); only layer-stacked parameters and the schedule's boundary
#   buffers name it.
AXIS_DCN_DP = "dcn_dp"
AXIS_PP = "pp"
AXIS_DP_REPLICATE = "dp_replicate"
AXIS_DP_SHARD = "dp_shard"
AXIS_CP = "cp"
AXIS_TP = "tp"
MESH_AXES: Tuple[str, ...] = (AXIS_DCN_DP, AXIS_PP, AXIS_DP_REPLICATE,
                              AXIS_DP_SHARD, AXIS_CP, AXIS_TP)

# Flattened views (reference fsdp2.py:181-221), extended with the cross-slice
# dcn_dp axis (which behaves exactly like an extra replicate axis):
#   dp          = dcn_dp x dp_replicate x dp_shard -> data/batch sharding
#   dp_shard_cp = dp_shard x cp                    -> parameter (FSDP) sharding
#   dp_cp       = dcn_dp x dp_replicate x dp_shard x cp
#                                                  -> loss / token reduction
DP_AXES: Tuple[str, ...] = (AXIS_DCN_DP, AXIS_DP_REPLICATE, AXIS_DP_SHARD)
FSDP_AXES: Tuple[str, ...] = (AXIS_DP_SHARD, AXIS_CP)
LOSS_AXES: Tuple[str, ...] = (AXIS_DCN_DP, AXIS_DP_REPLICATE, AXIS_DP_SHARD,
                              AXIS_CP)
BATCH_AXES: Tuple[str, ...] = (AXIS_DCN_DP, AXIS_DP_REPLICATE, AXIS_DP_SHARD)


@dataclasses.dataclass
class MeshConfig:
    """Sizing knobs, matching the reference ``FSDP2Manager`` constructor surface
    (``distributed/fsdp2.py:36-116``): any size may be None to be inferred."""

    dp_size: Optional[int] = None
    dp_replicate_size: int = 1
    dcn_dp_size: int = 1      # slices over DCN (hierarchical DP, outermost)
    tp_size: int = 1
    cp_size: int = 1
    pp_size: int = 1          # pipeline stages (training/pipeline.py)
    sequence_parallel: bool = False
    # Sequence layout over cp: "contiguous" | "zigzag" | None (None resolves
    # to zigzag when cp_size > 1 — the causal load-balanced default).
    cp_layout: Optional[str] = None


class MeshManager:
    """Builds and owns the global :class:`jax.sharding.Mesh`.

    YAML-instantiable (``distributed._target_``), mirroring ``FSDP2Manager``:

        distributed:
          _target_: automodel_tpu.distributed.mesh.MeshManager
          dp_size: none
          dp_replicate_size: 1
          tp_size: 1
          cp_size: 1
    """

    def __init__(
        self,
        dp_size: Optional[int] = None,
        dp_replicate_size: int = 1,
        dcn_dp_size: int = 1,
        tp_size: int = 1,
        cp_size: int = 1,
        pp_size: int = 1,
        sequence_parallel: bool = False,
        expert_parallel: bool = False,
        cp_layout: Optional[str] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        allow_split_physical_axes: bool = True,
        strict: Optional[bool] = None,
        **_unused,
    ):
        # Unknown kwargs are tolerated only for reference-YAML compatibility
        # (FSDP2Manager carries torch-only knobs).  They must never be
        # SILENT: a ``dcn_dp_size`` misspelling that quietly builds a
        # single-slice mesh is exactly the failure mode elastic recovery
        # cannot detect.  Default: warn; under strict config (``strict=True``
        # or AUTOMODEL_STRICT_CONFIG=1): raise.
        if _unused:
            code = type(self).__init__.__code__
            known = [k for k in code.co_varnames[1:code.co_argcount
                                                 + code.co_kwonlyargcount]]
            msg = (f"MeshManager: unknown config key(s) {sorted(_unused)} "
                   f"ignored (known keys: {sorted(known)})")
            if strict is None:
                strict = os.environ.get(
                    "AUTOMODEL_STRICT_CONFIG", "0") not in ("0", "", "false")
            if strict:
                raise TypeError(msg)
            logger.warning(msg)
        self.sequence_parallel = bool(sequence_parallel)
        # MoE expert placement: experts sharded over the tp axis (EP) vs
        # TP inside each expert — see ``shardings.default_rules``.
        self.expert_parallel = bool(expert_parallel)
        # Sequence layout over cp ("contiguous" | "zigzag"): resolved here so
        # a YAML typo fails at mesh construction with the valid enum listed,
        # not deep inside a traced attention call.
        from automodel_tpu.ops.zigzag import (
            normalize_cp_layout,
            resolve_cp_layout,
        )

        self.cp_layout = resolve_cp_layout(
            normalize_cp_layout(cp_layout), _none_to(cp_size, 1))
        devices = list(devices if devices is not None else jax.devices())
        world = len(devices)

        tp_size = _none_to(tp_size, 1)
        cp_size = _none_to(cp_size, 1)
        pp_size = _none_to(pp_size, 1)
        dp_replicate_size = _none_to(dp_replicate_size, 1)
        dcn_dp_size = _none_to(dcn_dp_size, 1)
        dp_size = _none_to(dp_size, None)
        if pp_size < 1:
            raise ValueError(f"pp_size must be >= 1, got {pp_size}")
        if dcn_dp_size < 1 or world % dcn_dp_size:
            raise ValueError(
                f"device count {world} not divisible into "
                f"dcn_dp_size={dcn_dp_size} slices")
        if dp_size is None:
            denom = tp_size * cp_size * pp_size
            if world % denom:
                raise ValueError(
                    f"world size {world} not divisible by tp*cp*pp={denom}"
                )
            dp_size = world // denom
        # dp_size is the TOTAL data-parallel extent: dcn_dp (across slices)
        # x dp_replicate x dp_shard (within a slice).
        if dp_size % (dcn_dp_size * dp_replicate_size):
            raise ValueError(
                f"dp_size {dp_size} not divisible by dcn_dp_size*"
                f"dp_replicate_size {dcn_dp_size * dp_replicate_size}"
            )
        dp_shard = dp_size // (dcn_dp_size * dp_replicate_size)
        total = (dcn_dp_size * pp_size * dp_replicate_size * dp_shard
                 * cp_size * tp_size)
        if total != world:
            raise ValueError(
                f"mesh {dcn_dp_size}x{pp_size}x{dp_replicate_size}x"
                f"{dp_shard}x{cp_size}x{tp_size}={total} != device count "
                f"{world}"
            )

        # One entry per MESH_AXES name: (dcn_dp, pp, dp_replicate, dp_shard,
        # cp, tp) — pp sits outermost below dcn_dp (the documented stage
        # placement: boundary transfers are point-to-point, so they get the
        # outermost ICI seam while dense collectives stay inner).
        self.shape: Tuple[int, int, int, int, int, int] = (
            dcn_dp_size,
            pp_size,
            dp_replicate_size,
            dp_shard,
            cp_size,
            tp_size,
        )
        # Device placement: the dcn_dp axis must map to SLICE boundaries —
        # slice i owns dev_array[i], so every dense (ICI) collective stays
        # within one slice and only the dcn_dp grad all-reduce crosses DCN.
        self._slice_devices: List[List[jax.Device]] = _partition_into_slices(
            devices, dcn_dp_size)
        inner_shape = self.shape[1:]
        slabs = []
        from jax.experimental import mesh_utils

        for slice_devs in self._slice_devices:
            # topology-aware device order; a shape the physical topology
            # cannot host raises here (JAX names the remedy,
            # ``allow_split_physical_axes``) instead of silently running on
            # a topology-blind reshape
            slabs.append(mesh_utils.create_device_mesh(
                inner_shape,
                devices=slice_devs,
                allow_split_physical_axes=allow_split_physical_axes,
            ))
        dev_array = np.stack(slabs, axis=0)
        self.mesh_shape: Tuple[int, ...] = self.shape
        self.mesh = Mesh(dev_array.reshape(self.mesh_shape), MESH_AXES)

    # -- reference-parity size accessors ----------------------------------
    @property
    def world_size(self) -> int:
        return int(np.prod(self.shape))

    @property
    def dcn_dp_size(self) -> int:
        return self.shape[0]

    @property
    def pp_size(self) -> int:
        return self.shape[1]

    @property
    def dp_replicate_size(self) -> int:
        return self.shape[2]

    @property
    def dp_shard_size(self) -> int:
        return self.shape[3]

    @property
    def cp_size(self) -> int:
        return self.shape[4]

    @property
    def tp_size(self) -> int:
        return self.shape[5]

    @property
    def dp_size(self) -> int:
        """TOTAL data-parallel extent: dcn_dp x dp_replicate x dp_shard."""
        return self.shape[0] * self.shape[2] * self.shape[3]

    @property
    def loss_reduce_size(self) -> int:
        """Size of the dp_cp group used for global token-count normalization."""
        return self.dp_size * self.cp_size

    # -- multi-slice topology ----------------------------------------------
    def slice_devices(self, slice_id: int) -> List[jax.Device]:
        """Devices owned by one ``dcn_dp`` slice (emulated or physical)."""
        return list(self._slice_devices[slice_id])

    def slice_processes(self, slice_id: int) -> Tuple[int, ...]:
        """Host process indices whose devices belong to ``slice_id`` — the
        mapping the elastic detector uses to blame a whole slice for one
        host's missed heartbeat."""
        return tuple(sorted({d.process_index
                             for d in self._slice_devices[slice_id]}))

    @property
    def retired_slices(self) -> dict:
        """``{retired_slice_token: (devices...)}`` — the slices a shrink
        removed from this mesh lineage, remembered so a healed slice can be
        re-admitted (``grow_slices``).  Tokens are the slice's id at the
        time it was lost (bumped past live ids on collision, since
        survivors renumber)."""
        return {k: tuple(v)
                for k, v in getattr(self, "_retired_slices", {}).items()}

    def retired_slice_processes(self, token: int) -> Tuple[int, ...]:
        """Host process indices of a RETIRED slice's devices (the set the
        elastic detector requires to fully re-announce before a grow-back
        probation streak counts)."""
        devs = getattr(self, "_retired_slices", {})[token]
        return tuple(sorted({d.process_index for d in devs}))

    def shrink_slices(self, lost_slice: int) -> "MeshManager":
        """The elastic-recovery mesh: same per-slice geometry, ``dcn_dp-1``
        slices, built over the SURVIVING slices' devices only.  Raises when
        there is no slice to lose (``dcn_dp == 1`` is the smallest mesh a
        run can shrink to).  The lost slice's devices are REMEMBERED on the
        shrunk manager (:attr:`retired_slices`) so a later
        :meth:`grow_slices` can rebuild the full pool when the slice
        heals."""
        n = self.dcn_dp_size
        if not 0 <= lost_slice < n:
            raise ValueError(
                f"lost_slice {lost_slice} out of range for dcn_dp={n}")
        if n <= 1:
            raise ValueError(
                "cannot shrink a single-slice mesh: dcn_dp is already 1 "
                "(slice loss at dcn_dp=1 is a full-pool loss — resume via "
                "relaunch, not elastic rebuild)")
        survivors: List[jax.Device] = []
        for s in range(n):
            if s != lost_slice:
                survivors.extend(self._slice_devices[s])
        mm = MeshManager(
            dcn_dp_size=n - 1,
            dp_size=(n - 1) * self.dp_replicate_size * self.dp_shard_size,
            dp_replicate_size=self.dp_replicate_size,
            tp_size=self.tp_size,
            cp_size=self.cp_size,
            pp_size=self.pp_size,
            sequence_parallel=self.sequence_parallel,
            expert_parallel=self.expert_parallel,
            cp_layout=self.cp_layout,
            devices=survivors,
        )
        retired = dict(getattr(self, "_retired_slices", {}))
        token = lost_slice
        while token in retired:  # stacked losses can reuse renumbered ids
            token += n
        retired[token] = list(self._slice_devices[lost_slice])
        mm._retired_slices = retired
        return mm

    def grow_slices(self, returned_slice: Optional[int] = None,
                    devices: Optional[Sequence[jax.Device]] = None
                    ) -> "MeshManager":
        """The grow-back mesh: inverse of :meth:`shrink_slices` — rebuild
        at ``dcn_dp + 1`` with the returned slice's devices appended as the
        LAST slice (survivors keep their ids, matching the loss-side
        renumbering convention).

        ``returned_slice`` names a retired-slice token
        (:attr:`retired_slices`; default: the most recently retired one);
        an explicit ``devices`` list admits a slice this lineage never saw
        (a replacement slice standing in for the dead one) — it must match
        the per-slice device count.  The grown manager forgets the admitted
        token but keeps any OTHER retired slices (stacked losses heal one
        at a time, each at its own checkpoint boundary)."""
        retired = dict(getattr(self, "_retired_slices", {}))
        if devices is None:
            if not retired:
                raise ValueError(
                    "grow_slices: no retired slice to re-admit (this mesh "
                    "lineage never shrank) — pass the returning slice's "
                    "devices explicitly")
            if returned_slice is None:
                # most recently retired = LAST INSERTED (dict order);
                # token values are not ordered by retirement time
                returned_slice = next(reversed(retired))
            if returned_slice not in retired:
                raise ValueError(
                    f"grow_slices: {returned_slice} is not a retired slice "
                    f"(retired: {sorted(retired)})")
            devices = retired.pop(returned_slice)
        else:
            devices = list(devices)
            if returned_slice is not None:
                retired.pop(returned_slice, None)
        per_slice = len(self._slice_devices[0])
        if len(devices) != per_slice:
            raise ValueError(
                f"grow_slices: returning slice has {len(devices)} devices, "
                f"the pool's per-slice geometry needs {per_slice}")
        n = self.dcn_dp_size
        all_devices: List[jax.Device] = []
        for s in range(n):
            all_devices.extend(self._slice_devices[s])
        all_devices.extend(devices)
        mm = MeshManager(
            dcn_dp_size=n + 1,
            dp_size=(n + 1) * self.dp_replicate_size * self.dp_shard_size,
            dp_replicate_size=self.dp_replicate_size,
            tp_size=self.tp_size,
            cp_size=self.cp_size,
            pp_size=self.pp_size,
            sequence_parallel=self.sequence_parallel,
            expert_parallel=self.expert_parallel,
            cp_layout=self.cp_layout,
            devices=all_devices,
        )
        mm._retired_slices = retired
        return mm

    def __enter__(self):
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)

    def __repr__(self) -> str:
        return (f"MeshManager(shape="
                f"{dict(zip(MESH_AXES, self.mesh_shape))})")


def _none_to(v, default):
    if v is None or (isinstance(v, str) and v.lower() in ("none", "null", "")):
        return default
    return int(v)


def _partition_into_slices(devices: Sequence[jax.Device],
                           n_slices: int) -> List[List[jax.Device]]:
    """Group devices into ``n_slices`` dcn_dp blocks.

    On a real multi-slice pool every device carries a ``slice_index`` and
    the grouping follows it (a dcn_dp block must be one physical slice so
    its inner collectives ride ICI).  On single-slice hardware and the
    CPU/dryrun platform the device list is partitioned contiguously into
    EMULATED slices — the topology elastic drills shrink."""
    per_slice = len(devices) // n_slices
    by_slice: dict = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", None), []).append(d)
    slice_ids = sorted(by_slice, key=lambda s: (s is None, s))
    if len(slice_ids) == n_slices and all(
            len(by_slice[s]) == per_slice for s in slice_ids):
        return [by_slice[s] for s in slice_ids]
    if len(slice_ids) > 1 and n_slices > 1:
        raise ValueError(
            f"dcn_dp_size={n_slices} does not match the physical slice "
            f"topology {{slice: n_devices}} = "
            f"{ {s: len(v) for s, v in by_slice.items()} }")
    return [list(devices[i * per_slice:(i + 1) * per_slice])
            for i in range(n_slices)]


def build_mesh(cfg=None, **kwargs) -> MeshManager:
    """Convenience builder from a ConfigNode or kwargs.

    Every cfg key is FORWARDED (minus ``_target_``) so MeshManager's
    unknown-kwarg guard sees misspellings — a whitelist here would silently
    drop a ``dcn_dp_size`` typo before the guard could warn/raise."""
    if cfg is not None:
        raw = cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg)
        fields = {k: v for k, v in raw.items() if k != "_target_"}
        fields.update(kwargs)
        kwargs = fields
    return MeshManager(**kwargs)
