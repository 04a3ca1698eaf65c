"""Checkpoint subsystem: Orbax sharded state + HF-safetensors model export.

TPU re-design of the reference's DCP stack
(``nemo_automodel/components/checkpoint/checkpointing.py:49-495`` plus the
~3.3k LoC of vendored ``_backports``): Orbax plays DCP's role for sharded
pytree state (model/optimizer), ``automodel_tpu.models.hf_io`` plays the
``_HuggingFaceStorageWriter/Reader`` + consolidation role (the exported repo
loads in HF ``transformers`` unchanged), and host-side stateful objects
(schedulers, RNG, dataloaders) round-trip via ``state_dict()`` pickles.

Checkpoint directory layout (reference ``base_recipe.py:126-180``):
    <ckpt_dir>/epoch_{e}_step_{s}/
        model/            consolidated HF safetensors or Orbax tree
        optim/            Orbax optimizer + LR-scheduler state
        <key>.pt          pickled state_dict of each tracked stateful
        config.yaml       the run config
        manifest.json     commit record: written LAST, by process 0 only

Crash-safe commit protocol (DCP/Orbax ``.tmp``+finalize semantics, which
the reference inherits from torch.distributed.checkpoint): every writer
targets ``epoch_{e}_step_{s}.tmp``; after all collective saves finish and a
cross-process barrier passes, process 0 writes ``manifest.json`` (step,
file list with sizes + sha256 for host-side files) inside the staging dir
and atomically renames it to the final name.  A checkpoint directory is
therefore visible under its final name iff it is complete — a kill at ANY
point mid-save leaves only a ``.tmp`` dir that discovery ignores and the
next save's staging prep clears.
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
import json
import logging
import os
import pickle
import random
import re
import shutil
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from automodel_tpu.utils.fault_injection import fault_point

logger = logging.getLogger(__name__)

MANIFEST_NAME = "manifest.json"
MANIFEST_VERSION = 1
STAGING_SUFFIX = ".tmp"
_GC_SUFFIX = ".gc.tmp"
# Host-side files small enough to checksum on every save; the multi-GB
# safetensors/Orbax payloads get size-only entries (hashing a 70B export
# per save would dwarf the save itself).
_CHECKSUM_SUFFIXES = (".pt", ".yaml", ".yml", ".json")


class CheckpointIntegrityError(RuntimeError):
    """A checkpoint directory is uncommitted or fails manifest validation."""


class CheckpointSaveError(RuntimeError):
    """A save was aborted before commit (this host's writes failed, or a
    peer voted failure in the pre-commit consensus); only staging was
    touched, the previous committed checkpoint is unaffected."""


class CheckpointFormat(str, enum.Enum):
    SAFETENSORS = "safetensors"
    ORBAX = "orbax"


@dataclasses.dataclass
class CheckpointingConfig:
    """Reference parity: ``checkpoint/checkpointing.py:49-70``."""

    enabled: bool = True
    checkpoint_dir: str = "checkpoints/"
    model_save_format: str = "safetensors"
    save_consolidated: bool = True
    is_peft: bool = False
    model_cache_dir: Optional[str] = None
    model_repo_id: Optional[str] = None
    # Parallel per-process shard writes for consolidated exports; set false
    # when the checkpoint dir is NOT a shared filesystem (host 0 writes all).
    distribute_writes: bool = True
    # Explicit resume target (YAML/CLI ``checkpoint.restore_from``); None
    # means "discover the latest committed checkpoint in checkpoint_dir".
    restore_from: Optional[str] = None
    # Retention: after each successful commit keep only the newest
    # ``keep_last_k`` committed checkpoints (None/0 = keep everything),
    # pinning any whose step is a multiple of ``keep_every_n_steps`` and
    # never the checkpoint the run resumed from.
    keep_last_k: Optional[int] = None
    keep_every_n_steps: Optional[int] = None
    # Transient-I/O retry for host-side filesystem ops (stateful pickles,
    # manifest, aux copies): ``io_retries`` extra attempts with exponential
    # backoff starting at ``io_retry_backoff`` seconds (plus jitter).
    io_retries: int = 3
    io_retry_backoff: float = 0.1
    # Asynchronous saves (docs/guides/checkpointing.md "Asynchronous
    # saves"): at a save boundary the training loop only SNAPSHOTS device
    # state to host buffers, then a single background committer thread runs
    # the full crash-safe protocol (stage -> write -> vote -> manifest ->
    # rename -> GC) while training resumes.  ``false`` restores the fully
    # inline save.  Bool-validated at config load (``config/loader.py``)
    # like ``distributed.cp_layout``; null means "use the default".
    async_save: bool = True
    # Peer-to-peer in-memory replication (docs/guides/checkpointing.md
    # "Peer replication"): after each ASYNC commit the committer pushes the
    # host snapshot to a ring-neighbor slice's RAM-resident replica store
    # so a later restore can skip storage (``checkpoint/replication.py``).
    # One replica generation resident (bounded memory); no effect on
    # inline saves or single-slice pools.  ``false`` disables the push —
    # restores then always read storage.
    replicate_to_peers: bool = True

    def __post_init__(self):
        if isinstance(self.model_save_format, CheckpointFormat):
            self.model_save_format = self.model_save_format.value
        assert self.model_save_format in ("safetensors", "orbax", "torch_save"), (
            f"unknown model_save_format {self.model_save_format!r}")
        if self.model_save_format == "torch_save":  # reference alias
            self.model_save_format = "orbax"
        if self.keep_last_k is not None and int(self.keep_last_k) < 0:
            raise ValueError(f"keep_last_k must be >= 0, got {self.keep_last_k}")
        if (self.keep_every_n_steps is not None
                and int(self.keep_every_n_steps) < 1):
            raise ValueError(
                f"keep_every_n_steps must be >= 1, got {self.keep_every_n_steps}")
        if int(self.io_retries) < 0:
            raise ValueError(f"io_retries must be >= 0, got {self.io_retries}")
        from automodel_tpu.config.loader import normalize_null_spelling

        # null and its YAML string spellings ("none"/"null"/"") mean "use
        # the default" — same delegation as cp_layout/moe.dispatch, so the
        # loader's validation can never bless a value this rejects
        if normalize_null_spelling(self.async_save) is None:
            self.async_save = True
        if not isinstance(self.async_save, bool):
            raise ValueError(
                f"checkpoint.async_save must be a bool (or null for the "
                f"default), got {self.async_save!r}")
        if normalize_null_spelling(self.replicate_to_peers) is None:
            self.replicate_to_peers = True
        if not isinstance(self.replicate_to_peers, bool):
            raise ValueError(
                f"checkpoint.replicate_to_peers must be a bool (or null "
                f"for the default), got {self.replicate_to_peers!r}")


def build_checkpoint_config(cfg=None, **kwargs) -> CheckpointingConfig:
    fields = {f.name for f in dataclasses.fields(CheckpointingConfig)}
    if cfg is not None:
        kwargs = {**{k: v for k, v in cfg.to_dict().items() if k in fields},
                  **kwargs}
    return CheckpointingConfig(**{k: v for k, v in kwargs.items() if k in fields})


# ---------------------------------------------------------------------------
# Transient-I/O retry
# ---------------------------------------------------------------------------
def retry_io(fn: Callable, *args, retries: int = 3, backoff: float = 0.1,
             retry_on: Tuple[type, ...] = (OSError,), desc: str = "",
             **kwargs):
    """Call ``fn(*args, **kwargs)``, retrying transient I/O failures.

    ``retries`` extra attempts after the first, sleeping
    ``backoff * 2**attempt`` seconds plus up to 25% jitter between tries
    (the jitter decorrelates hosts hammering a shared filesystem that just
    hiccuped).  Only ``retry_on`` exceptions are retried — anything else
    (including :class:`InjectedFault`) propagates immediately, and the last
    failure re-raises once attempts are exhausted.
    """
    attempts = int(retries) + 1
    for attempt in range(attempts):
        try:
            return fn(*args, **kwargs)
        except retry_on as e:
            if attempt == attempts - 1:
                raise
            delay = backoff * (2 ** attempt) * (1.0 + 0.25 * random.random())
            logger.warning(
                "transient I/O failure%s (attempt %d/%d, retrying in %.2fs): %s",
                f" in {desc}" if desc else "", attempt + 1, attempts, delay, e)
            time.sleep(delay)


# ---------------------------------------------------------------------------
# Host snapshot (async saves)
# ---------------------------------------------------------------------------
def _local_shard_coverage(x: jax.Array) -> int:
    """Number of DISTINCT global-array elements this host's addressable
    shards cover.  A sharding partitions the array among distinct shard
    indices (replicas share an index), so coverage == ``x.size`` iff the
    host can materialize the full array from local data alone."""
    seen = set()
    total = 0
    for shard in x.addressable_shards:
        key = tuple((s.start, s.stop, s.step) for s in shard.index)
        if key in seen:
            continue
        seen.add(key)
        total += int(np.prod(shard.data.shape))
    return total


def snapshot_is_host_complete(tree: Any) -> bool:
    """True iff :func:`snapshot_to_host` can materialize every leaf from
    THIS host's shards alone — always single-process; on multihost, when
    each leaf is fully addressable, replicated, or replica-complete on the
    host (HSDP with the shard axis inside a host).  False means a snapshot
    would need a cross-host gather of the full tree onto every host — at
    large scale that is an OOM, so ``BaseRecipe.save_checkpoint`` checks
    this once and falls back to the inline save instead."""
    if jax.process_count() == 1:
        return True
    for x in jax.tree.leaves(tree):
        if (isinstance(x, jax.Array) and not x.is_fully_addressable
                and _local_shard_coverage(x) < x.size):
            return False
    return True


def snapshot_to_host(tree: Any) -> Any:
    """Blocking device->host copy of a pytree — the only part of an async
    save the training loop waits for.

    Fully-addressable leaves ride ONE batched ``jax.device_get`` of the
    whole tree (parallel transfers; per-leaf fetches serialize a round trip
    per tensor, which makes the inline save path latency-bound).  Non-addressable leaves whose LOCAL shards
    cover the full array (replicated, or HSDP replica-complete on this
    host) are assembled from those shards — no cross-host traffic at all.
    A leaf genuinely sharded ACROSS hosts falls back to
    ``process_allgather`` — full-tree-per-host memory, which is why
    recipes probe :func:`snapshot_is_host_complete` first and keep such
    saves inline.  Everything here runs on the training thread, at the
    save boundary every host reaches together — the background committer
    never issues a device collective (a background device op could
    interleave with training-loop collectives in a different order on
    different hosts and deadlock the mesh).

    The copy matters even though ``jax.Array`` is immutable: the train step
    donates params/opt_state buffers, so a reference held across the next
    dispatch would be a deleted array.
    """
    gathered = {}
    if jax.process_count() > 1:
        leaves, _ = jax.tree.flatten(tree)
        for i, x in enumerate(leaves):
            if not isinstance(x, jax.Array) or x.is_fully_addressable:
                continue
            if _local_shard_coverage(x) == x.size:
                out = np.empty(x.shape, x.dtype)
                for shard in x.addressable_shards:
                    out[shard.index] = np.asarray(shard.data)
                gathered[i] = out
            else:
                from jax.experimental import multihost_utils

                gathered[i] = np.asarray(
                    multihost_utils.process_allgather(x, tiled=True))
    if gathered:
        leaves, treedef = jax.tree.flatten(tree)
        leaves = [gathered.get(i, x) for i, x in enumerate(leaves)]
        tree = jax.tree.unflatten(treedef, leaves)
    host = jax.device_get(tree)
    return jax.tree.map(
        lambda x: np.asarray(x) if isinstance(x, (jax.Array, np.generic))
        else x, host)


# ---------------------------------------------------------------------------
# Integrity manifest — written last, the commit marker
# ---------------------------------------------------------------------------
# Hashes of host-side files computed WHILE writing them (``save_stateful``
# pickles the bytes anyway): ``build_manifest`` reuses a hint instead of
# re-reading the file it just wrote — abspath -> (size, sha256), popped on
# use.  Size is double-checked so a file modified between write and
# manifest (or a stale hint) falls back to re-hashing.
_HASH_HINTS: Dict[str, Tuple[int, str]] = {}
_hash_hints_lock = threading.Lock()


def record_file_hash(path: str, size: int, sha256: str) -> None:
    with _hash_hints_lock:
        _HASH_HINTS[os.path.abspath(path)] = (int(size), sha256)


def _pop_file_hash(path: str, size: int) -> Optional[str]:
    with _hash_hints_lock:
        hint = _HASH_HINTS.pop(os.path.abspath(path), None)
    if hint is not None and hint[0] == size:
        return hint[1]
    return None


def _purge_file_hashes(prefix: str) -> None:
    """Drop hints under a staging dir being cleared (aborted save leftovers)."""
    prefix = os.path.abspath(prefix) + os.sep
    with _hash_hints_lock:
        for key in [k for k in _HASH_HINTS if k.startswith(prefix)]:
            del _HASH_HINTS[key]


def _file_sha256(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def build_manifest(ckpt_path: str, *, epoch: int, step: int,
                   config: Optional[CheckpointingConfig] = None) -> Dict[str, Any]:
    """Walk a (staged) checkpoint dir into a manifest dict: every file with
    its size, plus sha256 for the host-side pickles/configs (suffixes in
    ``_CHECKSUM_SUFFIXES``; the sharded tensor payloads are size-only)."""
    files: List[Dict[str, Any]] = []
    for root, _dirs, names in os.walk(ckpt_path):
        for name in sorted(names):
            if root == ckpt_path and name.startswith(MANIFEST_NAME):
                continue
            full = os.path.join(root, name)
            rel = os.path.relpath(full, ckpt_path).replace(os.sep, "/")
            entry: Dict[str, Any] = {"path": rel, "size": os.path.getsize(full)}
            if name.endswith(_CHECKSUM_SUFFIXES):
                entry["sha256"] = (_pop_file_hash(full, entry["size"])
                                   or _file_sha256(full))
            files.append(entry)
    from automodel_tpu import __version__ as framework_version

    return {
        "manifest_version": MANIFEST_VERSION,
        "framework": "automodel_tpu",
        "framework_version": framework_version,
        "jax_version": jax.__version__,
        "format": (config.model_save_format if config is not None
                   else CheckpointingConfig.model_save_format),
        "epoch": int(epoch),
        "step": int(step),
        "files": sorted(files, key=lambda e: e["path"]),
    }


def write_manifest(ckpt_path: str, *, epoch: int, step: int,
                   config: Optional[CheckpointingConfig] = None) -> Dict[str, Any]:
    """Build and atomically write ``manifest.json`` inside ``ckpt_path``."""
    manifest = build_manifest(ckpt_path, epoch=epoch, step=step, config=config)
    tmp = os.path.join(ckpt_path, MANIFEST_NAME + ".tmp")

    def _write():
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(ckpt_path, MANIFEST_NAME))

    cfg = config or CheckpointingConfig()
    retry_io(_write, retries=cfg.io_retries, backoff=cfg.io_retry_backoff,
             desc=f"manifest for {ckpt_path}")
    return manifest


def read_manifest(ckpt_path: str) -> Optional[Dict[str, Any]]:
    """The parsed manifest, or None for an uncommitted/legacy dir.

    A present-but-unparseable manifest raises
    :class:`CheckpointIntegrityError` naming the checkpoint (bit-rot or a
    partial overwrite must surface as a corrupt checkpoint, not an opaque
    ``JSONDecodeError``)."""
    path = os.path.join(ckpt_path, MANIFEST_NAME)
    if not os.path.isfile(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except ValueError as e:  # json.JSONDecodeError subclasses ValueError
        raise CheckpointIntegrityError(
            f"checkpoint {ckpt_path} is corrupt: {MANIFEST_NAME} is not "
            f"valid JSON ({e})") from e


def is_committed(ckpt_path: str) -> bool:
    """A checkpoint counts as committed iff it sits under its final name
    (not ``.tmp`` staging) and carries a manifest."""
    name = os.path.basename(os.path.normpath(ckpt_path))
    return (os.path.isdir(ckpt_path)
            and not name.endswith((STAGING_SUFFIX, _GC_SUFFIX))
            and os.path.isfile(os.path.join(ckpt_path, MANIFEST_NAME)))


def verify_manifest(ckpt_path: str, *, deep: bool = True) -> Dict[str, Any]:
    """Validate ``ckpt_path`` against its manifest; the manifest on success.

    Checks every listed file exists with its recorded size, and (``deep``)
    re-hashes the checksummed host-side files.  Raises
    :class:`CheckpointIntegrityError` naming the directory and the first
    problem found, so resume failures point at the corrupt artifact instead
    of an opaque unpickle/parse error downstream.
    """
    name = os.path.basename(os.path.normpath(ckpt_path))
    if name.endswith((STAGING_SUFFIX, _GC_SUFFIX)):
        raise CheckpointIntegrityError(
            f"checkpoint {ckpt_path} is an uncommitted staging directory "
            "(interrupted save) — resume from a committed checkpoint")
    manifest = read_manifest(ckpt_path)
    if manifest is None:
        raise CheckpointIntegrityError(
            f"checkpoint {ckpt_path} has no {MANIFEST_NAME}: it was never "
            "committed (interrupted save or pre-manifest legacy dir)")
    for entry in manifest.get("files", ()):
        full = os.path.join(ckpt_path, *entry["path"].split("/"))
        if not os.path.isfile(full):
            raise CheckpointIntegrityError(
                f"checkpoint {ckpt_path} is corrupt: manifest lists "
                f"{entry['path']} but the file is missing")
        size = os.path.getsize(full)
        if size != entry["size"]:
            raise CheckpointIntegrityError(
                f"checkpoint {ckpt_path} is corrupt: {entry['path']} is "
                f"{size} bytes, manifest recorded {entry['size']}")
        if deep and "sha256" in entry and _file_sha256(full) != entry["sha256"]:
            raise CheckpointIntegrityError(
                f"checkpoint {ckpt_path} is corrupt: {entry['path']} fails "
                "its sha256 checksum")
    return manifest


def adopt_legacy_checkpoint(ckpt_path: str) -> Dict[str, Any]:
    """Write a manifest for a pre-protocol checkpoint dir, making it
    resumable again.

    Upgrade path for checkpoints saved before the commit protocol existed:
    discovery (correctly) refuses manifest-less dirs, so an in-place
    upgrade would otherwise orphan them.  Adoption is an EXPLICIT operator
    action (``tools/verify_checkpoint.py --adopt``) — the operator asserts
    the dir is a complete save; this only sanity-checks the name and that
    there is something to adopt, then records the current file inventory.
    """
    name = os.path.basename(os.path.normpath(ckpt_path))
    m = _CKPT_RE.search(name)
    if m is None or name.endswith((STAGING_SUFFIX, _GC_SUFFIX)):
        raise CheckpointIntegrityError(
            f"{ckpt_path} is not adoptable: expected a final "
            "epoch_E_step_S directory name")
    if read_manifest(ckpt_path) is not None:
        return verify_manifest(ckpt_path)  # already committed — just check
    if not os.listdir(ckpt_path):
        raise CheckpointIntegrityError(f"{ckpt_path} is empty, nothing to adopt")
    return write_manifest(ckpt_path, epoch=int(m.group(1)),
                          step=int(m.group(2)))


# ---------------------------------------------------------------------------
# Atomic commit protocol
# ---------------------------------------------------------------------------
def staging_path(final_path: str) -> str:
    return final_path.rstrip("/") + STAGING_SUFFIX


def _sync_fns(coordinator=None):
    """The (all_hosts_ok, barrier) pair for a save: the module-level
    device-collective primitives on the training thread (``None``), or a
    :class:`~automodel_tpu.utils.dist_utils.CollectiveNamespace`'s KV-store
    routed ones when the protocol runs on the async committer thread."""
    if coordinator is not None:
        return coordinator.all_hosts_ok, coordinator.barrier
    from automodel_tpu.utils.dist_utils import all_hosts_ok, barrier

    return all_hosts_ok, barrier


def prepare_staging(final_path: str,
                    config: Optional[CheckpointingConfig] = None,
                    coordinator=None) -> str:
    """COLLECTIVE: (re)create the staging dir for ``final_path``.

    Process 0 clears any leftover from a previously interrupted save —
    stale files must not leak into the new manifest — and recreates it;
    everyone else waits on the vote-barrier so no writer races the cleanup.
    A process-0 I/O failure (retries exhausted) is voted, not raised past
    the sync point, so every host aborts with :class:`CheckpointSaveError`
    in lockstep instead of peers hanging.
    """
    all_hosts_ok, _barrier = _sync_fns(coordinator)

    cfg = config or CheckpointingConfig()
    staging = staging_path(final_path)
    _purge_file_hashes(staging)
    err: Optional[BaseException] = None
    if jax.process_index() == 0:
        try:
            if os.path.isdir(staging):
                retry_io(shutil.rmtree, staging, retries=cfg.io_retries,
                         backoff=cfg.io_retry_backoff,
                         desc=f"clearing stale staging {staging}")
            retry_io(os.makedirs, staging, exist_ok=True,
                     retries=cfg.io_retries, backoff=cfg.io_retry_backoff,
                     desc=f"creating staging {staging}")
        except OSError as e:
            err = e
    if not all_hosts_ok(err is None, "ckpt:staging_ready"):
        raise CheckpointSaveError(
            f"could not prepare staging {staging}") from err
    return staging


def commit_checkpoint(staging: str, final_path: str, *, epoch: int, step: int,
                      config: Optional[CheckpointingConfig] = None,
                      coordinator=None) -> str:
    """COLLECTIVE: finalize a fully-written staging dir.

    The barrier guarantees every process's collective writes (Orbax,
    distributed safetensors shards) have finished before process 0 writes
    the manifest and atomically renames ``.tmp`` -> final.  The closing
    vote keeps non-zero processes from observing (or GC-ing around) a
    half-committed state — and turns a process-0 I/O failure (manifest or
    rename, retries exhausted) into a lockstep
    :class:`CheckpointSaveError` on every host instead of peers hanging at
    a bare barrier.
    """
    all_hosts_ok, barrier = _sync_fns(coordinator)

    cfg = config or CheckpointingConfig()
    barrier("ckpt:all_writes_done")
    err: Optional[BaseException] = None
    husk = None
    if jax.process_index() == 0:
        try:
            write_manifest(staging, epoch=epoch, step=step, config=cfg)
            fault_point("ckpt_pre_rename")
            # Re-save of the same (epoch, step): move the old committed dir
            # aside with a RENAME (not an rmtree) so the only unprotected
            # window is between two metadata-cheap renames — and even a kill
            # inside it leaves the old payload (manifest included) intact in
            # the .gc.tmp husk, recoverable by renaming it back to the final
            # name before relaunching (a later save's GC sweeps husks),
            # rather than destroyed mid-rmtree of a multi-GB directory.
            if os.path.isdir(final_path):
                husk = final_path + _GC_SUFFIX
                if os.path.isdir(husk):
                    retry_io(shutil.rmtree, husk, retries=cfg.io_retries,
                             backoff=cfg.io_retry_backoff, desc=f"husk {husk}")
                retry_io(os.replace, final_path, husk,
                         retries=cfg.io_retries, backoff=cfg.io_retry_backoff,
                         desc=f"setting aside {final_path}")
            retry_io(os.replace, staging, final_path, retries=cfg.io_retries,
                     backoff=cfg.io_retry_backoff,
                     desc=f"committing {final_path}")
            if husk is not None:
                try:  # best-effort: retention GC sweeps .gc.tmp husks anyway
                    retry_io(shutil.rmtree, husk, retries=cfg.io_retries,
                             backoff=cfg.io_retry_backoff, desc=f"husk {husk}")
                except OSError as e:
                    logger.warning(
                        "could not remove replaced checkpoint %s: %s", husk, e)
        except OSError as e:  # injected faults propagate (not OSError)
            err = e
            # If the old committed dir was already set aside but the new
            # rename never landed, roll it back so the step still has a
            # committed checkpoint.
            if husk is not None and not os.path.isdir(final_path):
                try:
                    os.replace(husk, final_path)
                except OSError as rb:
                    logger.warning(
                        "could not roll back %s -> %s: %s", husk,
                        final_path, rb)
    if not all_hosts_ok(err is None, "ckpt:committed"):
        raise CheckpointSaveError(
            f"commit of {final_path} failed on process 0; staging left at "
            f"{staging} for inspection") from err
    return final_path


# ---------------------------------------------------------------------------
# Retention GC
# ---------------------------------------------------------------------------
def list_committed_checkpoints(checkpoint_dir: str) -> List[Tuple[int, int, str]]:
    """Committed checkpoints as ``(epoch, step, path)``, oldest first."""
    if not os.path.isdir(checkpoint_dir):
        return []
    out = []
    for name in sorted(os.listdir(checkpoint_dir)):
        m = _CKPT_RE.search(name)
        if not m:
            continue
        path = os.path.join(checkpoint_dir, name)
        if is_committed(path):
            out.append((int(m.group(1)), int(m.group(2)), path))
    out.sort(key=lambda t: t[:2])
    return out


def gc_checkpoints(checkpoint_dir: str, *, keep_last_k: Optional[int] = None,
                   keep_every_n_steps: Optional[int] = None,
                   protect: Iterable[str] = (),
                   config: Optional[CheckpointingConfig] = None) -> List[str]:
    """Delete superseded committed checkpoints; the deleted paths.

    Keeps the newest ``keep_last_k`` by (epoch, step) — ``None``/0 disables
    GC entirely — plus every checkpoint whose step is a multiple of
    ``keep_every_n_steps`` (milestone pins) and anything in ``protect``
    (the checkpoint the run resumed from).  Deletion renames the victim to
    ``<name>.gc.tmp`` first so a crash mid-rmtree can never leave a
    half-deleted dir that still looks committed; stale ``.gc.tmp`` husks
    and ``.tmp`` staging leftovers older than the newest commit are swept
    on the way.

    Process-0-only by contract (the caller gates); never call it while a
    save is in flight.
    """
    cfg = config or CheckpointingConfig()
    deleted: List[str] = []
    committed = list_committed_checkpoints(checkpoint_dir)
    protected = {os.path.realpath(p) for p in protect if p}

    def _remove(path: str) -> None:
        husk = path + _GC_SUFFIX if not path.endswith(_GC_SUFFIX) else path
        try:
            if not path.endswith(_GC_SUFFIX):
                retry_io(os.replace, path, husk, retries=cfg.io_retries,
                         backoff=cfg.io_retry_backoff, desc=f"GC {path}")
            retry_io(shutil.rmtree, husk, retries=cfg.io_retries,
                     backoff=cfg.io_retry_backoff, desc=f"GC {husk}")
            deleted.append(path)
        except OSError as e:  # GC must never fail a successful save
            logger.warning("checkpoint GC could not remove %s: %s", path, e)

    # stale husks from an interrupted previous GC are always garbage
    if os.path.isdir(checkpoint_dir):
        for name in os.listdir(checkpoint_dir):
            if name.endswith(_GC_SUFFIX):
                _remove(os.path.join(checkpoint_dir, name))
    if committed:
        # staging leftovers superseded by a newer commit: an interrupted
        # save's .tmp is dead weight once any (epoch, step) >= it committed
        newest_key = committed[-1][:2]
        for name in os.listdir(checkpoint_dir):
            if not name.endswith(STAGING_SUFFIX):
                continue
            m = _CKPT_RE.search(name[: -len(STAGING_SUFFIX)])
            if m and (int(m.group(1)), int(m.group(2))) <= newest_key:
                _remove(os.path.join(checkpoint_dir, name))
    if not keep_last_k or keep_last_k < 1:
        return deleted
    victims = committed[:-keep_last_k] if keep_last_k < len(committed) else []
    for epoch, step, path in victims:
        if keep_every_n_steps and step > 0 and step % keep_every_n_steps == 0:
            continue  # milestone pin
        if os.path.realpath(path) in protected:
            continue  # the checkpoint we resumed from stays until outranked
        _remove(path)
    return deleted


# ---------------------------------------------------------------------------
# Orbax helpers
# ---------------------------------------------------------------------------
def _checkpointer(namespace: Optional[str] = None):
    import orbax.checkpoint as ocp

    if namespace is None or jax.process_count() == 1:
        return ocp.StandardCheckpointer()
    # Async-committer path on a multi-process run: Orbax's own sync points
    # default to ``multihost_utils.sync_global_devices`` — a DEVICE
    # collective that must not be issued from a background thread (enqueue
    # order vs the training loop differs per host -> deadlock).  Naming the
    # active process set switches Orbax to its coordination-service barrier
    # (host-side KV RPC), and the key prefix keeps those barriers in the
    # committer's namespace.
    return ocp.StandardCheckpointer(
        multiprocessing_options=ocp.options.MultiprocessingOptions(
            active_processes=set(range(jax.process_count())),
            barrier_sync_key_prefix=namespace))


def save_pytree(path: str, tree: Any,
                namespace: Optional[str] = None) -> None:
    """Sharded pytree save — every process participates (Orbax collective).
    ``namespace``: route Orbax's internal sync through the coordination
    service under that key prefix (background/async saves)."""
    ckptr = _checkpointer(namespace)
    ckptr.save(os.path.abspath(path), tree, force=True)
    ckptr.wait_until_finished()


def restore_pytree(path: str, abstract: Any = None) -> Any:
    """Restore with target structure/shardings from ``abstract`` (a pytree of
    ``jax.ShapeDtypeStruct`` with ``.sharding`` set for sharded placement)."""
    return _checkpointer().restore(os.path.abspath(path), abstract)


def abstract_with_shardings(abstract: Any, shardings: Any) -> Any:
    """Attach NamedShardings to an abstract pytree for placed restore."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)


# ---------------------------------------------------------------------------
# Model save / load (reference checkpointing.py:71-237)
# ---------------------------------------------------------------------------
def save_model(model, params: Any, weights_path: str,
               config: Optional[CheckpointingConfig] = None,
               peft_config: Any = None, coordinator=None) -> None:
    """``params`` may be device arrays (inline save) or a host snapshot
    (async committer — :func:`snapshot_to_host`); the writers treat numpy
    leaves as already-materialized, so the snapshot is the ONE device->host
    transfer of an async save.  ``coordinator`` routes the writers' sync
    points off the device streams (background thread)."""
    config = config or CheckpointingConfig()
    os.makedirs(weights_path, exist_ok=True)
    if config.is_peft or peft_config is not None:
        from automodel_tpu.peft.lora import save_adapters

        save_adapters(model, params, weights_path, peft_config)
        return
    if config.model_save_format == "safetensors" and config.save_consolidated:
        # Consolidated HF repo: collective gathers, shard files written in
        # parallel (one per process, round-robin), tokenizer/generation
        # sidecars copied so the export is a complete standalone repo.
        from automodel_tpu.models.hf_io import copy_hf_aux_files, save_hf_weights

        save_hf_weights(model, params, weights_path,
                        distribute_writes=config.distribute_writes,
                        barrier_fn=(coordinator.barrier
                                    if coordinator is not None else None))
        retry_io(copy_hf_aux_files, getattr(model, "checkpoint_dir", None),
                 weights_path, retries=config.io_retries,
                 backoff=config.io_retry_backoff, desc="HF aux sidecars")
    else:
        # Non-consolidated: Orbax writes each host's own shards — no gather
        # at all (the reference's per-rank DCP sharded save role,
        # ``_backports/hf_storage.py:67``).
        save_pytree(os.path.join(weights_path, "orbax"), params,
                    namespace=(coordinator.name
                               if coordinator is not None else None))


def load_model(model, weights_path: str,
               config: Optional[CheckpointingConfig] = None,
               shardings: Any = None) -> Any:
    """Parallel load into (sharded) device arrays — the meta-device-init
    equivalent: abstract-eval first, stream only needed byte ranges."""
    config = config or CheckpointingConfig()
    if config.model_save_format == "safetensors" and config.save_consolidated:
        has_hf_repo = os.path.exists(
            os.path.join(weights_path, "model.safetensors.index.json")
        ) or os.path.exists(os.path.join(weights_path, "model.safetensors"))
        if not has_hf_repo:
            raise FileNotFoundError(
                f"{weights_path} has no model.safetensors[.index.json]; the "
                "config expects a consolidated safetensors checkpoint "
                "(interrupted save, wrong path, or a non-shared filesystem "
                "where another host wrote the shards?)")
        from automodel_tpu.models.hf_io import load_hf_weights

        return load_hf_weights(model, weights_path, shardings=shardings)
    abstract = model.abstract_params()
    if shardings is not None:
        abstract = abstract_with_shardings(abstract, shardings)
    return restore_pytree(os.path.join(weights_path, "orbax"), abstract)


def save_optimizer(opt_state: Any, optim_path: str, scheduler: Any = None,
                   config: Optional[CheckpointingConfig] = None,
                   coordinator=None) -> None:
    """``scheduler`` may be the live object or an already-materialized
    ``state_dict()`` dict (async snapshot); ``save_stateful`` handles both."""
    os.makedirs(optim_path, exist_ok=True)
    save_pytree(os.path.join(optim_path, "state"), opt_state,
                namespace=(coordinator.name
                           if coordinator is not None else None))
    if scheduler is not None and jax.process_index() == 0:
        save_stateful(optim_path, "lr_scheduler", scheduler, config)


def load_optimizer(optim_path: str, abstract_state: Any,
                   scheduler: Any = None,
                   config: Optional[CheckpointingConfig] = None) -> Any:
    state = restore_pytree(os.path.join(optim_path, "state"), abstract_state)
    if scheduler is not None:
        load_stateful(optim_path, "lr_scheduler", scheduler, config)
    return state


# ---------------------------------------------------------------------------
# Host-side statefuls (schedulers, rng, dataloader) — rank-0 pickles
# ---------------------------------------------------------------------------
def save_stateful(dirpath: str, key: str, obj: Any,
                  config: Optional[CheckpointingConfig] = None) -> None:
    """Pickle one host-side stateful (``state_dict()`` of a live object, or
    a plain dict as-is — the async snapshot path materializes the dicts at
    the save boundary and passes them here).  The manifest sha256 is
    computed from the in-memory pickle bytes while they are at hand
    (``record_file_hash``), so ``build_manifest`` never re-reads the file
    it just watched being written."""
    sd = obj.state_dict() if hasattr(obj, "state_dict") else obj
    cfg = config or CheckpointingConfig()
    blob = pickle.dumps(sd)
    path = os.path.join(dirpath, f"{key}.pt")

    def _write():
        with open(path, "wb") as f:
            f.write(blob)

    retry_io(_write, retries=cfg.io_retries, backoff=cfg.io_retry_backoff,
             desc=f"stateful {key}")
    record_file_hash(path, len(blob), hashlib.sha256(blob).hexdigest())


def load_stateful(dirpath: str, key: str, obj: Any,
                  config: Optional[CheckpointingConfig] = None) -> Any:
    path = os.path.join(dirpath, f"{key}.pt")
    cfg = config or CheckpointingConfig()

    def _read():
        with open(path, "rb") as f:
            return pickle.load(f)

    sd = retry_io(_read, retries=cfg.io_retries,
                  backoff=cfg.io_retry_backoff, desc=f"stateful {key}")
    if hasattr(obj, "load_state_dict"):
        obj.load_state_dict(sd)
        return obj
    return sd


def has_stateful(dirpath: str, key: str) -> bool:
    return os.path.exists(os.path.join(dirpath, f"{key}.pt"))


# ---------------------------------------------------------------------------
# Latest-checkpoint discovery (reference base_recipe.py:182-221,363)
# ---------------------------------------------------------------------------
_CKPT_RE = re.compile(r"epoch_(\d+)_step_(\d+)$")


def checkpoint_dir_name(epoch: int, step: int) -> str:
    return f"epoch_{epoch}_step_{step}"


def find_latest_checkpoint(checkpoint_dir: str) -> Optional[str]:
    """Newest COMMITTED checkpoint by (epoch, step), or None.

    Resume hardening: ``.tmp`` staging leftovers, ``.gc.tmp`` husks,
    manifest-less (half-written or legacy) dirs, stray files, and malformed
    names are all skipped — an interrupted save is invisible here, and the
    run falls back to the newest checkpoint that actually finished.
    """
    if not os.path.isdir(checkpoint_dir):
        return None
    best, best_key = None, (-1, -1)
    for name in os.listdir(checkpoint_dir):
        m = _CKPT_RE.search(name)
        if not m:
            continue
        path = os.path.join(checkpoint_dir, name)
        if not is_committed(path):
            logger.warning(
                "skipping uncommitted checkpoint dir %s (no %s — "
                "interrupted save?)", path, MANIFEST_NAME)
            continue
        key = (int(m.group(1)), int(m.group(2)))
        if key > best_key:
            best_key, best = key, path
    return best
