"""Persistent block-size autotuning for the Pallas kernel substrate.

Every in-tree kernel asks :func:`lookup` for its block shapes, passing its
hand-tuned choice as the DEFAULT — so with autotuning off (the default
mode) behavior is bit-identical to the pre-substrate kernels.  With the
``kernels.autotune`` knob on, winners measured by REAL timed lowerings are
served from a versioned JSON cache persisted alongside the PR-5 XLA
compile cache:

* **key** — ``(kernel, shape-bucket, dtype, topology)``: sequence/row dims
  bucket to the next power of two, topology is the device kind + count, so
  one sweep covers every run of the same recipe on the same slice shape.
* **sweep** — per-kernel adapters (registered by the kernel modules via
  :func:`register_sweep`) enumerate legal candidate block shapes and time
  the kernel's own entry point (forward + backward where it trains) with
  each candidate forced; the winner is recorded and the cache re-written
  atomically.  Sweeps run at SETUP time (``BaseRecipe.setup``) or from the
  operator CLI (``tools/autotune.py --sweep``) — never inside a traced
  step.
* **degradation** — a corrupt or unreadable cache warns once and falls
  back to the hand-tuned defaults; it can never fail setup (drilled by the
  ``kernel_autotune_cache`` fault point).  A winner that does not divide
  the actual runtime shape is rejected by the call site's ``validate``
  hook and the default used instead.

Modes (``AUTOTUNE_MODES``, enum-validated at config load like
``cp_layout`` / ``moe.dispatch``; YAML ``on``/``off`` literals arrive as
bools and are normalized):

* ``off``   — hand-tuned defaults only (no cache I/O);
* ``on``    — load the cache; sweep only MISSING keys at setup;
* ``force`` — re-sweep every planned key even on a warm cache.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import tempfile
import threading
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from automodel_tpu.utils.fault_injection import fault_point

logger = logging.getLogger(__name__)

AUTOTUNE_MODES = ("off", "on", "force")
DEFAULT_AUTOTUNE_MODE = "off"
CACHE_VERSION = 1
CACHE_BASENAME = f"pallas_autotune_v{CACHE_VERSION}.json"


def normalize_autotune_mode(mode: Any) -> Optional[str]:
    """YAML null spellings -> None; YAML ``on``/``off`` literals (which
    arrive as bools) -> their mode names."""
    from automodel_tpu.config.loader import normalize_null_spelling

    mode = normalize_null_spelling(mode)
    if mode is True:
        return "on"
    if mode is False:
        return "off"
    return mode


def validate_autotune_mode(mode: Optional[str]) -> Optional[str]:
    """None (defer to the default) or a member of AUTOTUNE_MODES."""
    if mode is None:
        return None
    if mode not in AUTOTUNE_MODES:
        raise ValueError(
            f"kernels.autotune must be one of {list(AUTOTUNE_MODES)}, "
            f"got {mode!r}")
    return mode


def resolve_autotune_mode(mode: Any) -> str:
    mode = validate_autotune_mode(normalize_autotune_mode(mode))
    return DEFAULT_AUTOTUNE_MODE if mode is None else mode


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------
def shape_bucket(n: int) -> int:
    """Next power of two >= n (min 128): one sweep covers a bucket of
    nearby shapes; winners are re-validated against the exact runtime
    shape at lookup."""
    b = 128
    while b < n:
        b *= 2
    return b


def topology() -> str:
    import jax

    dev = jax.devices()[0]
    return f"{dev.device_kind}x{jax.device_count()}".replace(" ", "_")


def make_key(kernel: str, fields: Mapping[str, Any]) -> str:
    parts = [kernel]
    parts += [f"{k}={fields[k]}" for k in sorted(fields)]
    parts.append(topology())
    return "|".join(parts)


def attention_sweep_key_fields(req: Mapping[str, Any],
                               **extra: Any) -> Dict[str, Any]:
    """The attention kernels' shared key schema — bucketized q/kv + dtype,
    plus any kernel-specific extras.  ONE builder (flash/splash/ring all
    call it), so sweep-time and runtime keys cannot drift per kernel when
    the schema changes."""
    fields = {"q": shape_bucket(req["q_seq"]),
              "kv": shape_bucket(req["kv_seq"]),
              "dtype": str(req.get("dtype", "bfloat16"))}
    fields.update(extra)
    return fields


def time_call(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    """Mean wall seconds per call of ``fn(*args)`` after ``warmup`` calls
    (the first pays the compile).  Host-side timing around complete device
    executions — the sweep's "real timed lowering" measurement."""
    import time

    import jax

    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)  # lint: disable=L004 (setup-time sweep timing, not the training loop)
    t0 = time.perf_counter()
    for _ in range(max(iters, 1)):
        out = fn(*args)
    jax.block_until_ready(out)  # lint: disable=L004 (setup-time sweep timing, not the training loop)
    return (time.perf_counter() - t0) / max(iters, 1)


# ---------------------------------------------------------------------------
# Sweep adapters (registered by kernel modules)
# ---------------------------------------------------------------------------
class SweepAdapter:
    """How to autotune one kernel: bucketized key fields for a request,
    legal candidates, and a timed run of the kernel's own entry point."""

    def __init__(self, kernel: str,
                 key_fields: Callable[[Mapping], Dict[str, Any]],
                 candidates: Callable[[Mapping], Sequence[Tuple[int, ...]]],
                 run: Callable[[Mapping, Tuple[int, ...]], float]):
        self.kernel = kernel
        self.key_fields = key_fields
        self.candidates = candidates
        self.run = run


_SWEEPS: Dict[str, SweepAdapter] = {}


def register_sweep(kernel: str, *, key_fields, candidates, run) -> None:
    _SWEEPS[kernel] = SweepAdapter(kernel, key_fields, candidates, run)


def sweep_adapters() -> Dict[str, SweepAdapter]:
    from automodel_tpu.ops.kernel_lib.registry import ensure_default_kernels

    ensure_default_kernels()
    return dict(_SWEEPS)


# ---------------------------------------------------------------------------
# Forced choices (sweep-time override, thread-local)
# ---------------------------------------------------------------------------
_FORCED = threading.local()


@contextlib.contextmanager
def forced(kernel: str, choice: Tuple[int, ...]):
    """Force ``lookup(kernel, ...)`` to return ``choice`` on this thread —
    how the sweep times one candidate through the kernel's own entry."""
    prev = getattr(_FORCED, "map", None)
    _FORCED.map = dict(prev or {})
    _FORCED.map[kernel] = tuple(choice)
    try:
        yield
    finally:
        _FORCED.map = prev or {}


# ---------------------------------------------------------------------------
# The autotuner
# ---------------------------------------------------------------------------
class BlockAutotuner:
    """In-memory winner table + the persistent JSON cache behind it."""

    def __init__(self, mode: str = DEFAULT_AUTOTUNE_MODE,
                 cache_path: Optional[str] = None):
        self.mode = resolve_autotune_mode(mode)
        self.cache_path = cache_path
        self.table: Dict[str, dict] = {}
        self.loaded_from_cache = False
        self.lookup_hits = 0
        self.lookup_misses = 0
        self.swept = 0
        self.chosen: Dict[str, List[int]] = {}     # key -> winning block
        self.last_sweep: Optional[dict] = None
        if self.mode != "off" and self.cache_path:
            self.load_cache()

    # -- cache I/O ---------------------------------------------------------
    def load_cache(self) -> None:
        """Read the persisted winner table.  A missing file is a cold
        start; ANY other failure (corrupt JSON, wrong version, unreadable
        file — or the armed ``kernel_autotune_cache`` fault) warns once
        and degrades to the hand-tuned defaults.  Never raises."""
        try:
            fault_point("kernel_autotune_cache")
            with open(self.cache_path) as f:
                data = json.load(f)
            if data.get("version") != CACHE_VERSION:
                raise ValueError(
                    f"cache version {data.get('version')!r} != "
                    f"{CACHE_VERSION}")
            entries = data.get("entries")
            if not isinstance(entries, dict):
                raise ValueError("cache has no 'entries' mapping")
            for key, entry in entries.items():
                if not (isinstance(entry, dict)
                        and isinstance(entry.get("block"), list)):
                    raise ValueError(f"malformed cache entry {key!r}")
            self.table = entries
            self.loaded_from_cache = True
        except FileNotFoundError:
            pass                                    # cold start: sweep fills it
        except Exception as e:
            logger.warning(
                "kernel autotune cache %s is unreadable (%s); falling back "
                "to the hand-tuned block-size defaults — delete or re-sweep "
                "it with tools/autotune.py", self.cache_path, e)

    def save_cache(self) -> None:
        """Atomic write (tmp + rename) so a crash mid-save can never leave
        a torn cache for the next run's load to trip on."""
        if not self.cache_path:
            return
        payload = {"version": CACHE_VERSION, "topology": topology(),
                   "entries": self.table}
        d = os.path.dirname(os.path.abspath(self.cache_path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".autotune_", dir=d)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.cache_path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise

    # -- lookups (called by kernels at trace time: pure dict reads) --------
    def lookup(self, kernel: str, fields: Mapping[str, Any],
               default: Tuple[int, ...],
               validate: Optional[Callable[[Tuple[int, ...]], bool]] = None,
               ) -> Tuple[int, ...]:
        forced_map = getattr(_FORCED, "map", None)
        if forced_map and kernel in forced_map:
            return tuple(forced_map[kernel])
        if self.mode == "off":
            return tuple(default)
        key = make_key(kernel, fields)
        entry = self.table.get(key)
        if entry is not None:
            choice = tuple(entry["block"])
            if validate is None or validate(choice):
                self.lookup_hits += 1
                self.chosen[key] = list(choice)
                return choice
        self.lookup_misses += 1
        return tuple(default)

    # -- sweeping ----------------------------------------------------------
    def sweep_requests(self, requests: Sequence[Tuple[str, Mapping]],
                       ) -> dict:
        """Time candidates for every (kernel, request) whose key is not
        already cached (``force`` re-sweeps all), record winners, persist.
        A failing candidate or adapter never fails the caller — it logs
        and moves on (the defaults remain available).

        Multihost runs never sweep: timing noise could elect different
        winners per host, and block sizes are baked into each host's
        compiled program — divergent choices would deadlock GSPMD.  All
        hosts either read the same warm cache or use the same defaults;
        pre-warm with ``tools/autotune.py --sweep`` on one host."""
        from automodel_tpu.ops.kernel_lib.registry import (
            ensure_default_kernels,
        )

        ensure_default_kernels()        # kernel modules register their sweeps
        report = {"requested": 0, "cached": 0, "swept": 0, "errors": 0}
        import jax

        if jax.process_count() > 1:
            missing = [k for k, r in requests
                       if k in _SWEEPS and make_key(
                           k, _SWEEPS[k].key_fields(r)) not in self.table]
            if missing:
                logger.warning(
                    "kernel autotune: skipping the block-size sweep on a "
                    "multihost run (%d uncached key(s): %s) — hosts must "
                    "compile identical programs; pre-warm the cache with "
                    "tools/autotune.py --sweep", len(missing), missing)
            report["cached"] = len(requests) - len(missing)
            self.last_sweep = report
            return report
        for kernel, req in requests:
            adapter = _SWEEPS.get(kernel)
            if adapter is None:
                continue
            report["requested"] += 1
            try:
                key = make_key(kernel, adapter.key_fields(req))
                if key in self.table and self.mode != "force":
                    report["cached"] += 1
                    continue
                best, best_t = None, float("inf")
                timings = {}
                for choice in adapter.candidates(req):
                    with forced(kernel, choice):
                        t = adapter.run(req, tuple(choice))
                    timings["x".join(map(str, choice))] = round(t * 1e3, 3)
                    if t < best_t:
                        best, best_t = tuple(choice), t
                if best is None:
                    continue
                self.table[key] = {"block": list(best),
                                   "ms": round(best_t * 1e3, 3),
                                   "timings_ms": timings}
                self.swept += 1
                report["swept"] += 1
                logger.info("autotuned %s -> %s (%.2f ms)", key,
                            "x".join(map(str, best)), best_t * 1e3)
            except Exception:
                report["errors"] += 1
                logger.warning("autotune sweep failed for %s %r (keeping "
                               "the hand-tuned default)", kernel, dict(req),
                               exc_info=True)
        if report["swept"]:
            try:
                self.save_cache()
            except OSError as e:
                logger.warning("could not persist the autotune cache to "
                               "%s: %s", self.cache_path, e)
        self.last_sweep = report
        return report

    # -- reporting ---------------------------------------------------------
    @property
    def cache_hit(self) -> bool:
        """True iff this process needed no sweep and every kernel lookup
        so far was served from the persisted table — the warm-start
        signal the bench reports."""
        return (self.loaded_from_cache and self.swept == 0
                and self.lookup_misses == 0
                and (self.lookup_hits > 0
                     or (self.last_sweep or {}).get("cached", 0) > 0))

    def report(self) -> dict:
        return {
            "mode": self.mode,
            "cache_path": self.cache_path,
            "cache_hit": self.cache_hit,
            "chosen": {k: "x".join(map(str, v))
                       for k, v in sorted(self.chosen.items())},
            "sweep": self.last_sweep,
        }


# ---------------------------------------------------------------------------
# Process-global active autotuner
# ---------------------------------------------------------------------------
_ACTIVE = BlockAutotuner(mode="off")


def default_cache_path() -> str:
    """Beside the persistent XLA compile cache, under its placement rule
    (``utils/compile_utils.cache_dir``)."""
    from automodel_tpu.utils.compile_utils import cache_dir

    return os.path.join(cache_dir(), CACHE_BASENAME)


def configure_autotune(mode: Any = None,
                       cache_path: Optional[str] = None) -> BlockAutotuner:
    """Install the process autotuner (recipes call this from ``setup()``)."""
    global _ACTIVE
    mode = resolve_autotune_mode(mode)
    if cache_path is None and mode != "off":
        cache_path = default_cache_path()
    _ACTIVE = BlockAutotuner(mode=mode, cache_path=cache_path)
    if mode != "off":
        logger.info("kernel block-size autotune %s (cache: %s)", mode,
                    cache_path)
    return _ACTIVE


def active_autotuner() -> BlockAutotuner:
    return _ACTIVE


def lookup(kernel: str, fields: Mapping[str, Any],
           default: Tuple[int, ...],
           validate: Optional[Callable[[Tuple[int, ...]], bool]] = None,
           ) -> Tuple[int, ...]:
    """The kernels' entry point: active-table lookup, hand-tuned default
    on miss/off.  Pure python — safe at trace time."""
    return _ACTIVE.lookup(kernel, fields, default, validate)


def autotune_report() -> dict:
    return _ACTIVE.report()


# ---------------------------------------------------------------------------
# Sweep-request planning from a model config (recipe setup / operator CLI)
# ---------------------------------------------------------------------------
def training_sweep_requests(model, seq_len: Optional[int],
                            local_batch: int = 1,
                            cp: int = 1) -> List[Tuple[str, dict]]:
    """The (kernel, request) list a training run of ``model`` at
    ``seq_len`` tokens per row will look up: attention per layer shape —
    the SPLASH key at cp=1, the RING inner-tile key when context
    parallelism is active (cp>1 dispatch resolves to the ring
    unconditionally, so sweeping splash there would be pure cost) — the
    fused linear-CE at the microbatch row count, and the grouped matmul
    for routed-expert configs.  Tolerant of partial model configs —
    underivable kernels are simply not planned (their lookups fall back
    to the hand-tuned defaults)."""
    cfg = getattr(model, "config", None)
    if cfg is None or not seq_len or seq_len % 128:
        return []
    dtype = str(getattr(model, "compute_dtype", None) or "bfloat16")
    out: List[Tuple[str, dict]] = []
    hidden = getattr(cfg, "hidden_size", None)
    hq = getattr(cfg, "num_attention_heads", None)
    hk = getattr(cfg, "num_key_value_heads", None) or hq
    d = getattr(cfg, "head_dim", None) or (
        hidden // hq if hidden and hq else None)
    if hq and d and cp > 1 and seq_len % cp == 0:
        # per-shard local sequence: what _block_attend's _tile_plan sees
        out.append(("ring", {
            "q_seq": seq_len // cp, "kv_seq": seq_len // cp, "head_dim": d,
            "num_q_heads": hq, "num_kv_heads": hk, "causal": True,
            "batch": max(local_batch, 1), "dtype": dtype}))
    elif hq and d:
        splash_req = {
            "q_seq": seq_len, "kv_seq": seq_len, "head_dim": d,
            "num_q_heads": hq, "num_kv_heads": hk, "causal": True,
            "batch": max(local_batch, 1), "dtype": dtype}
        out.append(("splash", splash_req))
        # the fused backward's own triple (block_q_dkv / block_kv_dkv)
        # sweeps under its own key — same request shape
        out.append(("splash_bwd", dict(splash_req)))
    vocab = getattr(cfg, "vocab_size", None)
    if hidden and vocab and hidden % 128 == 0:
        out.append(("linear_ce", {
            "t": max(local_batch, 1) * seq_len, "h": hidden, "v": vocab,
            "dtype": dtype}))
    n_exp = (getattr(cfg, "num_experts", None)
             or getattr(cfg, "n_routed_experts", None))
    moe_i = getattr(cfg, "moe_intermediate_size", None)
    top_k = getattr(cfg, "num_experts_per_tok", None) or 1
    if n_exp and moe_i and hidden and hidden % 128 == 0 and moe_i % 128 == 0:
        # the sorted dispatch's static buffer is N + E*block_rows rows
        # (ops/moe.py::sorted_expert_ffn), NOT N: plan with the padded row
        # count so the sweep's key buckets exactly like the runtime lookup
        # (N alone would land one bucket short whenever N is a power of 2)
        rows = max(local_batch, 1) * seq_len * top_k + n_exp * 128
        out.append(("gmm", {"m": rows, "k": hidden, "n": moe_i,
                            "num_groups": n_exp, "dtype": dtype}))
        out.append(("gmm", {"m": rows, "k": moe_i, "n": hidden,
                            "num_groups": n_exp, "dtype": dtype}))
    # Quantized compute (fp8.enabled): the dense projections route through
    # qdot, whose custom VJP issues THREE GEMMs per projection [K, N] —
    # fwd (rows, K, N), dgrad (rows, N, K) and wgrad (K, rows, N) — each
    # with its own (m-bucket, k, n) cache key, so a pre-warm must plan all
    # three or the backward lookups stay cold after a full sweep.  The
    # quantized grouped matmul shares the "gmm" key above (same schedule,
    # smaller operands).
    quant = getattr(model, "quant", None)
    inter = getattr(cfg, "intermediate_size", None)
    if (quant is not None and getattr(quant, "enabled", False)
            and hidden and inter and hidden % 128 == 0 and inter % 128 == 0):
        # seq_len % 128 == 0 is enforced at entry, so the wgrad GEMM's
        # row-count contraction (k = rows) is lane-aligned by construction
        rows = max(local_batch, 1) * seq_len
        pairs = {(hidden, inter), (inter, hidden)}      # gate/up, down
        if hq and d and (hq * d) % 128 == 0:
            pairs |= {(hidden, hq * d), (hq * d, hidden)}   # qkv-ish, o
            if hk and (hk * d) % 128 == 0:
                pairs.add((hidden, hk * d))                 # k/v (GQA)
        seen = set()
        for K, N in sorted(pairs):
            for m, k, n in ((rows, K, N), (rows, N, K), (K, rows, N)):
                key = (shape_bucket(m), k, n)
                if key in seen:
                    continue
                seen.add(key)
                out.append(("qdot", {"m": m, "k": k, "n": n,
                                     "quant_dtype": quant.dtype,
                                     "recipe": quant.recipe_name}))
    return out
