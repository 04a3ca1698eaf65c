"""What both runners share: the look for the chip, the compile cache, the
compile counter, host spans, quantiles and the comparison record."""

from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, Dict, List, Optional

from benchmark import manifest as mf


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def find_chips(chips: int, peaks: Dict[str, Any]) -> Dict[str, Any]:
    """The device as JAX reports it, or exit: no TPU, another count than
    the cell asks for, or a kind the peaks table lacks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) != chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"benchmark: device kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def place_compile_cache() -> Optional[str]:
    """The program's one rule: JAX_COMPILATION_CACHE_DIR, else the fixed
    in-checkout .jax_cache/.  Every program is kept, however fast it
    compiled, so that a second run compiles nothing."""
    import jax

    from automodel_tpu.utils.compile_utils import setup_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return setup_compile_cache()


class CompileCounter:
    """Counts backend compilations (cache hits do not compile)."""

    def __init__(self):
        import jax.monitoring as mon
        from jax._src import dispatch

        self.count = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self._event:
            self.count += 1


def peak_memory_bytes() -> int:
    """Peak on the fullest chip; a backend that reports none is an error."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.local_devices())


def check_rungs(expected: List[str], forbidden: List[str]) -> Dict[str, int]:
    from automodel_tpu.ops.kernel_lib import parity, registry

    rungs = registry.resolved_rungs()
    say(f"resolved rungs {rungs}")
    on = parity.interpret_flags_on()
    missing = [r for r in expected if not rungs.get(r)]
    wrong = [r for r in forbidden if rungs.get(r)]
    if missing or wrong or on:
        print(f"benchmark: kernel dispatch: expected {missing} never "
              f"resolved; XLA rungs resolved instead: {wrong}; interpret "
              f"flags on: {on}", file=sys.stderr)
        raise SystemExit(4)
    return rungs


class Spans:
    """Host spans on ``time.perf_counter``; while a profiler trace runs the
    same spans go into it as ``bench:<name>`` TraceAnnotations, so that the
    reduction can lay them against the device's timeline."""

    def __init__(self):
        self.rows: List[tuple] = []        # (name, t0, t1)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def total(self, name: str, t_lo: float, t_hi: float) -> float:
        return sum(min(b, t_hi) - max(a, t_lo) for n, a, b in self.rows
                   if n == name and b > t_lo and a < t_hi)


def quantile(values: List[float], q: float) -> float:
    """Linear-interpolated quantile of all values (numpy's default)."""
    import numpy as np

    return float(np.quantile(np.asarray(values, float), q))


class Compared:
    """Each number compared beside its limit; ``correct`` is all of them."""

    def __init__(self):
        self.rows: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        ok = value == value and value <= limit      # NaN fails
        self.rows[name] = {"value": float(value), "limit": float(limit),
                           "ok": bool(ok)}

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows.values())

    def print(self) -> None:
        for name, r in self.rows.items():
            print(f"compared {name} = {r['value']:.6g} (limit "
                  f"{r['limit']:.6g}) {'ok' if r['ok'] else 'FAILS'}",
                  file=sys.stderr, flush=True)


def start_trace(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop_trace(trace_dir: str) -> str:
    """Stop and return the path of the ``.xplane.pb`` just written."""
    import glob

    import jax

    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def trace_dir() -> str:
    """Inside the checkout, fixed, emptied before each traced run."""
    import shutil

    path = os.path.join(mf.ROOT, ".bench_trace")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
