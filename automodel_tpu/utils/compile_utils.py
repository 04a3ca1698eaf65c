"""Compilation controls: the persistent XLA compile cache.

Reference analogue: ``components/utils/compile_utils.py:28-234``
(``CompileConfig`` + ``torch.compile`` wiring with dynamo cache tuning).
On TPU everything is already compiled — jit is not optional — so the
meaningful control is the PERSISTENT compilation cache: first-compile of a
1B-scale train step costs tens of seconds per process; with a warm cache
the second run of the same program loads in about a second.

Placement has ONE rule, applied by :func:`setup_compile_cache`, which every
entry point (recipes, ``tools/*.py``, ``chip_smoke.py``) calls before its
first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX honours it on its own; nothing is
  set in code, so the operator's directory is the only one written.
* unset — :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored directory inside
  the checkout.  The path is part of the cache key, so it is never a
  temporary name, a pid or a time.
* on the CPU backend code sets no cache at all: jaxlib 0.9.0's XLA:CPU
  aborts the process when it executes some cache-LOADED multi-device
  programs (tier-1 died on its fifth test with the cache on), and nobody
  pays for CPU compile time.

The YAML ``compile:`` section keeps only what is not a placement::

    compile:
      enabled: true                    # false: this run sets no cache
      min_compile_time_secs: 1.0       # don't persist trivial programs
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """Where persistent caches (XLA programs, the autotune winner table)
    live under the placement rule above."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def setup_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache (idempotent) and return its
    directory — None on the CPU backend, where code sets none."""
    import jax

    if jax.default_backend() == "cpu":
        logger.info("persistent XLA compile cache: not set on the CPU "
                    "backend")
        return None
    path = cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    logger.info("persistent XLA compile cache at %s", path)
    return path


@dataclasses.dataclass
class CompileConfig:
    enabled: bool = True
    min_compile_time_secs: float = 1.0
    # accepted for reference-YAML compat; meaningless under XLA (everything
    # in the train step is one compiled program already)
    mode: Optional[str] = None
    fullgraph: Optional[bool] = None
    dynamic: Optional[bool] = None


def build_compile_config(cfg=None, **kwargs) -> CompileConfig:
    fields = {f.name for f in dataclasses.fields(CompileConfig)}
    if cfg is not None:
        kwargs = {**cfg.to_dict(), **kwargs}
    if "cache_dir" in kwargs:
        raise ValueError(
            "compile.cache_dir is no longer read: export "
            f"{CACHE_DIR_ENV}=<dir> to place the compile cache (default: "
            f"{DEFAULT_CACHE_DIR})")
    return CompileConfig(**{k: v for k, v in kwargs.items() if k in fields})


def apply_compile_config(config: CompileConfig) -> Optional[str]:
    """Apply a ``compile:`` section; returns the cache directory in use
    (None when the section disables it, or on the CPU backend)."""
    if not config.enabled:
        return None
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(config.min_compile_time_secs))
    return setup_compile_cache()
