"""Capability-probe + fallback registry: data-driven kernel dispatch.

Generalizes the hand-rolled splash -> flash -> SDPA chain that used to live
as per-call-site ``try/except`` logic in ``ops/attention.py``: each kernel
registers a :class:`KernelSpec` ``(name, probe, impl, fallback)`` and a
call site resolves a request by walking the fallback chain until a probe
accepts.  CPU / interpret / dryrun and TPU-generation differences are then
a property of the PROBES, not of every caller.

Contract:

* ``probe(request) -> bool`` — pure availability/capability check against a
  plain-dict request (static shapes, dtype, feature flags, sharding
  context).  "Unavailable" (wrong backend, unaligned shape) returns False;
  a backend that cannot initialise is not "unavailable" — it raises
  (:func:`on_tpu`), so a broken libtpu never reads as "no kernel here".
* ``impl(request, *args, **kwargs)`` — the kernel entry.  Impls look their
  collaborators up at CALL time (module globals), so tests can monkeypatch
  a kernel module and the registry follows.
* ``fallback`` — the next rung's registered name; ``None`` ends the chain.
* ``reference`` — optional XLA oracle with the same ``(request, *args)``
  signature, consumed by the shared interpret-mode parity harness
  (``kernel_lib/parity.py``).

Kernel modules register their rungs at import; :func:`ensure_default_kernels`
imports every in-tree kernel module (a module that fails to import is a
bug on the one installation there is, and raises) and is idempotent.
:func:`resolved_rungs` reports which rungs call sites actually resolved —
what the chip smoke asserts against.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import threading
from typing import Any, Callable, Dict, List, Mapping, Optional

Probe = Callable[[Mapping[str, Any]], bool]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel rung."""

    name: str                          # e.g. "attention.splash"
    probe: Probe
    impl: Callable[..., Any]
    fallback: Optional[str] = None
    reference: Optional[Callable[..., Any]] = None

    @property
    def kind(self) -> str:
        """Kernel family — the dotted prefix ("attention", "gmm", ...)."""
        return self.name.split(".", 1)[0]


_REGISTRY: Dict[str, KernelSpec] = {}
_RESOLVED: "collections.Counter[str]" = collections.Counter()
_LOCK = threading.Lock()
_defaults_loaded = False


def on_tpu() -> bool:
    """True when JAX's default backend is the TPU — the backend half of
    every Pallas rung's probe.  Backend initialisation errors propagate."""
    import jax

    return jax.default_backend() == "tpu"


def register_kernel(name: str, *, probe: Probe, impl: Callable,
                    fallback: Optional[str] = None,
                    reference: Optional[Callable] = None) -> KernelSpec:
    """Register (or re-register: kernel modules may be reloaded) a rung."""
    spec = KernelSpec(name=name, probe=probe, impl=impl, fallback=fallback,
                      reference=reference)
    with _LOCK:
        _REGISTRY[name] = spec
    return spec


def get_kernel(name: str) -> KernelSpec:
    ensure_default_kernels()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel registered under {name!r}; known: "
            f"{sorted(_REGISTRY)}") from None


def kernel_names() -> List[str]:
    ensure_default_kernels()
    return sorted(_REGISTRY)


def fallback_chain(name: str) -> List[str]:
    """The rung names walked for ``name``, head first."""
    out, cur = [], name
    while cur is not None:
        spec = get_kernel(cur)
        out.append(cur)
        cur = spec.fallback
        if cur in out:
            raise RuntimeError(f"kernel fallback cycle at {cur!r}: {out}")
    return out


def resolve(name: str, request: Mapping[str, Any]) -> KernelSpec:
    """First rung in ``name``'s fallback chain whose probe accepts
    ``request``.  Raises RuntimeError when the chain is exhausted — chains
    should end in an always-available anchor (SDPA, ragged_dot)."""
    seen: List[str] = []
    cur: Optional[str] = name
    while cur is not None:
        spec = get_kernel(cur)
        seen.append(cur)
        if spec.probe(request):
            with _LOCK:
                _RESOLVED[spec.name] += 1
            return spec
        cur = spec.fallback
        if cur in seen:
            raise RuntimeError(f"kernel fallback cycle at {cur!r}: {seen}")
    raise RuntimeError(
        f"no kernel in the {name!r} chain accepted the request "
        f"{dict(request)!r}; probed: {seen}")


def resolved_rungs() -> Dict[str, int]:
    """How often each rung won a :func:`resolve` in this process (trace
    time, so one count per traced call site, not per executed step)."""
    with _LOCK:
        return dict(_RESOLVED)


def dispatch(name: str, request: Mapping[str, Any], *args, **kwargs):
    """Resolve and call in one step."""
    return resolve(name, request).impl(request, *args, **kwargs)


# ---------------------------------------------------------------------------
# Default in-tree kernels
# ---------------------------------------------------------------------------
# (module, the rung it must register)
_DEFAULT_KERNEL_MODULES = (
    ("automodel_tpu.ops.ring_attention", "attention.ring"),
    ("automodel_tpu.ops.splash_attention", "attention.splash"),
    ("automodel_tpu.ops.flash_attention", "attention.flash"),
    ("automodel_tpu.ops.attention", "attention.sdpa"),
    ("automodel_tpu.ops.paged_attention_kernel", "attention.paged_decode"),
    ("automodel_tpu.ops.paged_attention", "attention.paged_gather"),
    ("automodel_tpu.ops.mla_paged_attention_kernel",
     "attention.mla_paged_decode"),
    ("automodel_tpu.ops.mla_paged_attention", "attention.mla_paged_gather"),
    ("automodel_tpu.ops.power_retention_kernel",
     "attention.retention_decode"),
    ("automodel_tpu.ops.power_retention", "attention.retention_chunk_xla"),
    ("automodel_tpu.ops.linear_ce_kernel", "linear_ce.pallas"),
    ("automodel_tpu.loss.linear_ce", "linear_ce.chunked"),
    ("automodel_tpu.ops.gmm_kernel", "gmm.pallas"),
    ("automodel_tpu.ops.moe_decode_kernel", "moe_decode.pallas"),
    ("automodel_tpu.ops.moe", "moe_decode.loop"),
    ("automodel_tpu.ops.qdot_kernel", "qdot.pallas"),
    ("automodel_tpu.ops.quant", "qdot.xla"),
    ("automodel_tpu.ops.gmm_quant_kernel", "gmm_quant.pallas"),
)


def ensure_default_kernels() -> None:
    """Import every in-tree kernel module once so their registrations run.
    An import failure propagates: a stubbed rung would let dispatch walk on
    to the XLA rung with nothing but a warning."""
    global _defaults_loaded
    if _defaults_loaded:
        return
    _defaults_loaded = True     # set first: kernel modules import us back
    try:
        for mod, rung in _DEFAULT_KERNEL_MODULES:
            importlib.import_module(mod)
            if rung not in _REGISTRY:
                raise RuntimeError(f"{mod} registered no {rung!r} rung")
    except BaseException:
        _defaults_loaded = False    # the next caller sees the failure too
        raise
