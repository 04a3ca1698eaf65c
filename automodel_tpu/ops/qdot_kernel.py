"""Pallas fused quantized matmul — the ``qdot.pallas`` rung.

One kernel fuses the quantized-compute hot path that the XLA rung
(``ops/quant.py::_qdot_xla_impl``) spells as three HBM round trips
(quantize a, quantize b, dot + rescale): each grid step loads a bf16/f32
``(tm, K)`` x ``(K, tn)`` tile pair into VMEM, quantizes it IN VMEM with the
pre-computed dynamic scales (the amax reductions stay in XLA — they are
bandwidth-bound and fuse with the producer), runs the int8/fp8 MXU dot with
exact accumulation (int32 for int8 x int8 — the native int8 MXU path — fp32
otherwise), and rescales into the f32 output tile.  The quantized operand
copies never exist in HBM.

Layout contract (shared with the XLA rung, see
``ops/quant.py::quantized_matmul``): ``a [m, k] @ b [k, n]`` with scale
arrays ``sa [m|1, 1]`` / ``sb [1, n|1]`` — rowwise scales ride the OUTPUT
dims only, so the rescale is a broadcast multiply and no scale ever varies
along the contraction.  K is not tiled: one dot per output tile means the
accumulation happens inside the MXU pass (fp32/int32), not across grid
steps — the "fp32 VMEM accumulation" of the fused recipe.

Registered on the kernel substrate per the PR-7 checklist: registry rung
(probe: TPU or interpret mode + lane-aligned k/n) with the XLA rung as
fallback AND parity reference, plus the ``qdot`` autotune sweep adapter.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling
from automodel_tpu.ops.quant import accum_dtype, quant_cast

# Pallas interpret mode: lets the CPU test suite execute the real kernel
# logic (tests monkeypatch this, mirroring ops/gmm_kernel.py).
_INTERPRET = False

_LANE = tiling.LANE


def qdot_kernel_available(m: int, k: int, n: int) -> bool:
    """Kernel path requires TPU (or interpret mode) and lane-aligned k/n
    (row tails are padded internally; k and n steer MXU tiles directly)."""
    if _INTERPRET:
        return True
    if k % _LANE or n % _LANE:
        return False
    return registry.on_tpu()


def _tile_bytes(tm: int, tn: int, k: int) -> int:
    """VMEM working set of one (tm, tn) tile pair: double-buffered bf16
    operand blocks, their in-VMEM quantized copies (1 byte), the fp32/int32
    dot result and the f32 out block.  ONE byte model — shared by the
    runtime tile search/validate AND the sweep's candidate filter."""
    return (2 * tm * k * 2 + 2 * k * tn * 2    # lhs/rhs double-buffer (bf16)
            + tm * k + k * tn                  # quantized copies (1 B)
            + tm * tn * 4                      # accumulator
            + 2 * tm * tn * 4)                 # f32 out block


def _tiles(m: int, k: int, n: int,
           budget: int = tiling.DEFAULT_TILE_BUDGET_BYTES) -> Tuple[int, int]:
    """(tm rows, tn cols) via the shared VMEM-budgeted search, overridden
    by a persisted autotune winner (kernel key ``"qdot"``) when it fits."""
    def use(tm: int, tn: int) -> int:
        return _tile_bytes(tm, tn, k)

    # n is not padded (the probe demands lane alignment): only column tiles
    # that DIVIDE n are legal, else the grid would drop output columns.
    cols = tuple(c for c in (512, 256, 128) if n % c == 0) or (n,)
    default = tiling.fit_tile_pair(m, (512, 256, 128), cols, use, budget)
    if n % default[1]:
        default = (default[0], n)
    fields = {"m": autotune.shape_bucket(m), "k": k, "n": n}
    return autotune.lookup(
        "qdot", fields, default,
        validate=lambda c: (len(c) == 2 and c[0] % _LANE == 0
                            and n % c[1] == 0
                            and use(c[0], c[1]) <= budget))


def _qdot_kernel(a_ref, b_ref, sa_ref, sb_ref, out_ref, *, a_dtype, b_dtype):
    sa = sa_ref[...].astype(jnp.float32)
    sb = sb_ref[...].astype(jnp.float32)
    aq = quant_cast(a_ref[...], sa, a_dtype)       # (tm, k) in VMEM
    bq = quant_cast(b_ref[...], sb, b_dtype)       # (k, tn) in VMEM
    acc = jax.lax.dot_general(
        aq, bq, (((1,), (0,)), ((), ())),
        preferred_element_type=accum_dtype(a_dtype, b_dtype))
    out_ref[...] = acc.astype(jnp.float32) * sa * sb


def qdot_pallas(a: jnp.ndarray, b: jnp.ndarray, sa: jnp.ndarray,
                sb: jnp.ndarray, a_dtype, b_dtype) -> jnp.ndarray:
    """``a [m, k] @ b [k, n] -> f32`` quantized per the operand dtypes with
    broadcast scales ``sa``/``sb`` (see module docstring for the layout
    contract)."""
    m, k = a.shape
    n = b.shape[1]
    a_dtype, b_dtype = jnp.dtype(a_dtype), jnp.dtype(b_dtype)
    tm, tn = _tiles(m, k, n)
    if n % tn:
        # A non-dividing column tile would run an EMPTY/truncated grid and
        # silently drop output columns.  _tiles' validate already rejects
        # persisted winners like this, but forced() sweep choices bypass
        # validation AND apply to every sibling GEMM of the fwd+bwd chain
        # (whose n differs from the keyed one) — clamp here so an illegal
        # tile can never skip work, it just runs a legal edge.
        tn = next((c for c in (512, 256, 128) if n % c == 0), n)
    mp = -(-m // tm) * tm
    if mp != m:
        a = jnp.pad(a, ((0, mp - m), (0, 0)))
        if sa.shape[0] != 1:
            # pad rows carry scale 1 so the in-kernel divide stays finite
            sa = jnp.pad(sa, ((0, mp - m), (0, 0)), constant_values=1.0)
    rowwise_a, rowwise_b = sa.shape[0] != 1, sb.shape[1] != 1

    from jax.experimental import pallas as pl

    out = pl.pallas_call(
        functools.partial(_qdot_kernel, a_dtype=a_dtype, b_dtype=b_dtype),
        grid=(mp // tm, n // tn),
        in_specs=[
            tiling.block_spec((tm, k), lambda i, j: (i, 0)),
            tiling.block_spec((k, tn), lambda i, j: (0, j)),
            tiling.block_spec((tm, 1) if rowwise_a else (1, 1),
                              (lambda i, j: (i, 0)) if rowwise_a
                              else (lambda i, j: (0, 0))),
            tiling.block_spec((1, tn) if rowwise_b else (1, 1),
                              (lambda i, j: (0, j)) if rowwise_b
                              else (lambda i, j: (0, 0))),
        ],
        out_specs=tiling.block_spec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * k * n, transcendentals=0,
            bytes_accessed=mp * k * a.dtype.itemsize
            + (mp // tm) * k * n * b.dtype.itemsize + mp * n * 4),
        interpret=_INTERPRET,
    )(a, b, sa.astype(jnp.float32), sb.astype(jnp.float32))
    return out[:m]


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _qdot_pallas_probe(request) -> bool:
    return qdot_kernel_available(request["m"], request["k"], request["n"])


def _qdot_pallas_impl(request, a, b, sa, sb):
    return qdot_pallas(a, b, sa, sb, request["a_dtype"], request["b_dtype"])


def _sweep_key_fields(req):
    return {"m": autotune.shape_bucket(req["m"]), "k": req["k"],
            "n": req["n"]}


def _sweep_candidates(req):
    # Same legality model as the runtime lookup's validate — VMEM budget
    # AND n % tn == 0: forced() bypasses validation, so a non-dividing tn
    # would run an EMPTY grid (computes nothing, "wins" every timing) and
    # then be rejected on every real call; an over-budget one would be
    # persisted-then-rejected (the PR-7 gmm/linear_ce hardening class).
    return [(tm, tn) for tm in (512, 256, 128) for tn in (512, 256, 128)
            if req["n"] % tn == 0
            and _tile_bytes(tm, tn, req["k"])
            <= tiling.DEFAULT_TILE_BUDGET_BYTES]


def _sweep_run(req, choice) -> float:
    from automodel_tpu.ops.quant import qdot

    m, k, n = req["m"], req["k"], req["n"]
    dtype = req.get("quant_dtype", "int8")
    recipe = req.get("recipe", "tensorwise")
    key = jax.random.key(0)
    x = jax.random.normal(key, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.float32).astype(jnp.bfloat16)

    def loss(x, w):
        return jnp.sum(qdot(x, w, recipe, dtype).astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1)))
    return autotune.time_call(fn, x, w)


from automodel_tpu.ops.quant import _qdot_xla_impl  # noqa: E402

registry.register_kernel(
    "qdot.pallas", probe=_qdot_pallas_probe, impl=_qdot_pallas_impl,
    fallback="qdot.xla", reference=_qdot_xla_impl)
autotune.register_sweep(
    "qdot", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
