"""Pallas grouped matmul (``gmm``) for sort-based dropless MoE.

The sorted MoE path (``ops/moe.py::sorted_expert_ffn``) lays every routed
assignment out as one contiguous buffer ordered by expert id, so the expert
FFNs become a single *grouped* matmul: ``out[rows of expert e] = lhs[rows of
expert e] @ rhs[e]`` with ragged per-expert row counts.  This is the TPU
shape of MegaBlocks' block-sparse expert compute and MaxText's megablox
``gmm``: instead of the GShard dispatch/combine einsums (whose
``[G, M, E, C]`` operands dwarf the useful FLOPs at large E), the MXU only
ever sees the ``O(tokens * k)`` rows that actually routed.

Kernel layout (megablox structure):

* **work items** — the grid's inner dimension enumerates (row-tile, group)
  pairs.  A row tile that straddles a group boundary is visited once per
  group it intersects; rows outside the work item's group are masked to
  zero, so no tile alignment is required of the caller.  The static work
  item count is ``m/tm + E`` (each group adds at most one straddle; empty
  groups get one phantom item so every output block is initialized).
* **accumulation** — row-tile ids are non-decreasing over work items, so an
  fp32 VMEM scratch accumulates every group's contribution to the current
  out tile and stores once on the last visit (bf16 inputs, fp32 accumulate).
* **scalar prefetch** — group ids / tile ids / segment bounds ride
  ``PrefetchScalarGridSpec`` so BlockSpec index maps can steer the rhs
  (expert weight) DMA per work item.

The backward pass is two more grouped matmuls with the SAME grouping:
``dlhs = gmm(dout, rhs^T)`` and ``drhs = tgmm(lhs, dout)`` (per-group
``x^T @ dy``, accumulated across the group's row tiles), wired as a
``custom_vjp`` because Pallas kernels do not autodiff.

Rows past ``sum(group_sizes)`` (capacity-dropped assignments sorted to the
tail) produce zeros and receive zero gradient.

The pure-XLA fallback keeps the whole path runnable and testable under
``JAX_PLATFORMS=cpu``: when the caller guarantees every group starts at a
``block_rows`` boundary (``block_aligned=True`` — ``sorted_expert_ffn``
pads its segments exactly so), each block belongs to one group and the
grouped matmul is an einsum over block segments with the block's expert
weight gathered — ``O(m * k * n)`` like the kernel, not the
``O(E * m * k * n)`` dense expansion ``lax.ragged_dot`` lowers to off-TPU.
Unaligned callers fall through to ``lax.ragged_dot`` (correct, dense).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling

# Pallas interpret mode: lets the CPU test suite execute the real kernel
# logic (tests monkeypatch this, mirroring ops/linear_ce_kernel.py).
_INTERPRET = False

_LANE = tiling.LANE


def gmm_kernel_available(m: int, k: int, n: int) -> bool:
    """Kernel path requires TPU (or interpret mode) and lane-aligned k/n
    (row tails are padded internally; k and n steer MXU tiles directly)."""
    if _INTERPRET:
        return True
    if k % _LANE or n % _LANE:
        return False
    return registry.on_tpu()


def _tile_bytes(tm: int, tn: int, k: int) -> int:
    """VMEM working set of one (tm, tn) tile pair: double-buffered lhs/rhs
    blocks + fp32 accumulator + out block.  ONE byte model — shared by the
    runtime tile search/validate AND the sweep's candidate filter, so an
    estimate change can never let the sweep persist a winner the runtime
    would reject."""
    return (2 * tm * k * 2 + 2 * k * tn * 2    # lhs/rhs double-buffer
            + tm * tn * 4                      # fp32 accumulator
            + 2 * tm * tn * 2)                 # out block


def _tiles(m: int, k: int, n: int,
           budget: int = tiling.DEFAULT_TILE_BUDGET_BYTES) -> Tuple[int, int]:
    """(tm rows, tn cols): largest tile pair whose ``_tile_bytes`` fit the
    budget (``tiling.fit_tile_pair`` — same sizing philosophy as
    linear_ce_kernel._tiles; tails are masked/padded, so only the 128 lane
    constrains shapes).  A persisted autotune winner (kernel key ``"gmm"``)
    overrides when it fits."""
    def use(tm: int, tn: int) -> int:
        return _tile_bytes(tm, tn, k)

    default = tiling.fit_tile_pair(
        m, (512, 256, 128), (512, 256, 128), use, budget)
    fields = {"m": autotune.shape_bucket(m), "k": k, "n": n}
    return autotune.lookup(
        "gmm", fields, default,
        validate=lambda c: (len(c) == 2 and c[0] % _LANE == 0
                            and c[1] % _LANE == 0
                            and use(c[0], c[1]) <= budget))


# ---------------------------------------------------------------------------
# Work-item metadata: (row tile, group) schedule shared by gmm and tgmm
# ---------------------------------------------------------------------------
def _group_tile_metadata(group_sizes: jnp.ndarray, m: int, tm: int):
    """Static-shape schedule over (row tile, group) intersections.

    Returns int32 arrays of length ``W = m/tm + E``: per work item the group
    id (clamped), the row-tile id (non-decreasing — the accumulation
    contract), first/last-visit flags for the OUT TILE (gmm) and for the
    GROUP (tgmm), and a validity flag killing phantom/pad contributions.
    Row tiles past the last group's rows are covered by pad items so every
    output block is written (zeros), and every group — even empty ones —
    owns at least one item so every tgmm block is written.
    """
    E = group_sizes.shape[0]
    nmt = m // tm
    W = nmt + E
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    # tiles each group visits (>= 1 so empty groups still zero-init their
    # tgmm output block; the row mask kills their gmm contribution)
    tiles_per = jnp.maximum((ends + tm - 1) // tm - starts // tm, 1)
    woff = jnp.cumsum(tiles_per)
    total = woff[-1]
    wstart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), woff[:-1].astype(jnp.int32)])
    warr = jnp.arange(W, dtype=jnp.int32)
    gid = jnp.searchsorted(woff, warr, side="right").astype(jnp.int32)
    gid_c = jnp.minimum(gid, E - 1)
    mid = (jnp.take(starts, gid_c) // tm
           + (warr - jnp.take(wstart, gid_c))).astype(jnp.int32)
    # A trailing empty group whose start == m would index tile m/tm — one
    # past the end (and non-monotonic after the pad items below).  Its row
    # mask is empty either way, so clamp it onto the last real tile.
    mid = jnp.minimum(mid, nmt - 1)
    valid = warr < total
    # pad items sweep the uncovered tail tiles (dropped-assignment rows),
    # clamped to the last tile once everything is covered
    covered = jnp.where(total > 0,
                        jnp.take(mid, jnp.maximum(total - 1, 0)) + 1, 0)
    mid = jnp.where(valid, mid,
                    jnp.clip(covered + (warr - total), 0, nmt - 1))
    mid = mid.astype(jnp.int32)
    gid_c = jnp.where(valid, gid_c, E - 1).astype(jnp.int32)

    def edges(a):
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), a[:-1]])
        nxt = jnp.concatenate([a[1:], jnp.full((1,), -1, jnp.int32)])
        return (a != prev).astype(jnp.int32), (a != nxt).astype(jnp.int32)

    tile_first, tile_last = edges(mid)
    # Group-edge flags drive tgmm's out-block init/store; pad items (which
    # the BlockSpec index map clamps onto the LAST group's block) must
    # neither re-init nor re-store it, so their flags are masked off — the
    # ``E`` sentinel in the edge array guarantees the last valid item of
    # the last group still sees a group transition.
    grp_first, grp_last = edges(jnp.where(valid, gid_c, E))
    vmask = valid.astype(jnp.int32)
    grp_first = grp_first * vmask
    grp_last = grp_last * vmask
    return dict(gid=gid_c, mid=mid, starts=starts, ends=ends,
                tile_first=tile_first, tile_last=tile_last,
                grp_first=grp_first.astype(jnp.int32),
                grp_last=grp_last.astype(jnp.int32),
                valid=valid.astype(jnp.int32), num_items=W)


def _row_mask(mid_ref, starts_ref, ends_ref, valid_ref, g, w, tm):
    rows = mid_ref[w] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return ((rows >= starts_ref[g]) & (rows < ends_ref[g])
            & (valid_ref[w] == 1))


# ---------------------------------------------------------------------------
# Forward kernel: out[rows of g] = lhs[rows of g] @ rhs[g]
# ---------------------------------------------------------------------------
def _gmm_kernel(gid_ref, mid_ref, starts_ref, ends_ref, first_ref, last_ref,
                valid_ref, lhs_ref, rhs_ref, out_ref, acc, *, tm: int,
                acc_t=jnp.float32):
    w = pl.program_id(1)

    @pl.when(first_ref[w] == 1)
    def _():
        acc[...] = jnp.zeros_like(acc)

    g = gid_ref[w]
    mask = _row_mask(mid_ref, starts_ref, ends_ref, valid_ref, g, w, tm)
    x = jnp.where(mask, lhs_ref[...], jnp.zeros((), lhs_ref.dtype))
    acc[...] += jnp.dot(x, rhs_ref[0], preferred_element_type=acc_t)

    @pl.when(last_ref[w] == 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _gmm_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray,
                group_sizes: jnp.ndarray, *,
                acc_dtype=jnp.float32,
                out_dtype=None) -> jnp.ndarray:
    """``acc_dtype``/``out_dtype`` parametrize the quantized rungs
    (``ops/gmm_quant_kernel.py``): int8 operands accumulate EXACTLY in an
    int32 VMEM scratch (the native int8 MXU path) and store f32; the
    defaults are bit-identical to the pre-quantization kernel."""
    m, k = lhs.shape
    E, _, n = rhs.shape
    out_dtype = lhs.dtype if out_dtype is None else jnp.dtype(out_dtype)
    tm, tn = _tiles(m, k, n)
    mp, np_ = -(-m // tm) * tm, -(-n // tn) * tn
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    if np_ != n:
        rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, np_ - n)))
    meta = _group_tile_metadata(group_sizes, mp, tm)
    grid = (np_ // tn, meta["num_items"])
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, acc_t=jnp.dtype(acc_dtype)),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=7,
            grid=grid,
            in_specs=[
                tiling.block_spec((tm, k),
                                  lambda j, w, gid, mid, *_: (mid[w], 0)),
                tiling.block_spec((1, k, tn),
                                  lambda j, w, gid, mid, *_: (gid[w], 0, j)),
            ],
            out_specs=tiling.block_spec(
                (tm, tn), lambda j, w, gid, mid, *_: (mid[w], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.dtype(acc_dtype))],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * k * np_, transcendentals=0,
            bytes_accessed=mp * k * lhs.dtype.itemsize
            + (mp // tm + E) * k * tn * rhs.dtype.itemsize),
        interpret=_INTERPRET,
    )(meta["gid"], meta["mid"], meta["starts"], meta["ends"],
      meta["tile_first"], meta["tile_last"], meta["valid"], lhs, rhs)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Transposed kernel: drhs[g] = lhs[rows of g]^T @ dout[rows of g]
# ---------------------------------------------------------------------------
def _tgmm_kernel(gid_ref, mid_ref, starts_ref, ends_ref, first_ref, last_ref,
                 valid_ref, lhs_ref, dout_ref, out_ref, acc, *, tm: int):
    w = pl.program_id(1)

    @pl.when(first_ref[w] == 1)
    def _():
        acc[...] = jnp.zeros_like(acc)

    g = gid_ref[w]
    mask = _row_mask(mid_ref, starts_ref, ends_ref, valid_ref, g, w, tm)
    x = jnp.where(mask, lhs_ref[...], jnp.zeros((), lhs_ref.dtype))
    acc[...] += lax.dot_general(
        x, dout_ref[...], dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[w] == 1)
    def _():
        out_ref[0] = acc[...].astype(out_ref.dtype)


def _tgmm_pallas(lhs: jnp.ndarray, dout: jnp.ndarray,
                 group_sizes: jnp.ndarray) -> jnp.ndarray:
    m, k = lhs.shape
    _, n = dout.shape
    E = group_sizes.shape[0]
    tm, tn = _tiles(m, k, n)
    mp, np_ = -(-m // tm) * tm, -(-n // tn) * tn
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
        dout = jnp.pad(dout, ((0, mp - m), (0, 0)))
    if np_ != n:
        dout = jnp.pad(dout, ((0, 0), (0, np_ - n)))
    meta = _group_tile_metadata(group_sizes, mp, tm)
    grid = (np_ // tn, meta["num_items"])
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=7,
            grid=grid,
            in_specs=[
                tiling.block_spec((tm, k),
                                  lambda j, w, gid, mid, *_: (mid[w], 0)),
                tiling.block_spec((tm, tn),
                                  lambda j, w, gid, mid, *_: (mid[w], j)),
            ],
            out_specs=tiling.block_spec(
                (1, k, tn), lambda j, w, gid, mid, *_: (gid[w], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, k, np_), lhs.dtype),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * k * np_, transcendentals=0,
            bytes_accessed=2 * mp * (k + np_) * lhs.dtype.itemsize),
        interpret=_INTERPRET,
    )(meta["gid"], meta["mid"], meta["starts"], meta["ends"],
      meta["grp_first"], meta["grp_last"], meta["valid"], lhs, dout)
    return out[:, :, :n]


@jax.custom_vjp
def _gmm_pallas_diff(lhs, rhs, group_sizes):
    return _gmm_pallas(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return _gmm_pallas(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_pallas(dout, jnp.swapaxes(rhs, 1, 2), group_sizes)
    drhs = _tgmm_pallas(lhs, dout, group_sizes)
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


_gmm_pallas_diff.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# Pure-XLA fallbacks
# ---------------------------------------------------------------------------
def _gmm_xla_blocked(lhs: jnp.ndarray, rhs: jnp.ndarray,
                     group_sizes: jnp.ndarray, block: int) -> jnp.ndarray:
    """Block-aligned fallback: every group starts at a ``block`` boundary
    (the caller's promise — sorted_expert_ffn pads segments exactly so), so
    each row block belongs to one group and the grouped matmul is a batched
    einsum over blocks with the block's expert weight gathered.  Same
    ``O(m*k*n)`` FLOPs as the kernel; the weight gather materializes
    ``[m/block, k, n]`` — fine at fallback (CPU-test / small-E) scale, which
    is why the TPU path is a kernel and not this."""
    m, k = lhs.shape
    E, _, n = rhs.shape
    nb = m // block
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    gid = jnp.searchsorted(
        ends, jnp.arange(nb, dtype=jnp.int32) * block, side="right")
    valid = gid < E
    wb = jnp.take(rhs, jnp.minimum(gid, E - 1), axis=0)     # [nb, k, n]
    out = jnp.einsum("bmk,bkn->bmn", lhs.reshape(nb, block, k), wb,
                     preferred_element_type=jnp.float32)
    out = jnp.where(valid[:, None, None], out, jnp.zeros((), out.dtype))
    return out.reshape(m, n).astype(lhs.dtype)


def _tgmm_xla_blocked(lhs: jnp.ndarray, dout: jnp.ndarray,
                      group_sizes: jnp.ndarray, block: int) -> jnp.ndarray:
    """Block-aligned XLA tgmm (per-group ``lhs^T @ dout`` -> [E, k, n]):
    under the same caller promise as :func:`_gmm_xla_blocked` each row block
    belongs to one group, so the per-group outer products are a batched
    einsum over blocks scatter-added into the expert slots.  ``O(m*k*n)``
    like the kernel; consumed by the quantized grouped matmul's backward
    (``ops/gmm_quant_kernel.py``) where no Pallas path is available."""
    m, k = lhs.shape
    n = dout.shape[1]
    E = group_sizes.shape[0]
    nb = m // block
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    gid = jnp.searchsorted(
        ends, jnp.arange(nb, dtype=jnp.int32) * block, side="right")
    valid = gid < E
    prods = jnp.einsum("bmk,bmn->bkn", lhs.reshape(nb, block, k),
                       dout.reshape(nb, block, n),
                       preferred_element_type=jnp.float32)
    prods = jnp.where(valid[:, None, None], prods, jnp.zeros((), prods.dtype))
    out = jnp.zeros((E, k, n), jnp.float32).at[
        jnp.minimum(gid, E - 1)].add(prods)
    return out.astype(lhs.dtype)


def tgmm(lhs: jnp.ndarray, dout: jnp.ndarray, group_sizes: jnp.ndarray, *,
         block_aligned: bool = False, block_rows: int = 128) -> jnp.ndarray:
    """Per-group ``lhs[rows of e]^T @ dout[rows of e] -> [E, k, n]`` — the
    grouped wgrad.  Pallas kernel on TPU/interpret; block-aligned XLA
    fallback under the caller's alignment promise; dense one-hot einsum as
    the anchor.  Not a registry family of its own: it is only reachable
    through the gmm/gmm_quant backward passes, whose parity tests execute
    all three branches."""
    m, k = lhs.shape
    n = dout.shape[1]
    if gmm_kernel_available(m, k, n):
        return _tgmm_pallas(lhs, dout, group_sizes)
    if block_aligned and m % block_rows == 0:
        return _tgmm_xla_blocked(lhs, dout, group_sizes, block_rows)
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes.astype(jnp.int32)
    rows = jnp.arange(m, dtype=jnp.int32)
    onehot = ((rows[:, None] >= starts[None, :])
              & (rows[:, None] < ends[None, :])).astype(lhs.dtype)  # [m, E]
    return jnp.einsum("me,mk,mn->ekn", onehot, lhs, dout,
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, *,
        block_aligned: bool = False, block_rows: int = 128) -> jnp.ndarray:
    """Grouped matmul: rows of ``lhs`` [m, k] are contiguous per-group
    segments sized by ``group_sizes`` [E]; each multiplies ``rhs`` [E, k, n].
    Rows past ``sum(group_sizes)`` yield zeros (and zero grads).

    ``block_aligned=True`` is the caller's STATIC promise that every group
    size is a multiple of ``block_rows`` (and ``m`` too) — it selects the
    efficient XLA fallback off-TPU; the Pallas kernel never needs it.
    Differentiable w.r.t. ``lhs``/``rhs`` on every path.

    Dispatch is data-driven through the kernel registry: ``gmm.pallas`` ->
    ``gmm.xla_blocked`` -> ``gmm.ragged`` (dense, the anchor).
    """
    m, k = lhs.shape
    n = rhs.shape[-1]
    request = {"kind": "gmm", "m": m, "k": k, "n": n,
               "block_aligned": bool(block_aligned),
               "block_rows": int(block_rows),
               "dtype": str(lhs.dtype)}
    return registry.dispatch("gmm.pallas", request, lhs, rhs, group_sizes)


# ---------------------------------------------------------------------------
# Registry rungs + autotune adapter
# ---------------------------------------------------------------------------
def _gmm_reference(request, lhs, rhs, group_sizes):
    """Dense XLA oracle: per-group segment einsum via one-hot group ids —
    O(E*m*k*n), parity-harness only."""
    m = lhs.shape[0]
    E = rhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes.astype(jnp.int32)
    rows = jnp.arange(m, dtype=jnp.int32)
    onehot = ((rows[:, None] >= starts[None, :])
              & (rows[:, None] < ends[None, :])).astype(lhs.dtype)  # [m, E]
    return jnp.einsum("me,mk,ekn->mn", onehot, lhs, rhs,
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


def _gmm_pallas_probe(request) -> bool:
    return gmm_kernel_available(request["m"], request["k"], request["n"])


def _gmm_pallas_impl(request, lhs, rhs, group_sizes):
    return _gmm_pallas_diff(lhs, rhs, group_sizes)


def _gmm_blocked_probe(request) -> bool:
    return (request.get("block_aligned", False)
            and request["m"] % request.get("block_rows", 128) == 0)


def _gmm_blocked_impl(request, lhs, rhs, group_sizes):
    return _gmm_xla_blocked(lhs, rhs, group_sizes,
                            request.get("block_rows", 128))


def _gmm_ragged_probe(request) -> bool:
    return hasattr(lax, "ragged_dot")


def _gmm_ragged_impl(request, lhs, rhs, group_sizes):
    return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def _sweep_key_fields(req):
    return {"m": autotune.shape_bucket(req["m"]), "k": req["k"],
            "n": req["n"]}


def _sweep_candidates(req):
    # Same VMEM-budget model as the runtime lookup's validate: an
    # over-budget candidate could win the sweep (forced() bypasses
    # validation) but would be rejected on every real call.
    return [(tm, tn) for tm in (512, 256, 128) for tn in (512, 256, 128)
            if _tile_bytes(tm, tn, req["k"])
            <= tiling.DEFAULT_TILE_BUDGET_BYTES]


def _sweep_run(req, choice) -> float:
    m, k, n = req["m"], req["k"], req["n"]
    E = int(req.get("num_groups", 8))
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    key = jax.random.key(0)
    lhs = jax.random.normal(key, (m, k), jnp.float32).astype(dtype)
    rhs = jax.random.normal(key, (E, k, n), jnp.float32).astype(dtype)
    sizes = jnp.full((E,), m // E, jnp.int32)
    sizes = sizes.at[-1].add(m - int(m // E) * E)

    def loss(lhs, rhs):
        return jnp.sum(gmm(lhs, rhs, sizes).astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1)))
    return autotune.time_call(fn, lhs, rhs)


registry.register_kernel(
    "gmm.pallas", probe=_gmm_pallas_probe, impl=_gmm_pallas_impl,
    fallback="gmm.xla_blocked", reference=_gmm_reference)
registry.register_kernel(
    "gmm.xla_blocked", probe=_gmm_blocked_probe, impl=_gmm_blocked_impl,
    fallback="gmm.ragged", reference=_gmm_reference)
registry.register_kernel(
    "gmm.ragged", probe=_gmm_ragged_probe, impl=_gmm_ragged_impl,
    fallback=None)
autotune.register_sweep(
    "gmm", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
