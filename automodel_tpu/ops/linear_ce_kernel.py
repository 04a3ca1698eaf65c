"""Pallas fused linear + cross-entropy kernel: lm_head matmul, online
logsumexp and label-pick in one pass — the logits tensor never exists.

TPU port target named by SURVEY §2.9 items 2-3: the reference wraps Apple
cut-cross-entropy (``nemo_automodel/components/loss/linear_ce.py:118``) and
ships a Triton vocab-parallel CE (``loss/triton/te_cross_entropy.py:49-291``).
Here the same memory behaviour is a first-class Pallas kernel:

* **Forward** — one grid pass ``(rows/TM, vocab/TV)`` with the vocab tiles
  innermost: each step matmuls a ``[TM, H] x [H, TV]`` tile on the MXU and
  folds it into running ``(max, sumexp, picked-logit)`` scratch (flash-style
  online logsumexp), so peak memory is one tile instead of ``[T, V]``.
* **Backward** — recompute-based, two kernels (``bwd_mode="pallas"``, the
  default): ``dh`` accumulates over vocab tiles with the row tile resident;
  ``dw`` accumulates over row tiles with the vocab tile resident.  Both
  rebuild the logits tile on the MXU and apply ``dlogits = softmax * dlse +
  onehot * dpick`` in registers — 4 matmul units but zero intermediate HBM
  traffic, measured **263 ms/iter** for the full value_and_grad at Llama-1B
  shapes on v5e vs **1050 ms** for the checkpointed-scan loss (plain-matmul
  calibration: 62 ms/unit).  ``bwd_mode="xla"`` is a 3-unit chunk-scan
  recompute (287 ms — the materialized dlogits tiles cost more than the
  extra Pallas recompute unit); kept as the comparison point.

Vocab tails are masked in-kernel (columns >= V read -inf), so V only needs
lane alignment and tiles stay large for awkward vocabs (128256 = Llama-3).

The kernel boundary is ``lse_and_pick(h, w, labels) -> (lse, picked)``; CE
assembly (``sum(valid * (lse - picked))``) happens OUTSIDE in plain JAX.
That boundary makes vocab parallelism free: with ``w`` sharded ``[H, V/tp]``
each shard runs the same kernel on its slice and the caller combines the
per-shard ``lse``/``picked`` with psum collectives — the custom VJP's
``(dlse, dpick)`` cotangents are exactly what the combine's autodiff
produces, so no TP-specific backward is needed (see
``loss/linear_ce.py:_sharded_lse_pick``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling

# Pallas interpret mode: lets the CPU test suite execute the real kernel
# logic (tests monkeypatch this, mirroring ops/splash_attention.py).
_INTERPRET = False

_LANE = tiling.LANE
_NEG_INF = -1e30

# Mosaic's DEFAULT scoped-vmem budget is 16 MB, far under v5e's physical
# 128 MB — tile choices near the default ceiling failed to compile at some
# token counts (the pipeline's own buffering isn't in our estimate).  The
# substrate default (kernel_lib.tiling.compiler_params) raises the kernel
# limit to 64 MB, giving the static tile table real headroom.
_COMPILER_PARAMS = tiling.compiler_params()


def linear_ce_kernel_available(n_tokens: int, hidden: int, vocab: int) -> bool:
    """The kernel requires TPU (or interpret mode) and a lane-aligned H."""
    if hidden % _LANE:
        return False
    if _INTERPRET:
        return True
    return registry.on_tpu()


def _tile_bytes(tm: int, tv: int, hidden: int,
                acc_bytes_per_row: int = 0,
                acc_bytes_per_col: int = 0) -> int:
    """VMEM working set of one (TM, TV) tile pair: double-buffered h and w
    tiles + one f32 logits tile + any f32 accumulator the kernel keeps per
    row/col.  ONE byte model — shared by the runtime tile search/validate
    AND the sweep's candidate filter, so an estimate change can never let
    the sweep persist a winner the runtime would reject."""
    return (2 * tm * hidden * 2 + 2 * hidden * tv * 2
            + tm * tv * 4 + tm * acc_bytes_per_row
            + tv * acc_bytes_per_col)


def _tiles(n_tokens: int, hidden: int, vocab: int,
           acc_bytes_per_row: int = 0, acc_bytes_per_col: int = 0,
           budget: int = tiling.DEFAULT_TILE_BUDGET_BYTES) -> Tuple[int, int]:
    """(TM rows, TV vocab cols): the largest tile pair whose
    ``_tile_bytes`` working set fits the budget (``tiling.fit_tile_pair``).
    Grid steps have fixed Mosaic overhead (~5 us), so bigger tiles =
    closer to the MXU roofline (tail tiles are masked in-kernel, so no
    divisibility constraint beyond the 128 lane).  The budget works WITH
    the raised 64 MB ``vmem_limit_bytes`` (the estimate undercounts
    Mosaic's own pipeline buffering by ~2x); (1024, 512) everywhere
    measured 262 ms/iter for the Llama-1B value_and_grad vs 281 ms for the
    16 MB-era conservative tiles.  A persisted autotune winner (kernel key
    ``"linear_ce"``) overrides the budget search when it fits THIS call's
    accumulator budget."""
    def use(tm: int, tv: int) -> int:
        return _tile_bytes(tm, tv, hidden, acc_bytes_per_row,
                           acc_bytes_per_col)

    default = tiling.fit_tile_pair(
        n_tokens, (1024, 512, 256, 128), (512, 128), use, budget)
    fields = {"t": autotune.shape_bucket(n_tokens), "h": hidden, "v": vocab}
    return autotune.lookup(
        "linear_ce", fields, default,
        validate=lambda c: (len(c) == 2 and c[0] % _LANE == 0
                            and c[1] % _LANE == 0
                            and use(c[0], c[1]) <= budget))


def _masked_logits(h_ref, w_ref, j, v_actual):
    """One [TM, TV] logits tile; columns at/past the true vocab end get
    -inf so they vanish from max/exp/picked."""
    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    return tiling.mask_tail_columns(logits, j, v_actual, neg=_NEG_INF)


# ---------------------------------------------------------------------------
# Forward: online logsumexp + label pick
# ---------------------------------------------------------------------------
def _fwd_kernel(lab_ref, h_ref, w_ref, lse_ref, pick_ref, m_scr, s_scr, p_scr,
                *, v_actual: int):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    logits = _masked_logits(h_ref, w_ref, j, v_actual)
    tm, tv = logits.shape
    col = lab_ref[...] - j * tv                                # [TM, 1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (tm, tv), 1)
    hit = cols == col                                          # off-tile: none
    if v_actual % tv:   # out-of-shard labels must not hit a padded column
        hit = hit & (j * tv + cols < v_actual)
    pick_t = jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)
    lmax = jnp.max(logits, axis=1, keepdims=True)              # [TM, 1]

    @pl.when(j == 0)
    def _():
        m_scr[...] = lmax
        s_scr[...] = jnp.sum(jnp.exp(logits - lmax), axis=1, keepdims=True)
        p_scr[...] = pick_t

    @pl.when(j > 0)
    def _():
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, lmax)
        s_scr[...] = (s_scr[...] * jnp.exp(m_prev - m_new)
                      + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
        m_scr[...] = m_new
        p_scr[...] = p_scr[...] + pick_t

    @pl.when(j == nv - 1)
    def _():
        lse_ref[...] = m_scr[...] + jnp.log(s_scr[...])
        pick_ref[...] = p_scr[...]


def _pad_cols(w: jnp.ndarray, tv: int) -> jnp.ndarray:
    pad = (-w.shape[1]) % tv
    return jnp.pad(w, ((0, 0), (0, pad))) if pad else w


def _fwd_pallas(h: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray,
                tm: int, tv: int):
    t, hid = h.shape
    v = w.shape[1]
    wp = _pad_cols(w, tv)
    grid = (t // tm, wp.shape[1] // tv)
    lab2d = labels.reshape(t, 1).astype(jnp.int32)
    out_shape = [jax.ShapeDtypeStruct((t, 1), jnp.float32)] * 2
    lse, pick = pl.pallas_call(
        functools.partial(_fwd_kernel, v_actual=v),
        grid=grid,
        in_specs=[
            tiling.vmem_block_spec((tm, 1), lambda i, j: (i, 0)),
            tiling.vmem_block_spec((tm, hid), lambda i, j: (i, 0)),
            tiling.vmem_block_spec((hid, tv), lambda i, j: (0, j)),
        ],
        out_specs=[
            tiling.vmem_block_spec((tm, 1), lambda i, j: (i, 0)),
            tiling.vmem_block_spec((tm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tm, 1), jnp.float32)] * 3,
        cost_estimate=pl.CostEstimate(
            flops=2 * t * hid * v,
            bytes_accessed=(t // tm) * hid * v * w.dtype.itemsize
            + t * hid * h.dtype.itemsize,
            transcendentals=t * v,
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=_INTERPRET,
    )(lab2d, h, wp)
    return lse[:, 0], pick[:, 0]


# ---------------------------------------------------------------------------
# Backward kernels: dlogits = exp(logits - lse) * dlse + onehot * dpick
# ---------------------------------------------------------------------------
def _dlogits_tile(h_ref, w_ref, lab_ref, lse_ref, dlse_ref, dpick_ref, j,
                  v_actual):
    logits = _masked_logits(h_ref, w_ref, j, v_actual)
    tm, tv = logits.shape
    p = jnp.exp(logits - lse_ref[...])        # pad cols: exp(-inf) = 0
    col = lab_ref[...] - j * tv
    cols = jax.lax.broadcasted_iota(jnp.int32, (tm, tv), 1)
    hit = cols == col
    if v_actual % tv:   # out-of-shard labels must not hit a padded column
        hit = hit & (j * tv + cols < v_actual)
    return p * dlse_ref[...] + hit.astype(jnp.float32) * dpick_ref[...]


def _bwd_dh_kernel(lab_ref, lse_ref, dlse_ref, dpick_ref, h_ref, w_ref,
                   dh_ref, acc_scr, *, v_actual: int):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    dlog = _dlogits_tile(h_ref, w_ref, lab_ref, lse_ref, dlse_ref, dpick_ref,
                         j, v_actual)
    # [TM, TV] x [H, TV]^T -> [TM, H]; cast dlog to the weight dtype so the
    # contraction runs on the MXU.
    part = jax.lax.dot_general(
        dlog.astype(w_ref.dtype), w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        acc_scr[...] = part

    @pl.when(j > 0)
    def _():
        acc_scr[...] = acc_scr[...] + part

    @pl.when(j == nv - 1)
    def _():
        dh_ref[...] = acc_scr[...].astype(dh_ref.dtype)


def _bwd_dw_kernel(lab_ref, lse_ref, dlse_ref, dpick_ref, h_ref, w_ref,
                   dw_ref, acc_scr, *, v_actual: int):
    i = pl.program_id(1)            # rows INNER: the dw tile stays resident
    nt = pl.num_programs(1)
    j = pl.program_id(0)
    dlog = _dlogits_tile(h_ref, w_ref, lab_ref, lse_ref, dlse_ref, dpick_ref,
                         j, v_actual)
    # [TM, H]^T x [TM, TV] -> [H, TV]
    part = jax.lax.dot_general(
        h_ref[...], dlog.astype(h_ref.dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        acc_scr[...] = part

    @pl.when(i > 0)
    def _():
        acc_scr[...] = acc_scr[...] + part

    @pl.when(i == nt - 1)
    def _():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _bwd_pallas(h, w, labels, lse, dlse, dpick):
    t, hid = h.shape
    v = w.shape[1]
    lab2d = labels.reshape(t, 1).astype(jnp.int32)
    cols = (lse.reshape(t, 1), dlse.reshape(t, 1), dpick.reshape(t, 1))

    tm, tv = _tiles(t, hid, v, acc_bytes_per_row=hid * 4)
    wp = _pad_cols(w, tv)
    col1 = lambda i, j: (i, 0)
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, v_actual=v),
        grid=(t // tm, wp.shape[1] // tv),
        in_specs=[tiling.vmem_block_spec((tm, 1), col1)] * 4
        + [
            tiling.vmem_block_spec((tm, hid), lambda i, j: (i, 0)),
            tiling.vmem_block_spec((hid, tv), lambda i, j: (0, j)),
        ],
        out_specs=tiling.vmem_block_spec((tm, hid), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, hid), h.dtype),
        scratch_shapes=[pltpu.VMEM((tm, hid), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * t * hid * v,
            bytes_accessed=(t // tm) * hid * v * w.dtype.itemsize,
            transcendentals=t * v),
        compiler_params=_COMPILER_PARAMS,
        interpret=_INTERPRET,
    )(lab2d, *cols, h, wp)

    tm, tv = _tiles(t, hid, v, acc_bytes_per_col=hid * 4)
    wp = _pad_cols(w, tv)
    swap = lambda j, i: (i, 0)
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, v_actual=v),
        grid=(wp.shape[1] // tv, t // tm),
        in_specs=[tiling.vmem_block_spec((tm, 1), swap)] * 4
        + [
            tiling.vmem_block_spec((tm, hid), lambda j, i: (i, 0)),
            tiling.vmem_block_spec((hid, tv), lambda j, i: (0, j)),
        ],
        out_specs=tiling.vmem_block_spec((hid, tv), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((hid, wp.shape[1]), w.dtype),
        scratch_shapes=[pltpu.VMEM((hid, tv), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * t * hid * v,
            bytes_accessed=(wp.shape[1] // tv) * t * hid * h.dtype.itemsize,
            transcendentals=t * v),
        compiler_params=_COMPILER_PARAMS,
        interpret=_INTERPRET,
    )(lab2d, *cols, h, wp)
    return dh, dw[:, :v]


def _bwd_xla(h, w, labels, lse, dlse, dpick, chunk_rows: int):
    """Chunk-scan recompute backward: one logits tile per scan step in XLA.
    Kept as a measurable alternative to the Pallas backward (3 matmul units
    + materialized tiles vs 4 units + none)."""
    t, hid = h.shape
    c = chunk_rows
    n = t // c

    def body(dw_acc, args):
        hc, labc, lsec, dlsec, dpickc = args
        logits = jnp.dot(hc, w, preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lsec[:, None])
        onehot = jax.nn.one_hot(labc, w.shape[1], dtype=jnp.float32)
        dlog = (p * dlsec[:, None] + onehot * dpickc[:, None]).astype(h.dtype)
        dhc = jnp.dot(dlog, w.T, preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jax.lax.dot_general(
            hc, dlog, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw_acc, dhc.astype(h.dtype)

    args = (h.reshape(n, c, hid), labels.reshape(n, c), lse.reshape(n, c),
            dlse.reshape(n, c), dpick.reshape(n, c))
    dw, dh = jax.lax.scan(body, jnp.zeros(w.shape, jnp.float32), args)
    return dh.reshape(t, hid), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# custom_vjp boundary
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def lse_and_pick(h: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray,
                 bwd_mode: str = "pallas"):
    """``(logsumexp(h @ w, -1), (h @ w)[labels])`` per row, fused.

    ``h`` [T, H], ``w`` [H, V], ``labels`` [T] int (out-of-range labels —
    ignore-index rows or other shards' vocab — pick 0).  T is padded to the
    row tile and V to the vocab tile internally; H must be 128-aligned
    (``linear_ce_kernel_available``).
    """
    return _fwd(h, w, labels, bwd_mode)[0]


def _pad_rows(h, labels, tm):
    t = h.shape[0]
    pad = (-t) % tm
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    return h, labels, t


def _fwd(h, w, labels, bwd_mode):
    tm, tv = _tiles(h.shape[0], h.shape[1], w.shape[1])
    hp, labp, t = _pad_rows(h, labels, tm)
    lse, pick = _fwd_pallas(hp, w.astype(h.dtype), labp, tm, tv)
    return (lse[:t], pick[:t]), (h, w, labels, lse)


def _bwd(bwd_mode, res, cot):
    h, w, labels, lse_pad = res
    dlse, dpick = cot
    tm, _ = _tiles(h.shape[0], h.shape[1], w.shape[1])
    hp, labp, t = _pad_rows(h, labels, tm)
    pad = hp.shape[0] - t
    if pad:
        dlse = jnp.pad(dlse, (0, pad))
        dpick = jnp.pad(dpick, (0, pad))
    wd = w.astype(h.dtype)
    if bwd_mode == "xla":
        dh, dw = _bwd_xla(hp, wd, labp, lse_pad, dlse, dpick,
                          chunk_rows=min(tm, hp.shape[0]))
    else:
        dh, dw = _bwd_pallas(hp, wd, labp, lse_pad, dlse, dpick)
    return (dh[:t].astype(h.dtype), dw.astype(w.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


lse_and_pick.defvjp(lambda h, w, labels, bwd_mode: _fwd(h, w, labels, bwd_mode),
                    _bwd)


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _lce_probe(request) -> bool:
    return linear_ce_kernel_available(request["t"], request["h"],
                                      request["v"])


def _lce_impl(request, h, w, labels):
    return lse_and_pick(h, w, labels, request.get("bwd_mode", "pallas"))


def _sweep_key_fields(req):
    return {"t": autotune.shape_bucket(req["t"]), "h": req["h"],
            "v": req["v"]}


def _sweep_candidates(req):
    # Only candidates every runtime lookup can accept: the strictest
    # role's accumulator (dh keeps a [TM, H] fp32 scratch) must fit the
    # budget, else the persisted "winner" would be validate-rejected on
    # each call and the sweep's cost never pays out.
    hd = req["h"]
    out = []
    for tm in (1024, 512, 256, 128):
        for tv in (512, 256, 128):
            if (tm <= -(-req["t"] // _LANE) * _LANE
                    and _tile_bytes(tm, tv, hd, acc_bytes_per_row=hd * 4)
                    <= tiling.DEFAULT_TILE_BUDGET_BYTES):
                out.append((tm, tv))
    return out


def _sweep_run(req, choice) -> float:
    t, hd, v = req["t"], req["h"], req["v"]
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    key = jax.random.key(0)
    h = jax.random.normal(key, (t, hd), jnp.float32).astype(dtype)
    w = (jax.random.normal(key, (hd, v), jnp.float32) * 0.05).astype(dtype)
    labels = jax.random.randint(key, (t,), 0, v, jnp.int32)

    def loss(h, w):
        lse, pick = lse_and_pick(h, w, labels, "pallas")
        return jnp.sum(lse - pick)

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    return autotune.time_call(fn, h, w)


from automodel_tpu.ops.kernel_lib.parity import (  # noqa: E402
    dense_lse_pick_reference,
)

registry.register_kernel(
    "linear_ce.pallas", probe=_lce_probe, impl=_lce_impl,
    fallback="linear_ce.chunked", reference=dense_lse_pick_reference)
autotune.register_sweep(
    "linear_ce", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
