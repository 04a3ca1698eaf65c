"""Pallas power retention — the ``attention.retention_decode`` and
``attention.retention_chunk`` rungs (``ops/power_retention.py`` owns the
contract, the embedding and the state's layout).

Both walk the state the same way: grid ``(row, kv head)``; the unit's whole
state ``S [O, dv, d]`` (4.26 MB at ``d`` = 128) comes into VMEM once, is
scaled by the gate, gets ``v (x) phi_k``, serves the group's queries and
goes back to the SAME tile of the stacked plane (``input_output_aliases``:
the planes are donated and updated in place; the layer rides scalar
prefetch, a rung addresses the stacked plane AT the layer).  While a unit
is computed the next one's tile is on its way in and the last one's on its
way out, so the kernel runs at the speed the state can be read and written
as long as a unit's arithmetic takes less than its 8.5 MB of traffic.
``phi`` is formed in VMEM from ``k`` and ``q`` by lane rotations
(``pltpu.roll``), one offset a row; it never exists in HBM.

* **decode** (one token a row): all on the vector unit, float32 exact.  Per
  offset ``o`` and block of 32 value rows: ``S <- g S + v (x) phi_k[o]``
  (the row of ``phi_k`` broadcast over sublanes, ``v`` lane-broadcast once
  a unit), then one multiply-add a query head against the row of
  ``phi_q[o]``; the lane sums wait until every offset is in.  No matrix
  unit: with one token the state would be the stationary operand, loaded
  65 times a unit for 5 rows each.
* **chunk** (a ``[rows, C]`` step buffer, ragged valid lengths): the decay
  is folded into small operands outside (``exp(G_t)`` a query row, ``exp(G_C
  - G_s) v_s`` a key row, the chunk's own ``[C, C]`` decay matrix: all
  ``[B, Hk, C]``-sized, made by XLA), so per offset the kernel runs two
  products on the matrix unit — ``phi_q[o] S[o]^T`` (``[G C, d] x [d,
  dv]``) for the read-out through the state and ``[v w ; w]^T phi_k[o]``
  (``[dv + 8, C] x [C, d]``) for the state's and the normaliser's advance —
  and after the walk the chunk's own pairs ``(Q K^T)^2 * decay`` against
  ``V``.  Padding columns carry ``k = 0`` and weight 0; an idle row keeps
  its state (scaled by 1, plus nothing).

A row that starts from zero gets ``g = 0`` (decode) or ``exp(G) = 0``
(chunk) from outside: the state is finite always (zeros at build, finite
updates), so scaling by 0 IS the reset and no select touches the tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.ops.kernel_lib import registry, tiling
from automodel_tpu.ops.power_retention import (
    F32,
    norm_rows,
    num_offsets,
    offset_weights,
    retention_reference,
)

# Pallas interpret mode: the CPU suite runs the real kernel logic.
_INTERPRET = False

_D = 128             # the head size both kernels are written for
_Q_PAD = 8           # a group's queries, padded to a sublane tile (decode)
_V_ROWS = 32         # value rows per accumulator block (decode)
_MAX_CHUNK = 128
_PRECISION = lax.Precision.HIGHEST


def retention_available(request) -> bool:
    g = request["num_q_heads"] // max(1, request["num_kv_heads"])
    if (request["head_dim"] != _D or request["value_dim"] != _D
            or request["state_dtype"] != "float32"
            or request["num_q_heads"] % request["num_kv_heads"]
            or g > _Q_PAD):
        return False
    c = request["q_seq"]
    if c > 1 and (c % 8 or c > _MAX_CHUNK):
        return False
    if _INTERPRET:
        return True
    return registry.on_tpu()


def _roll_back(x, o: int):
    """``x[..., (l + o) % d]`` at lane ``l``."""
    from jax.experimental.pallas import tpu as pltpu

    if o == 0:
        return x
    return pltpu.roll(x, shift=(_D - o) % _D, axis=x.ndim - 1)


def _state_specs(O: int):
    """Block specs of one (row, kv head) unit of the stacked planes at the
    prefetched layer."""
    state = tiling.block_spec(
        (1, 1, 1, O, _D, _D), lambda b, h, ly: (ly[0], b, h, 0, 0, 0))
    norm = tiling.block_spec(
        (1, 1, 1, norm_rows(_D), _D), lambda b, h, ly: (ly[0], b, h, 0, 0))
    return state, norm


def _unit_spec(rows: int, cols: int):
    return tiling.block_spec((1, 1, rows, cols),
                             lambda b, h, ly: (b, h, 0, 0))


# ---------------------------------------------------------------------------
# decode: one token a row
# ---------------------------------------------------------------------------
def _decode_kernel(ly_ref, q_ref, k_ref, v_ref, g_ref, s_ref, z_ref,
                   o_ref, so_ref, zo_ref, phik, phiq, *, G: int, O: int):
    from jax.experimental import pallas as pl

    w = offset_weights(_D)
    k = k_ref[0, 0]                                         # (1, d)
    q = q_ref[0, 0]                                         # (8, d)
    g_row = g_ref[0, 0]                                     # (1, d): g
    for o in range(O):
        phik[pl.ds(o, 1), :] = k * _roll_back(k, o)
        pq = (q * _roll_back(q, o)) * float(w[o])
        for g in range(G):
            phiq[g, pl.ds(o, 1), :] = pq[g:g + 1, :]
    zn = z_ref[0, 0, 0, pl.ds(0, O), :] * g_row + phik[pl.ds(0, O), :]
    zo_ref[0, 0, 0, pl.ds(0, O), :] = zn                    # (O, d)
    zo_ref[0, 0, 0, pl.ds(O, norm_rows(_D) - O), :] = jnp.zeros(
        (norm_rows(_D) - O, _D), F32)
    inv = [1.0 / jnp.sum(phiq[g, pl.ds(0, O), :] * zn, axis=(0, 1),
                         keepdims=True) for g in range(G)]  # (1, 1) each
    for blk in range(_D // _V_ROWS):
        rows = pl.ds(blk * _V_ROWS, _V_ROWS)
        v_b = jnp.broadcast_to(v_ref[0, 0, rows, :], (_V_ROWS, _D))

        def walk(o, accs):
            sn = s_ref[0, 0, 0, o, rows, :] * g_row \
                + v_b * phik[pl.ds(o, 1), :]
            so_ref[0, 0, 0, o, rows, :] = sn
            return tuple(a + sn * phiq[g, pl.ds(o, 1), :]
                         for g, a in enumerate(accs))

        accs = lax.fori_loop(
            0, O, walk,
            tuple(jnp.zeros((_V_ROWS, _D), F32) for _ in range(G)))
        for g in range(G):
            o_ref[0, 0, rows, g:g + 1] = jnp.sum(
                accs[g], axis=1, keepdims=True) * inv[g]
    if G < _Q_PAD:
        o_ref[0, 0, :, G:] = jnp.zeros((_D, _Q_PAD - G), F32)


def retention_decode_pallas(q, k, v, log_g, state, norm, layer, n_valid,
                            reset):
    """``q [B, 1, Hq, d]``, ``k, v [B, 1, Hk, d]``, ``log_g [B, 1, Hk]`` over
    layer ``layer`` of the stacked planes -> ``(o [B, 1, Hq, d], state,
    norm)`` (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, Hq, d = q.shape
    Hk = k.shape[2]
    G, O = Hq // Hk, num_offsets(d)
    live = n_valid > 0
    g = jnp.where(reset[:, None], 0.0, jnp.exp(
        jnp.where(live[:, None], log_g[:, 0].astype(F32), 0.0)))  # [B, Hk]
    kk = jnp.where(live[:, None, None], k[:, 0].astype(F32), 0.0)
    qq = (q[:, 0].astype(F32) * d ** -0.5).reshape(B, Hk, G, d)
    qq = jnp.pad(qq, ((0, 0), (0, 0), (0, _Q_PAD - G), (0, 0)))
    state_spec, norm_spec = _state_specs(O)
    out, state, norm = pl.pallas_call(
        functools.partial(_decode_kernel, G=G, O=O),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=1,
            grid=(B, Hk),
            in_specs=[_unit_spec(_Q_PAD, d), _unit_spec(1, d),
                      _unit_spec(d, 1), _unit_spec(1, d),
                      state_spec, norm_spec],
            out_specs=[_unit_spec(d, _Q_PAD), state_spec, norm_spec],
            scratch_shapes=[
                pltpu.VMEM((norm_rows(d), d), F32),
                pltpu.VMEM((G, norm_rows(d), d), F32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, Hk, d, _Q_PAD), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operands count the scalar prefetch: 5 = state, 6 = norm
        input_output_aliases={5: 1, 6: 2},
        compiler_params=tiling.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
    )(jnp.asarray(layer, jnp.int32).reshape(1), qq, kk[:, :, None, :],
      v[:, 0].astype(F32)[..., None],
      jnp.broadcast_to(g[..., None, None], (B, Hk, 1, d)), state, norm)
    o = out[..., :G].transpose(0, 1, 3, 2).reshape(B, 1, Hq, d)
    o = jnp.where(live[:, None, None, None], o, 0.0)    # idle: 0 / 0
    return o.astype(q.dtype), state, norm


# ---------------------------------------------------------------------------
# chunk: a [rows, C] step buffer
# ---------------------------------------------------------------------------
def _chunk_kernel(ly_ref, q_ref, k_ref, v_ref, vw_ref, dec_ref, eq_ref,
                  gl_ref, s_ref, z_ref, o_ref, so_ref, zo_ref, phik, phiq,
                  num, den, *, O: int):
    from jax.experimental import pallas as pl

    w = offset_weights(_D)
    k = k_ref[0, 0]                                         # (C, d)
    q = q_ref[0, 0]                                         # (G C, d)
    gl = gl_ref[0, 0]                                       # (1, d)
    vw = vw_ref[0, 0]                                       # (dv + 8, C)
    for o in range(O):
        phik[o] = k * _roll_back(k, o)
        phiq[o] = (q * _roll_back(q, o)) * float(w[o])
    num[...] = jnp.zeros_like(num)
    den[...] = jnp.zeros_like(den)

    def walk(o, carry):
        s_o = s_ref[0, 0, 0, o]                             # (dv, d)
        z_o = z_ref[0, 0, 0, pl.ds(o, 1), :]                # (1, d)
        pq = phiq[o]
        num[...] += lax.dot_general(
            pq, s_o, (((1,), (1,)), ((), ())), precision=_PRECISION,
            preferred_element_type=F32)                     # (G C, dv)
        den[...] += pq * z_o
        upd = lax.dot_general(
            vw, phik[o], (((1,), (0,)), ((), ())), precision=_PRECISION,
            preferred_element_type=F32)                     # (dv + 8, d)
        so_ref[0, 0, 0, o] = s_o * gl + upd[:_D]
        zo_ref[0, 0, 0, pl.ds(o, 1), :] = z_o * gl + upd[_D:_D + 1]
        return carry

    lax.fori_loop(0, O, walk, 0)
    zo_ref[0, 0, 0, pl.ds(O, norm_rows(_D) - O), :] = jnp.zeros(
        (norm_rows(_D) - O, _D), F32)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        precision=_PRECISION, preferred_element_type=F32)
    a = s * s * dec_ref[0, 0]                               # (G C, C)
    eq = eq_ref[0, 0]                                       # (G C, 1)
    top = eq * num[...] + lax.dot_general(
        a, v_ref[0, 0], (((1,), (0,)), ((), ())), precision=_PRECISION,
        preferred_element_type=F32)
    bot = (eq * jnp.sum(den[...], axis=1, keepdims=True)
           + jnp.sum(a, axis=1, keepdims=True))
    o_ref[0, 0] = top / jnp.where(bot > 0.0, bot, 1.0)


def retention_chunk_pallas(q, k, v, log_g, state, norm, layer, n_valid,
                           reset):
    """``q [B, C, Hq, d]``, ``k, v [B, C, Hk, d]``, ``log_g [B, C, Hk]`` over
    layer ``layer`` of the stacked planes -> ``(o [B, C, Hq, d], state,
    norm)`` (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, C, Hq, d = q.shape
    Hk = k.shape[2]
    G, O = Hq // Hk, num_offsets(d)
    col = jnp.arange(C, dtype=jnp.int32)
    valid = col[None, :] < n_valid[:, None]                 # [B, C]
    lg = jnp.where(valid[..., None], log_g.astype(F32), 0.0)
    Gc = jnp.cumsum(lg, axis=1).transpose(0, 2, 1)          # [B, Hk, C]
    fresh = reset[:, None, None]
    gl = jnp.where(fresh, 0.0, jnp.exp(Gc[..., -1:]))       # [B, Hk, 1]
    eq = jnp.where(fresh, 0.0, jnp.exp(Gc))                 # [B, Hk, C]
    wk = jnp.where(valid[:, None, :], jnp.exp(Gc[..., -1:] - Gc), 0.0)
    pair = (col[:, None] >= col[None, :])[None] & valid[:, None, :]
    dec = jnp.exp(jnp.where(pair[:, None], Gc[..., :, None]
                            - Gc[..., None, :], -jnp.inf))  # [B, Hk, t, s]
    kk = jnp.where(valid[..., None, None], k.astype(F32), 0.0)
    vv = v.astype(F32).transpose(0, 2, 1, 3)                # [B, Hk, C, dv]
    vw = jnp.concatenate([
        (vv * wk[..., None]).transpose(0, 1, 3, 2), wk[:, :, None, :],
        jnp.zeros((B, Hk, 7, C), F32)], axis=2)             # [B,Hk,dv+8,C]
    qq = (q.astype(F32) * d ** -0.5).reshape(B, C, Hk, G, d).transpose(
        0, 2, 3, 1, 4).reshape(B, Hk, G * C, d)             # head-major rows
    state_spec, norm_spec = _state_specs(O)
    out, state, norm = pl.pallas_call(
        functools.partial(_chunk_kernel, O=O),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=1,
            grid=(B, Hk),
            in_specs=[_unit_spec(G * C, d), _unit_spec(C, d),
                      _unit_spec(C, d), _unit_spec(d + 8, C),
                      _unit_spec(G * C, C), _unit_spec(G * C, 1),
                      _unit_spec(1, d), state_spec, norm_spec],
            out_specs=[_unit_spec(G * C, d), state_spec, norm_spec],
            scratch_shapes=[
                pltpu.VMEM((O, C, d), F32),
                pltpu.VMEM((O, G * C, d), F32),
                pltpu.VMEM((G * C, d), F32),
                pltpu.VMEM((G * C, d), F32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, Hk, G * C, d), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operands count the scalar prefetch: 8 = state, 9 = norm
        input_output_aliases={8: 1, 9: 2},
        compiler_params=tiling.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
    )(jnp.asarray(layer, jnp.int32).reshape(1), qq,
      kk.transpose(0, 2, 1, 3), vv, vw, jnp.tile(dec, (1, 1, G, 1)),
      jnp.tile(eq, (1, 1, G))[..., None],
      jnp.broadcast_to(gl[..., None], (B, Hk, 1, d)), state, norm)
    o = out.reshape(B, Hk, G, C, d).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, C, Hq, d).astype(q.dtype), state, norm


# ---------------------------------------------------------------------------
def _decode_impl(request, q, k, v, log_g, state, norm, layer, n_valid, reset):
    # XLA:TPU names a Mosaic custom call after the innermost component of
    # its scope path: ``retention_decode`` is the name to read in a trace.
    with jax.named_scope("retention_decode"):
        return retention_decode_pallas(q, k, v, log_g, state, norm, layer,
                                       n_valid, reset)


def _chunk_impl(request, q, k, v, log_g, state, norm, layer, n_valid, reset):
    with jax.named_scope("retention_chunk"):
        return retention_chunk_pallas(q, k, v, log_g, state, norm, layer,
                                      n_valid, reset)


registry.register_kernel(
    "attention.retention_decode", probe=retention_available,
    impl=_decode_impl, fallback="attention.retention_decode_xla",
    reference=retention_reference)
registry.register_kernel(
    "attention.retention_chunk", probe=retention_available,
    impl=_chunk_impl, fallback="attention.retention_chunk_xla",
    reference=retention_reference)
