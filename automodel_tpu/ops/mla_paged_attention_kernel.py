"""Pallas MLA paged decode — the ``attention.mla_paged_decode`` rung.

MQA over the engine's latent cache (``ops/mla_paged_attention.py`` owns the
contract): one cached row of ``R`` values per token serves every query
head as key, and its first ``value_dim`` values as value.  So the kernel's
unit of work is a ROW of the batch, not a (row, kv head) pair:

* grid ``(B, S / sq)``: one row's query tokens in tiles of ``sq`` tokens,
  all ``Hq`` heads of a token folded into the tile's rows (``[sq * Hq, R]``,
  no transpose: the heads are the minor axis of ``q`` already).  Decode
  (``S = 1``) is one tile of ``Hq`` rows; a prefill chunk is a few tiles,
  and a tile wholly past the row's valid tokens (a decode row riding a
  mixed step has ONE, the rest of its columns are padding) is skipped;
* the pool stays in HBM (``memory_space=ANY``).  Per tile the kernel walks
  the row's context in chunks of ``_CHUNK`` tokens: the pages of a chunk
  are DMA'd (block table and layer ride scalar prefetch) into one of two
  VMEM buffers while the other is computed on, and the walk stops at the
  last key the tile may see — a loop bound, not a grid axis, so a short
  row costs no grid steps for the context it lacks;
* per chunk one ``[sq * Hq, R] x [chunk, R]^T`` score product, the
  flash-style online softmax in VMEM scratch, and one ``[sq * Hq, chunk] x
  [chunk, value_dim]`` product against the SAME buffer's leading columns:
  the latent is read once.

Per-query causality comes from each row's first position (the engine
writes a row's step tokens at consecutive positions) and the number of
valid tokens from the last (pad columns repeat the last valid position).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import registry, tiling
from automodel_tpu.ops.mla_paged_attention import mla_paged_reference

# Pallas interpret mode: the CPU suite runs the real kernel logic.
_INTERPRET = False

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
_MAX_Q = 64          # the small-q rung: decode and chunked prefill
_Q_ROWS = 512        # query rows (tokens x heads) of one tile
_CHUNK = 512         # cached tokens fetched and computed at a time


def mla_decode_available(q_seq: int, latent_dim: int, value_dim: int) -> bool:
    if not 1 <= q_seq <= _MAX_Q or value_dim % tiling.LANE \
            or latent_dim % tiling.LANE or latent_dim <= value_dim:
        return False
    if _INTERPRET:
        return True
    return registry.on_tpu()


def _q_tile(s: int, hq: int) -> int:
    """Query tokens per tile: the most that keep ``sq * hq`` rows under
    ``_Q_ROWS``, divide ``s`` and keep the tile's rows sublane-aligned;
    else the whole of ``s`` in one tile."""
    for sq in range(min(s, max(1, _Q_ROWS // hq)), 0, -1):
        if s % sq == 0 and (sq * hq) % 16 == 0:
            return sq
    return s


def _mla_kernel(bt_ref, cl_ref, p0_ref, nv_ref, ly_ref, q_ref, pool_ref,
                o_ref, buf, sems, m_ref, l_ref, acc_ref, *, bs, ppc, hq, sq,
                vdim, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t = pl.program_id(0), pl.program_id(1)
    ctx, pos0, nv, ly = cl_ref[b], p0_ref[b], nv_ref[b], ly_ref[0]
    tok0 = t * sq
    ck = ppc * bs
    rows = sq * hq

    @pl.when(tok0 >= nv)
    def _padding():                 # every token of the tile is a pad column
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(tok0 < nv)
    def _tile():
        # keys the tile's last valid token may see: positions < kv_hi
        kv_hi = jnp.minimum(ctx, pos0 + jnp.minimum(tok0 + sq, nv))
        n_chunks = (kv_hi + ck - 1) // ck

        def copies(c, slot):
            return [pltpu.make_async_copy(
                pool_ref.at[ly, bt_ref[b, c * ppc + p]],
                buf.at[slot, pl.ds(p * bs, bs)], sems.at[slot])
                for p in range(ppc)]

        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for cp in copies(0, 0):
            cp.start()
        q = q_ref[0]                                        # (rows, R)

        def chunk(c, carry):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                for cp in copies(c + 1, 1 - slot):
                    cp.start()

            for cp in copies(c, slot):
                cp.wait()
            k = buf[slot]                                   # (ck, R)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, ck)
            kv_pos = c * ck + jax.lax.broadcasted_iota(
                jnp.int32, (rows, ck), 1)
            qpos = pos0 + tok0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, ck), 0) // hq
            s = jnp.where((kv_pos < ctx) & (kv_pos <= qpos), s, _NEG_INF)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :vdim], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (rows, vdim)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, n_chunks, chunk, 0)
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def mla_paged_decode_pallas(q, pool, layer, block_tables, context_lens,
                            positions, *, value_dim: int, scale: float):
    """``q [B, S, Hq, R]`` over layer ``layer`` of ``pool [L, NB, BS, R]``
    -> ``[B, S, Hq, value_dim]`` (module docstring)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hq, R = q.shape
    _, _, BS, _ = pool.shape
    MB = block_tables.shape[1]
    assert S <= _MAX_Q, "mla_paged_decode is the small-q rung"
    sq = _q_tile(S, Hq)
    rows = sq * Hq
    ppc = max(1, min(_CHUNK // BS, MB))
    # whole chunks of pages: the table's tail reads the null page
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, -MB % ppc)))
    pos = positions.astype(jnp.int32)

    def q_index(b, t, *_):
        return (b, t, 0)

    out = pl.pallas_call(
        functools.partial(_mla_kernel, bs=BS, ppc=ppc, hq=Hq, sq=sq,
                          vdim=value_dim, scale=float(scale)),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=5,
            grid=(B, S // sq),
            in_specs=[
                tiling.block_spec((1, rows, R), q_index),
                tiling.block_spec(memory_space=pl.ANY),
            ],
            out_specs=tiling.block_spec((1, rows, value_dim), q_index),
            scratch_shapes=[
                pltpu.VMEM((2, ppc * BS, R), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, S * Hq, value_dim), q.dtype),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
    )(tables, context_lens.astype(jnp.int32), pos[:, 0],
      pos[:, -1] - pos[:, 0] + 1, jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, S * Hq, R).astype(pool.dtype), pool)
    return out.reshape(B, S, Hq, value_dim)


def _mla_decode_probe(request) -> bool:
    return mla_decode_available(request["q_seq"], request["latent_dim"],
                                request["value_dim"])


def _mla_decode_impl(request, q, pool, layer, block_tables, context_lens,
                     positions, *, value_dim: int, scale: float):
    # XLA:TPU names a Mosaic custom call after the innermost component of
    # its scope path: ``mla_decode`` is the name to read in a trace.
    with jax.named_scope("mla_decode"):
        return mla_paged_decode_pallas(
            q, pool, layer, block_tables, context_lens, positions,
            value_dim=value_dim, scale=scale)


registry.register_kernel(
    "attention.mla_paged_decode", probe=_mla_decode_probe,
    impl=_mla_decode_impl, fallback="attention.mla_paged_gather",
    reference=mla_paged_reference)
