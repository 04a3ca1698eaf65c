"""Paged attention over a block-paged KV cache — the serving decode path.

The serving engine (``automodel_tpu/serving``) keeps every request's KV
history in fixed-size *blocks* of a static pool, ``[num_blocks,
block_size, Hk, D]`` per layer and stacked over the layers; a per-request
*block table* names which pool blocks hold its positions
``0..context_len-1`` (position ``p`` lives in slot ``p % block_size`` of
block ``table[p // block_size]``, in every layer alike).  Attention over
that layout is its own kernel family on the PR-7 substrate:

* ``attention.paged_decode`` — Pallas gather-by-block-table online-softmax
  decode (``ops/paged_attention_kernel.py``): the block table rides scalar
  prefetch so BlockSpec index maps DMA exactly the pages a row owns, with
  wholly-past-the-context pages skipped.  Small queries (the decode hot
  path at S=1, the speculative verify step at S=spec_k+1, and chunked
  prefill) — the S query tokens fold into the query-group dim, with
  per-query causality derived from each row's FIRST position (queries are
  consecutive by the contract below).
* ``attention.paged_gather`` — the XLA anchor registered HERE: gather the
  pool by block table, mask by per-token positions + context lengths, SDPA.
  Always available (CPU test path, chunked-prefill queries of any length,
  GSPMD-correct), and structurally distinct from the parity harness's
  ``reference`` (dense per-row reconstruction + vmapped
  ``dot_product_attention``), so the two can actually disagree.

Both rungs speak one request/operand contract (:func:`paged_attention`):

* ``q [B, S, Hq, D]`` — per-row query tokens at CONSECUTIVE positions
  ``positions[b, t]`` (pad columns repeat the last valid position and are
  discarded by the caller);
* ``k_pool / v_pool [L, NB, BS, Hk, D]`` — the position-major pools of
  ALL layers, stacked, optionally int8 with per-slot-per-head scale planes
  ``[L, NB, BS, Hk]`` (the quantized KV cache, see
  ``serving/kv_cache.py``), and ``layer`` — an int32 scalar, traced or
  not, naming the layer to attend.  A pool of fewer kv heads than its
  dtype's sublane packing is stored as ROWS, ``[L, NB, BS, Hk * D]``: the
  same bytes in the same order, whose ``(BS, Hk * D)`` page fills the
  chip's tiles where a ``(Hk, D)`` slice would fill a fraction of one
  (:func:`stores_rows` decides; :func:`kv_heads` reads ``Hk`` from either
  form).  A rung addresses the stacked pool AT the layer (page ``layer *
  NB + block``) and never takes ``pool[layer]`` first: inside the engine's
  layer scan the pools are the loop's carry, and a slice of them would be
  a copy of one layer per layer per step.  A caller with one layer's pools
  passes ``L = 1, layer = 0``;
* ``block_tables [B, MB]`` int32, ``context_lens [B]`` int32 (valid
  positions INCLUDING tokens written this step).  Under a static
  ``local_window_size`` the entries before :func:`window_first_block` of a
  row's first query are never read (the engine's window group has released
  those blocks and left the null page there): the Pallas rung starts its
  walk at that entry and walks :func:`window_span_blocks` entries, the
  gather anchor reads and masks them.  Rows must satisfy
  ``context_lens >= 1`` and ``positions >= 0`` so every query has at least
  one attendable key (softmax never sees an all-masked row).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import registry

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def window_span_blocks(window: int, q_len: int, block_size: int) -> int:
    """The most blocks that hold the keys ``q_len`` consecutive queries see
    through a window of ``window`` keys: ``window + q_len - 1`` consecutive
    positions, wherever they start in a block.  What a window layer's walk
    is long, and the most blocks of a window group a row holds at a step
    of that width (``ceil(window / block_size) + 1`` at decode)."""
    return -(-(window + q_len - 2) // block_size) + 1


def window_first_block(first_position, window: int, block_size: int):
    """The table index of the first block that the query at
    ``first_position`` (a row's first this step; int or array) sees through
    a window of ``window`` keys: every block before it lies wholly behind
    the window, for this query and for every later one."""
    behind = first_position - window + 1
    return behind * (behind > 0) // block_size


def stores_rows(num_kv_heads: int, dtype, quantized: bool) -> bool:
    """Whether a per-head pool of ``num_kv_heads`` heads in ``dtype`` is
    stored as rows ``[.., BS, Hk * D]``: an unquantized pool whose heads are
    fewer than the dtype's sublane packing (16 for bfloat16, 8 for
    float32).  Such a pool's ``(Hk, D)`` slice of one slot would fill a
    fraction of a ``(packing, 128)`` tile; its ``(BS, Hk * D)`` page fills
    whole ones.  An int8 pool keeps its heads: its scale planes are
    indexed by head."""
    return not quantized and num_kv_heads < 4 // jnp.dtype(dtype).itemsize * 8


def kv_heads(pool: jnp.ndarray, head_dim: int) -> int:
    """``Hk`` of a per-head pool, ``[.., Hk, D]`` or rows ``[.., Hk * D]``."""
    return pool.shape[3] if pool.ndim == 5 else pool.shape[3] // head_dim


def dequantize_pool(pool: jnp.ndarray, scale: Optional[jnp.ndarray],
                    dtype=jnp.float32) -> jnp.ndarray:
    """int8 pool [..., Hk, D] * per-slot scale [..., Hk] -> compute dtype;
    non-quantized pools pass through (cast only)."""
    if scale is None:
        return pool.astype(dtype)
    return pool.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def gathered_cache(pool: jnp.ndarray, scale: Optional[jnp.ndarray], layer,
                   block_tables: jnp.ndarray, dtype=jnp.float32,
                   head_dim: Optional[int] = None):
    """Linearize a row's blocks of layer ``layer`` of the stacked pool by
    position: ``[B, MB*BS, Hk, D]``.

    Because block tables are position-major (position ``p`` -> slot ``p %
    BS`` of ``table[p // BS]``), gathering blocks in table order IS the
    dense per-row cache reconstruction.  The gather runs over the pool's
    ``[L*NB, ...]`` pages at ``layer * NB + table``, so no layer of the
    pool is ever materialised.  A pool stored as rows (``head_dim``
    given, a 4-D pool) is split into its heads after the gather.
    """
    L, NB = pool.shape[:2]
    pages = jnp.asarray(layer, jnp.int32) * NB + block_tables
    g = pool.reshape(L * NB, *pool.shape[2:])[pages]   # [B, MB, BS, Hk, D]
    if head_dim is not None and g.ndim == 4:
        g = g.reshape(*g.shape[:3], -1, head_dim)
    gs = None if scale is None else scale.reshape(
        L * NB, *scale.shape[2:])[pages]
    B, MB, BS = g.shape[:3]
    g = dequantize_pool(g, gs, dtype).reshape(B, MB * BS, *g.shape[3:])
    return g


def _paged_gather_impl(request, q, k_pool, v_pool, k_scale, v_scale, layer,
                       block_tables, context_lens, positions, *,
                       scale=None, logits_soft_cap=None,
                       local_window_size=None, kernel_name=None):
    """XLA anchor: gather-by-table + masked SDPA, any query length."""
    B, S, Hq, D = q.shape
    Hk = kv_heads(k_pool, D)
    assert Hq % Hk == 0, f"query heads {Hq} not a multiple of kv heads {Hk}"
    G = Hq // Hk
    scale = D ** -0.5 if scale is None else scale

    keys = gathered_cache(k_pool, k_scale, layer, block_tables,
                          head_dim=D)                       # [B,K,Hk,D]
    vals = gathered_cache(v_pool, v_scale, layer, block_tables, head_dim=D)
    K = keys.shape[1]

    qg = q.reshape(B, S, Hk, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        keys, precision=jax.lax.Precision.DEFAULT) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

    kv_pos = jnp.arange(K, dtype=jnp.int32)
    valid = kv_pos[None, None, :] < context_lens[:, None, None]   # [B, 1, K]
    causal = positions[:, :, None] >= kv_pos[None, None, :]       # [B, S, K]
    mask = valid & causal
    if local_window_size is not None:
        mask &= positions[:, :, None] - kv_pos[None, None, :] \
            < local_window_size
    logits = jnp.where(mask[:, None, None], logits, _NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(vals.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vals)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def paged_reference(request, q, k_pool, v_pool, k_scale, v_scale, layer,
                    block_tables, context_lens, positions, *,
                    scale=None, logits_soft_cap=None,
                    local_window_size=None):
    """The family's parity oracle: dense per-row cache reconstruction +
    vmapped :func:`~automodel_tpu.ops.attention.dot_product_attention` with
    each row's first query position as ``q_offset`` (queries are
    consecutive by contract) and the context length as a padding mask —
    i.e. exactly what the dense ``generate()`` cache path would compute on
    the same numbers."""
    from automodel_tpu.ops.attention import dot_product_attention

    D = q.shape[-1]
    keys = gathered_cache(k_pool, k_scale, layer, block_tables, head_dim=D)
    vals = gathered_cache(v_pool, v_scale, layer, block_tables, head_dim=D)
    K = keys.shape[1]

    def row(qb, kb, vb, ctx, pos0):
        am = (jnp.arange(K, dtype=jnp.int32) < ctx)[None]   # [1, K]
        return dot_product_attention(
            qb[None], kb[None], vb[None], causal=True, q_offset=pos0,
            attention_mask=am, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size)[0]

    out = jax.vmap(row)(q.astype(jnp.float32), keys, vals, context_lens,
                        positions[:, 0])
    return out.astype(q.dtype)


def build_paged_request(q, k_pool, *, quantized: bool,
                        soft_cap: bool = False,
                        window: bool = False) -> Dict[str, Any]:
    """The plain-dict request the ``attention.paged_decode`` chain's probes
    answer from (static shapes + feature flags only)."""
    return {
        "kind": "paged_attention",
        "q_seq": q.shape[1], "head_dim": q.shape[3],
        "num_q_heads": q.shape[2],
        "num_kv_heads": kv_heads(k_pool, q.shape[3]),
        "num_blocks": k_pool.shape[1], "block_size": k_pool.shape[2],
        "dtype": str(q.dtype), "quantized": bool(quantized),
        "soft_cap": bool(soft_cap), "window": bool(window),
        "rows": k_pool.ndim == 4,
    }


def paged_attention(q, k_pool, v_pool, *, layer, block_tables, context_lens,
                    positions, k_scale=None, v_scale=None, scale=None,
                    logits_soft_cap=None, local_window_size=None,
                    kernel_name=None):
    """The serving path's attention entry point: build one request and
    resolve the ``attention.paged_decode -> attention.paged_gather`` chain
    (see module docstring for the operand contract).  ``kernel_name``: the
    name the Pallas rung's call bears in a trace (a cache of several block
    groups names one kernel a group)."""
    request = build_paged_request(
        q, k_pool, quantized=k_scale is not None,
        soft_cap=logits_soft_cap is not None,
        window=local_window_size is not None)
    spec = registry.resolve("attention.paged_decode", request)
    return spec.impl(
        request, q, k_pool, v_pool, k_scale, v_scale, layer, block_tables,
        context_lens, positions, scale=scale,
        logits_soft_cap=logits_soft_cap,
        local_window_size=local_window_size, kernel_name=kernel_name)


def _paged_gather_probe(request: Mapping[str, Any]) -> bool:
    return True          # the chain's always-available anchor


registry.register_kernel(
    "attention.paged_gather", probe=_paged_gather_probe,
    impl=_paged_gather_impl, fallback=None, reference=paged_reference)
