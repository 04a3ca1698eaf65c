"""Paged attention over a LATENT cache — MLA's serving decode path.

Multi-head latent attention (DeepSeek-V2/V3, Kimi-K2) caches per token and
layer ONE row ``[c_kv | rope(k_rope)]`` of ``R = kv_lora_rank +
qk_rope_head_dim`` values that every query head shares.  In the absorbed
form the query of head ``i`` is ``[q_nope_i W_uk_i^T | q_rope_i]`` (``R``
wide), the score is its inner product with the cached row, and the value
is the row's first ``value_dim = kv_lora_rank`` values: MQA with one
kv head whose value is a slice of its key.  The engine keeps these rows in
one stacked plane ``[L, NB, BS, R]`` (``serving/kv_cache.py``), addressed
by the same block tables as a per-head cache.  The plane is stored at
``R`` rounded up to the 128-lane tile (``kv_cache.latent_plane_width``:
640 for 576), pad columns zero in cache and query alike; below ``R`` is
that stored width.

The family's rungs, on the ``kernel_lib`` registry like
``attention.paged_decode``'s:

* ``attention.mla_paged_decode`` — Pallas (``ops/mla_paged_attention_
  kernel.py``): per row, the pages a row owns are DMA'd once per query
  tile and serve all heads;
* ``attention.mla_paged_gather`` — the XLA anchor registered HERE (CPU,
  tests): gather the rows by block table, one masked softmax.

One operand contract (:func:`mla_paged_attention`): ``q [B, S, Hq, R]`` at
consecutive positions ``positions[b, :]`` (pad columns repeat the last
valid one), ``pool [L, NB, BS, R]`` with ``layer`` an int32 scalar (a rung
addresses the stacked pool AT the layer, never ``pool[layer]``),
``block_tables [B, MB]``, ``context_lens [B]`` including this step's
writes.  Returns ``[B, S, Hq, value_dim]``.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import registry
from automodel_tpu.ops.paged_attention import gathered_cache

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _mla_gather_impl(request, q, pool, layer, block_tables, context_lens,
                     positions, *, value_dim: int, scale: float):
    """XLA anchor: gather-by-table, masked softmax over the latent rows."""
    rows = gathered_cache(pool[..., None, :], None, layer,
                          block_tables)[:, :, 0]              # [B, K, R] f32
    K = rows.shape[1]
    logits = jnp.einsum("bshr,bkr->bshk", q.astype(jnp.float32), rows,
                        precision=jax.lax.Precision.DEFAULT) * scale
    kv_pos = jnp.arange(K, dtype=jnp.int32)
    mask = ((kv_pos[None, None, :] < context_lens[:, None, None])
            & (positions[:, :, None] >= kv_pos[None, None, :]))  # [B, S, K]
    logits = jnp.where(mask[:, :, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bshk,bkv->bshv", probs, rows[..., :value_dim])
    return out.astype(q.dtype)


def mla_paged_reference(request, q, pool, layer, block_tables, context_lens,
                        positions, *, value_dim: int, scale: float):
    """The family's parity oracle: the dense per-row cache as ONE kv head
    whose value is the key, through the dense decode path's own
    ``dot_product_attention`` (``q_offset`` = the row's first position, the
    context length as a padding mask), sliced to the value's width."""
    from automodel_tpu.ops.attention import dot_product_attention

    rows = gathered_cache(pool[..., None, :], None, layer, block_tables)
    K = rows.shape[1]

    def row(qb, kb, ctx, pos0):
        am = (jnp.arange(K, dtype=jnp.int32) < ctx)[None]
        return dot_product_attention(
            qb[None], kb[None], kb[None], causal=True, q_offset=pos0,
            attention_mask=am, scale=scale)[0]

    out = jax.vmap(row)(q.astype(jnp.float32), rows, context_lens,
                        positions[:, 0])
    return out[..., :value_dim].astype(q.dtype)


def build_mla_request(q, pool, value_dim: int) -> Dict[str, Any]:
    return {
        "kind": "mla_paged_attention",
        "q_seq": q.shape[1], "num_q_heads": q.shape[2],
        "latent_dim": q.shape[3], "value_dim": int(value_dim),
        "num_blocks": pool.shape[1], "block_size": pool.shape[2],
        "dtype": str(q.dtype), "pool_dtype": str(pool.dtype),
    }


def mla_paged_attention(q, pool, *, layer, block_tables, context_lens,
                        positions, value_dim: int, scale: float):
    """The latent serving path's attention entry point: one request,
    resolved down the ``attention.mla_paged_decode ->
    attention.mla_paged_gather`` chain."""
    request = build_mla_request(q, pool, value_dim)
    spec = registry.resolve("attention.mla_paged_decode", request)
    return spec.impl(request, q, pool, layer, block_tables, context_lens,
                     positions, value_dim=value_dim, scale=scale)


def _mla_gather_probe(request: Mapping[str, Any]) -> bool:
    return True          # the chain's always-available anchor


registry.register_kernel(
    "attention.mla_paged_gather", probe=_mla_gather_probe,
    impl=_mla_gather_impl, fallback=None, reference=mla_paged_reference)
