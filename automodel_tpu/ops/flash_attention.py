"""TPU flash attention: Pallas kernel with segment-id packing support.

This is the TPU equivalent of the reference's FlashAttention-2 path
(``nemo_automodel/components/_transformers/auto_model.py:50-144``) and of
FA2-for-packed-sequences with position_ids (``recipes/llm/train_ft.py:113-118``):
the Pallas MHA kernel (``jax.experimental.pallas.ops.tpu.flash_attention``)
consumes *segment ids* natively, so packed sequences need no 4-D masks.

Dispatch contract: this module registers the ``attention.flash`` rung of
the kernel registry (``ops/kernel_lib/registry.py``) — probed when splash
declines (shape/backend/feature) and falling back to XLA SDPA, the same
fallback-chain idea as the reference's fa3->fa2->sdpa
(``auto_model.py:119-144``) with XLA in the anchor role.  Block sizes
route through the substrate's autotuner (``kernel_lib/autotune``) with the
hand-tuned divisor pick as the default.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling

logger = logging.getLogger(__name__)

_BLOCK = 128  # minimum pallas flash block (MIN_BLOCK_SIZE)
# Largest legal block that divides the sequence: the hand-tuned default the
# autotuner falls back to ("flash" kernel key).
_BLOCK_CANDIDATES = (512, 256, 128)


def flash_attention_available(q_seq: int, kv_seq: int, head_dim: int) -> bool:
    return (
        registry.on_tpu()
        and q_seq % _BLOCK == 0
        and kv_seq % _BLOCK == 0
        and head_dim >= 8
    )


def _block_plan(q_seq: int, kv_seq: int, dtype) -> Tuple[int, int]:
    """(block_q, block_kv): hand-tuned default = largest legal divisor,
    overridden by a persisted autotune winner when one fits the shape."""
    default = (min(tiling.pick_block(q_seq, _BLOCK_CANDIDATES), q_seq),
               min(tiling.pick_block(kv_seq, _BLOCK_CANDIDATES), kv_seq))
    fields = autotune.attention_sweep_key_fields(
        {"q_seq": q_seq, "kv_seq": kv_seq, "dtype": str(dtype)})
    return autotune.lookup(
        "flash", fields, default,
        validate=lambda c: (len(c) == 2 and q_seq % c[0] == 0
                            and kv_seq % c[1] == 0))


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "logits_soft_cap",
                              "block", "block_kv"))
def _flash(q, k, v, segment_ids, causal, scale, logits_soft_cap,
           block, block_kv):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention,
    )

    seg = None
    if segment_ids is not None:
        seg = SegmentIds(q=segment_ids, kv=segment_ids)

    sizes = BlockSizes(
        block_q=block, block_k_major=block_kv, block_k=block_kv,
        block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block_kv,
        block_k_dkv=block_kv, block_q_dkv=block,
        block_k_major_dq=block_kv, block_k_dq=block_kv, block_q_dq=block,
    )
    return flash_attention(
        q, k, v, segment_ids=seg, causal=causal, sm_scale=scale,
        block_sizes=sizes)


def flash_attention_bshd(
    q: jnp.ndarray,                         # [B, S, Hq, D]
    k: jnp.ndarray,                         # [B, Skv, Hk, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: Optional[jnp.ndarray] = None,   # [B, S]
    attention_mask: Optional[jnp.ndarray] = None,  # [B, Skv] padding mask
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
) -> jnp.ndarray:
    """Pallas flash attention in the framework's [B, S, H, D] convention.

    GQA is handled by repeating kv heads (the splash rung removes the
    repeat).  Padding masks fold into segment ids: pad positions get
    segment 0, which real tokens (segments >= 1) never attend to.
    """
    B, S, Hq, D = q.shape
    Hk = k.shape[2]
    assert Hq % Hk == 0
    if logits_soft_cap is not None:
        raise NotImplementedError("soft cap not supported by the flash path")
    scale = D ** -0.5 if scale is None else scale

    from automodel_tpu.ops.attention import fold_padding_into_segments

    segment_ids = fold_padding_into_segments((B, S), segment_ids,
                                             attention_mask)
    block, block_kv = _block_plan(S, k.shape[1], q.dtype)

    # [B, S, H, D] -> [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if Hk != Hq:
        rep = Hq // Hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    out = _flash(qt, kt, vt, segment_ids, causal, scale, logits_soft_cap,
                 block, block_kv)
    return out.transpose(0, 2, 1, 3)


def sharded_flash_attention(
    q, k, v, mesh, *,
    causal: bool = True,
    segment_ids=None,
    attention_mask=None,
    scale=None,
    batch_axes=None,
    head_axis: str = "tp",
):
    """shard_map wrapper: a pallas_call must run per-shard under GSPMD, so
    batch goes over dp (incl. the cross-slice dcn_dp axis) and heads over
    tp; seq stays whole (cp=1 path — cp>1 routes to ring attention
    instead).  ``batch_axes=None`` (default) uses the dp-family axes
    PRESENT in the mesh; an explicit tuple is used verbatim, so a typo'd
    axis still fails loudly at spec resolution."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.distributed.mesh import BATCH_AXES

    if batch_axes is None:
        batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    qspec = P(tuple(batch_axes), None, head_axis, None)
    kvspec = P(tuple(batch_axes), None, head_axis, None)
    sspec = P(tuple(batch_axes), None)

    from automodel_tpu.ops.attention import fold_padding_into_segments

    B, S, Hq, D = q.shape
    segment_ids = fold_padding_into_segments((B, S), segment_ids,
                                             attention_mask)

    def inner(q, k, v, seg):
        return flash_attention_bshd(
            q, k, v, causal=causal, segment_ids=seg, scale=scale)

    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: inner(q, k, v, None), mesh=mesh,
            in_specs=(qspec, kvspec, kvspec), out_specs=qspec,
            check_vma=False)(q, k, v)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, sspec), out_specs=qspec,
        check_vma=False)(q, k, v, segment_ids.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _attention_probe(request) -> bool:
    # soft caps and sliding windows are not expressible on this kernel —
    # splash takes them; SDPA anchors whatever remains
    if request.get("soft_cap") or request.get("window"):
        return False
    return flash_attention_available(
        request["q_seq"], request["kv_seq"], request["head_dim"])


def _attention_impl(request, q, k, v, *, causal=True, segment_ids=None,
                    attention_mask=None, scale=None, logits_soft_cap=None,
                    local_window_size=None):
    del logits_soft_cap, local_window_size        # excluded by the probe
    mesh = request.get("mesh")
    if mesh is not None:
        return sharded_flash_attention(
            q, k, v, mesh, causal=causal, segment_ids=segment_ids,
            attention_mask=attention_mask, scale=scale)
    return flash_attention_bshd(
        q, k, v, causal=causal, segment_ids=segment_ids,
        attention_mask=attention_mask, scale=scale)


def _sweep_key_fields(req):
    return autotune.attention_sweep_key_fields(req)


def _sweep_candidates(req):
    out = []
    for b in (1024, 512, 256, 128):
        if req["q_seq"] % b == 0 and req["kv_seq"] % b == 0:
            out.append((b, b))
    return out


def _sweep_run(req, choice) -> float:
    B = int(req.get("batch", 1))
    S, Skv = req["q_seq"], req["kv_seq"]
    Hq, D = int(req.get("num_q_heads", 8)), req["head_dim"]
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    key = jax.random.key(0)
    mk = lambda seq: jax.random.normal(
        key, (B, seq, Hq, D), jnp.float32).astype(dtype)
    # kv pre-repeated to Hq heads: times the kernel, not the GQA repeat
    q, k, v = mk(S), mk(Skv), mk(Skv)

    def loss(q, k, v):
        return jnp.sum(flash_attention_bshd(
            q, k, v, causal=bool(req.get("causal", True))
        ).astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return autotune.time_call(fn, q, k, v)


from automodel_tpu.ops.kernel_lib.parity import sdpa_reference  # noqa: E402

registry.register_kernel(
    "attention.flash", probe=_attention_probe, impl=_attention_impl,
    fallback="attention.sdpa", reference=sdpa_reference)
autotune.register_sweep(
    "flash", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
