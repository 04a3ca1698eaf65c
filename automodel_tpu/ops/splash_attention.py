"""Splash attention: the TPU sparse-flash kernel with NATIVE grouped-query
support — no kv-head repeat.

Replaces the plain Pallas flash path on the training hot loop (reference
analogue: the FlashAttention-2 fast path, ``nemo_automodel/components/
_transformers/auto_model.py:50-144``).  Advantages over
``ops/flash_attention.py``:

* **GQA without materializing kv repeats** — q is viewed as
  ``[Hkv, G, S, D]`` and the MQA kernel is vmapped over kv heads, so kv
  bandwidth stays at ``Hkv/Hq`` of the repeat path (4x less for Llama-3).
* **soft-cap support** (``attn_logits_soft_cap``) — lifts the Gemma-style
  restriction the flash path had.
* the STATIC mask structure (causal, sliding window) is processed
  host-side once per shape and its skipped blocks are never executed
  (causal = ~2x fewer FLOPs, exactly); with segment ids the block map is
  then narrowed PER ROW, in the step, to the blocks a document spans
  (``_segment_block_maps``): a block whose query rows and key columns
  share no segment id is not run and not fetched.

Block sizes route through the substrate autotuner (``kernel_lib/autotune``,
kernel key ``"splash"``) with a LAYOUT-AWARE default: a partially-masked
block (the causal diagonal, segment boundaries) still executes every
``block_kv_compute`` sub-block — masked halves and all — so the wasted
compute is ~``block_kv/S`` of the exact causal FLOPs.  At short S big
blocks win (grid overhead dominates); at long S the diagonal waste does:
1024-edge blocks at S=16k burn ~6.25% extra MXU time (the documented
``long_context_16k`` bench gap), so causal/windowed masks at
``S >= _DIAG_FINE_MIN_SEQ`` cap the edge at ``_DIAG_FINE_BLOCK`` (halving
the waste), and the autotuner can refine further per (shape, dtype,
topology).

What is static and what is per row.  Per (shape, mask kind, blocks) the
library's kernel is built once and cached (``_build_kernel``): its
``block_mask`` / ``data_next`` scalar-prefetch arrays describe the causal
or windowed mask alone.  Without segment ids that kernel runs as built.
With them, each row's ids give every query block and key block a range of
ids (least, greatest); a block can hold an unmasked pair only if the two
ranges meet, so ``block_mask`` is zeroed elsewhere and ``data_next`` is
pointed past the skipped blocks, as traced ``[1, q_blocks, kv_blocks]``
arrays swapped into the cached kernel's pytree, one pair for the forward
grid and one for the fused backward's.  Inside a block that runs nothing
changes: the mask function and ``SegmentIds`` mask pair by pair as before.
The block plan is the same with and without segment ids: on a v5e, over a
packed SFT mix at S=4096, no edge under 1024 beat 1024 with the map (a
512-edge block costs ~29 % more per pair than a 1024-edge one, which eats
what the finer map skips; the sweep's table is in PERF.md section 6).  A
call without segment ids builds no map: its program is unchanged.

Segment ids (packed sequences) and padding masks use the framework-wide
convention: pad positions get segment 0 (``ops/attention.py:
fold_padding_into_segments``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling

_BLOCK = 128      # minimum legal splash block edge
_SEQ_ALIGN = tiling.SEQ_ALIGN  # pad sequences so block edges stay >= 256

# Layout-aware diagonal tiling: below this sequence length the largest
# legal block edge wins (Mosaic grid overhead dominates); at/above it the
# causal-diagonal partial-block waste (~edge/S of the exact causal FLOPs)
# dominates, so the edge is capped.  512 halves the 16k-context waste
# (6.25% -> 3.1%) while staying on the >=256 MXU-friendly side the repo's
# v5e measurements established (128-edge blocks cost ~30%).
_DIAG_FINE_MIN_SEQ = 8192
_DIAG_FINE_BLOCK = 512

# Pallas interpret mode: lets the CPU test suite execute the real kernel
# logic (tests monkeypatch this; the dispatcher never routes CPU traffic
# here on its own — see splash_attention_available).
_INTERPRET = False


def splash_attention_available(q_seq: int, kv_seq: int, head_dim: int) -> bool:
    return (
        registry.on_tpu()
        and q_seq % _BLOCK == 0
        and kv_seq % _BLOCK == 0
        and head_dim >= 8
    )


def _pick_block(n: int) -> int:
    return tiling.pick_block(n, (1024, 512, 256, 128))


def _block_plan(q_seq: int, kv_seq: int, *, causal: bool,
                local_window: Optional[int], dtype) -> Tuple[int, int, int]:
    """(block_q, block_kv, block_kv_compute) for this shape.

    Hand-tuned default: largest legal edge, capped at ``_DIAG_FINE_BLOCK``
    for causal/windowed masks at long sequence (the layout-aware diagonal
    tiling — see the module docstring), with kv-compute sub-blocks at half
    the kv block (fused-backward sweet spot of the measured v5e grid).  A
    persisted autotune winner overrides when it divides the shape.
    """
    bq, bkv = _pick_block(q_seq), _pick_block(kv_seq)
    if (causal or local_window is not None) and max(
            q_seq, kv_seq) >= _DIAG_FINE_MIN_SEQ:
        bq = min(bq, _pick_block(min(_DIAG_FINE_BLOCK, q_seq)))
        bkv = min(bkv, _pick_block(min(_DIAG_FINE_BLOCK, kv_seq)))
    default = (bq, bkv, max(bkv // 2, _BLOCK))
    fields = autotune.attention_sweep_key_fields(
        {"q_seq": q_seq, "kv_seq": kv_seq, "dtype": str(dtype)},
        causal=bool(causal), window=int(local_window or 0))

    def _legal(c) -> bool:
        return (len(c) == 3 and q_seq % c[0] == 0 and kv_seq % c[1] == 0
                and c[1] % c[2] == 0 and c[2] >= _BLOCK)

    return autotune.lookup("splash", fields, default, validate=_legal)


def _bwd_block_plan(q_seq: int, kv_seq: int, *, causal: bool,
                    local_window: Optional[int], dtype,
                    fwd_blocks: Tuple[int, int, int]
                    ) -> Tuple[int, int, int]:
    """(block_q_dkv, block_kv_dkv, block_kv_dkv_compute) for the fused
    backward.  Defaults to MIRRORING the forward triple (the pre-sweep
    behavior, bit-identical with autotune off), but carries its own autotune
    key ``"splash_bwd"`` — the dq/dkv pass has a different arithmetic
    intensity (reads out/logsumexp residuals, writes three gradients) so
    its sweet spot need not be the forward's (ROADMAP kernel follow-up)."""
    fields = autotune.attention_sweep_key_fields(
        {"q_seq": q_seq, "kv_seq": kv_seq, "dtype": str(dtype)},
        causal=bool(causal), window=int(local_window or 0))

    def _legal(c) -> bool:
        return (len(c) == 3 and q_seq % c[0] == 0 and kv_seq % c[1] == 0
                and c[1] % c[2] == 0 and c[2] >= _BLOCK)

    return autotune.lookup("splash_bwd", fields, fwd_blocks,
                           validate=_legal)


@functools.lru_cache(maxsize=64)
def _build_kernel(q_seq: int, kv_seq: int, q_heads_per_kv: int,
                  causal: bool, soft_cap: Optional[float],
                  interpret: bool = False,
                  local_window: Optional[int] = None,
                  blocks: Optional[Tuple[int, int, int]] = None,
                  bwd_blocks: Optional[Tuple[int, int, int]] = None):
    """Mask processing runs host-side on numpy and is the expensive part —
    cache the built kernel per (shape, group, mask, blocks) signature.

    ``ensure_compile_time_eval`` keeps the kernel's mask-info arrays real
    device constants even when this is first called inside a jit trace;
    without it the cached kernel would hold leaked tracers."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    if local_window is not None:
        # causal sliding window: attend [q - window + 1, q]; off-window
        # blocks are skipped outright (Gemma3/Mistral sliding layers)
        head_mask = sm.LocalMask((q_seq, kv_seq),
                                 window_size=(local_window - 1, 0), offset=0)
    else:
        head_mask = (sm.CausalMask((q_seq, kv_seq)) if causal
                     else sm.FullMask((q_seq, kv_seq)))
    mask = sm.MultiHeadMask([head_mask for _ in range(q_heads_per_kv)])
    if blocks is None:
        blocks = _block_plan(q_seq, kv_seq, causal=causal,
                             local_window=local_window, dtype=jnp.bfloat16)
    bq, bkv, bkvc = blocks
    # Fused dq+dkv backward (one bwd pass instead of two) with kv-compute
    # sub-blocks at half the kv block: best of the measured grid on the
    # Llama-1B/v5e bench (~+6% step time vs plain 512 blocks + split bwd);
    # block_*_dq are unused in fused mode.  The backward triple mirrors the
    # forward unless an autotuned "splash_bwd" winner overrides it
    # (callers thread it via ``bwd_blocks``).
    bq_d, bkv_d, bkvc_d = bwd_blocks if bwd_blocks is not None else blocks
    sizes = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
        block_q_dkv=bq_d, block_kv_dkv=bkv_d, block_kv_dkv_compute=bkvc_d,
        use_fused_bwd_kernel=True,
    )
    with jax.ensure_compile_time_eval():
        # residual_checkpoint_name tags the kernel's (out, logsumexp)
        # residuals so a ``save_names:splash_residuals`` remat policy keeps
        # them across the layer checkpoint: the backward then runs dq/dkv
        # directly instead of re-running the forward kernel first (~50
        # ms/step at Llama-1B bench shapes for ~1.1 GB of saved residuals).
        return sk.make_splash_mqa_single_device(
            mask=mask, block_sizes=sizes, attn_logits_soft_cap=soft_cap,
            residual_checkpoint_name="splash_residuals",
            interpret=interpret)


# ---------------------------------------------------------------------------
# The per-row block map: which blocks of the static mask a row's documents
# reach.  One rule, written once for NumPy (the host's counter) and for
# jax.numpy (the traced map), by passing the array module.
# ---------------------------------------------------------------------------
_PAD_RANGE_ID = np.iinfo(np.int32).max


def _block_ranges(xp, seg, block: int):
    """Least and greatest segment id of every ``block`` positions of
    ``[..., S]`` ids.  Padding is segment 0 AFTER a packed row's last
    document; for the range test alone it takes an id above every
    document's, so the row's ids stay monotone and a tail block's range
    does not reach back over every document before it."""
    ids = xp.where(seg == 0, _PAD_RANGE_ID, seg)
    ids = ids.reshape(*seg.shape[:-1], -1, block)
    return ids.min(-1), ids.max(-1)


def _blocks_meet(xp, seg_q, seg_kv, bq: int, bkv: int):
    """``[..., q_blocks, kv_blocks]`` bool: may block (i, j) hold a pair of
    equal segment ids?  Two positions with one id put that id inside both
    blocks' ranges, so ranges that do not meet prove there is no such pair,
    in whatever order the ids come (ids that are not monotone only make the
    map less sparse, never wrong).

    Two blocks that share a position share its id, so they always meet: a
    causal or windowed mask keeps every query row's own (q, q) pair, the
    block that holds it runs, and no softmax row is left empty (a row with
    every block skipped would divide 0 by 0 into the residual stream)."""
    qlo, qhi = _block_ranges(xp, seg_q, bq)
    klo, khi = _block_ranges(xp, seg_kv, bkv)
    return ((qlo[..., :, None] <= khi[..., None, :])
            & (klo[..., None, :] <= qhi[..., :, None]))


def _narrowed(info, meet, *, dkv: bool):
    """``info`` (the cached kernel's static ``MaskInfo`` of one grid) with
    ``block_mask`` zeroed where ``meet`` ``[q_blocks, kv_blocks]`` is false
    and ``data_next`` recomputed, for ONE row, as traced arrays.

    In a static ``MaskInfo`` a block that runs holds its OWN data index in
    ``data_next`` (the key block for the forward grid, also where the grid
    was shrunk to a window's width; the query block for the fused
    backward's), and a block that does not run holds the index of the next
    one that does, in the order the grid is walked, so the pipeline fetches
    nothing for it.  The forward walks a query block's key blocks; the
    fused backward walks a key block's query blocks: its order is the
    transpose.  Where the grid was not shrunk (every causal grid) a block's
    own index is its place in the walk and no gather is needed."""
    mask = info.block_mask[0]
    shrunk = mask.shape != meet.shape
    if shrunk:
        own = info.data_next[0].astype(jnp.int32)
        meet = jnp.take_along_axis(meet, own, axis=0 if dkv else 1)
    run = (mask != 0) & meet
    run_w = run.T if dkv else run
    n = run_w.size
    at = jnp.where(run_w.reshape(-1), jnp.arange(n, dtype=jnp.int32), n)
    following = jax.lax.cummin(at, axis=0, reverse=True)
    # past the last block that runs: the first, as the library wraps
    following = jnp.where(following == n, at.min(), following)
    if shrunk:
        data_next = (own.T if dkv else own).reshape(-1)[following]
    else:
        data_next = following % run_w.shape[1]
    data_next = data_next.reshape(run_w.shape)
    data_next = data_next.T if dkv else data_next
    return info._replace(
        block_mask=jnp.where(run, mask, 0).astype(mask.dtype)[None],
        data_next=data_next.astype(info.data_next.dtype)[None])


def _segment_block_maps(kernel, seg, blocks, bwd_blocks):
    """The cached static ``kernel`` with both grids' maps narrowed to the
    blocks one row's ``[S]`` segment ids reach."""
    fwd = _narrowed(kernel.fwd_mask_info,
                    _blocks_meet(jnp, seg, seg, blocks[0], blocks[1]),
                    dkv=False)
    dkv = _narrowed(kernel.dkv_mask_info,
                    _blocks_meet(jnp, seg, seg, bwd_blocks[0], bwd_blocks[1]),
                    dkv=True)
    return type(kernel)(fwd, kernel.dq_mask_info, dkv, **kernel.kwargs)


def _static_blocks(nq: int, nkv: int, bq: int, bkv: int, causal: bool,
                   local_window: Optional[int]) -> np.ndarray:
    """``[nq, nkv]`` bool: blocks of the static mask that hold a pair."""
    q_lo = (np.arange(nq) * bq)[:, None]
    k_lo = (np.arange(nkv) * bkv)[None, :]
    if local_window is not None:        # attend [q - window + 1, q]
        return ((k_lo <= q_lo + bq - 1)
                & (k_lo + bkv - 1 >= q_lo - (local_window - 1)))
    if causal:
        return np.broadcast_to(k_lo <= q_lo + bq - 1, (nq, nkv))
    return np.ones((nq, nkv), bool)


def segment_block_counts(segment_ids, *, causal: bool = True,
                         local_window_size: Optional[int] = None
                         ) -> Tuple[int, int]:
    """(blocks run, static blocks) of the FORWARD grid over a host batch's
    ``[B, S]`` segment ids: the rule of :func:`_segment_block_maps` in
    NumPy at the plan's forward edges, for the train loop's counters
    (``attn_blocks_run`` / ``attn_blocks_static`` on the ``dispatch``
    span).  Per layer and head the kernel runs the first number where the
    static mask alone would run the second."""
    seg = np.atleast_2d(np.asarray(segment_ids))
    seg = np.pad(seg, ((0, 0), (0, (-seg.shape[-1]) % _SEQ_ALIGN)))
    S = seg.shape[-1]
    bq, bkv, _ = _block_plan(S, S, causal=causal,
                             local_window=local_window_size,
                             dtype=jnp.bfloat16)
    static = _static_blocks(S // bq, S // bkv, bq, bkv, causal,
                            local_window_size)
    run = _blocks_meet(np, seg, seg, bq, bkv) & static
    return int(run.sum()), int(static.sum()) * seg.shape[0]


def splash_attention_bshd(
    q: jnp.ndarray,                         # [B, S, Hq, D]
    k: jnp.ndarray,                         # [B, Skv, Hk, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: Optional[jnp.ndarray] = None,     # [B, S]
    attention_mask: Optional[jnp.ndarray] = None,  # [B, Skv] padding mask
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    local_window_size: Optional[int] = None,   # static int only
) -> jnp.ndarray:
    """Splash attention in the framework's [B, S, H, D] convention."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    from automodel_tpu.ops.attention import fold_padding_into_segments

    B, S, Hq, D = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    assert Hq % Hk == 0, f"query heads {Hq} not a multiple of kv heads {Hk}"
    G = Hq // Hk
    scale = D ** -0.5 if scale is None else scale

    segment_ids = fold_padding_into_segments((B, S), segment_ids,
                                             attention_mask)

    # Sequence alignment: the kernel block edge must divide S, so odd
    # multiples of 128 force 128-edge blocks — measured ~30% step-time
    # penalty at Llama-1B shapes on v5e vs >=256 blocks.  Pad the attention
    # operand to the next 256 multiple and slice the output: strictly
    # cheaper than padding the whole batch (MLP/projections keep the true
    # S).  Correctness: pads sit at the END, so causal real queries never
    # see padded kv; otherwise padded positions get segment 0, which real
    # tokens (segments >= 1, see fold_padding_into_segments) never match.
    orig_S = S
    pad_q, pad_kv = (-S) % _SEQ_ALIGN, (-Skv) % _SEQ_ALIGN
    if pad_q or pad_kv:
        assert S == Skv, (
            "sequence-alignment padding assumes self-attention (S == Skv); "
            f"got S={S}, Skv={Skv}")
        if segment_ids is None and not causal:
            segment_ids = jnp.ones((B, S), jnp.int32)
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad_q)))
        S, Skv = S + pad_q, Skv + pad_kv

    window = (None if local_window_size is None else int(local_window_size))
    blocks = _block_plan(S, Skv, causal=causal, local_window=window,
                         dtype=q.dtype)
    bwd_blocks = _bwd_block_plan(S, Skv, causal=causal, local_window=window,
                                 dtype=q.dtype, fwd_blocks=blocks)
    kernel = _build_kernel(S, Skv, G, causal,
                           None if logits_soft_cap is None
                           else float(logits_soft_cap),
                           interpret=_INTERPRET,
                           local_window=window,
                           blocks=blocks,
                           bwd_blocks=bwd_blocks)

    # The kernel has no sm_scale param: fold the scale into q.
    qs = (q * jnp.asarray(scale, q.dtype)).transpose(0, 2, 1, 3)
    qs = qs.reshape(B, Hk, G, S, D)
    kt = k.transpose(0, 2, 1, 3)            # [B, Hk, Skv, D]
    vt = v.transpose(0, 2, 1, 3)

    if segment_ids is None:
        per_kv = jax.vmap(kernel, in_axes=(0, 0, 0, None))  # over kv heads
        out = jax.vmap(per_kv, in_axes=(0, 0, 0, None))(qs, kt, vt, None)
    else:
        def row(q, k, v, seg):
            # one row: its own block maps, shared by its kv heads
            mapped = _segment_block_maps(kernel, seg, blocks, bwd_blocks)
            return jax.vmap(mapped, in_axes=(0, 0, 0, None))(
                q, k, v, sk.SegmentIds(q=seg, kv=seg))

        # The maps are scalar-prefetch operands, which a Pallas grid cannot
        # batch: rows run one after another.  Unrolled here rather than by
        # vmap's loop over a batched operand, which carries every row's
        # unreduced dq through a while loop (AOT for v5e, 2 rows, an edge
        # of 512: 689 MB of temporaries against 182 for this form).
        seg = segment_ids.astype(jnp.int32)
        out = jnp.stack([row(qs[b], kt[b], vt[b], seg[b]) for b in range(B)])
    # [B, Hk, G, S, D] -> [B, S, Hq, D] (alignment pads sliced off)
    out = out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    return out[:, :orig_S] if orig_S != S else out


def sharded_splash_attention(
    q, k, v, mesh, *,
    causal: bool = True,
    segment_ids=None,
    attention_mask=None,
    scale=None,
    logits_soft_cap=None,
    local_window_size: Optional[int] = None,
    batch_axes=None,
    head_axis: str = "tp",
):
    """shard_map wrapper: a pallas_call runs per-shard under GSPMD — batch
    over dp (incl. the cross-slice dcn_dp axis), heads over tp, sequence
    whole (cp>1 routes to ring attention before reaching here).
    ``batch_axes=None`` (default) uses the dp-family axes PRESENT in the
    mesh; an explicit tuple is used verbatim (typos fail loudly)."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.distributed.mesh import BATCH_AXES
    from automodel_tpu.ops.attention import fold_padding_into_segments

    B, S = q.shape[:2]
    segment_ids = fold_padding_into_segments((B, S), segment_ids,
                                             attention_mask)

    if batch_axes is None:
        batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    qspec = P(tuple(batch_axes), None, head_axis, None)
    sspec = P(tuple(batch_axes), None)

    def inner(q, k, v, seg):
        return splash_attention_bshd(
            q, k, v, causal=causal, segment_ids=seg, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size)

    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: inner(q, k, v, None), mesh=mesh,
            in_specs=(qspec, qspec, qspec), out_specs=qspec,
            check_vma=False)(q, k, v)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, qspec, qspec, sspec), out_specs=qspec,
        check_vma=False)(q, k, v, segment_ids.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _attention_probe(request) -> bool:
    if request.get("traced_window"):
        # a TRACED window (per-layer scalar riding a scan) cannot steer the
        # host-side mask build; only SDPA expresses it
        return False
    return splash_attention_available(
        request["q_seq"], request["kv_seq"], request["head_dim"])


def _attention_impl(request, q, k, v, *, causal=True, segment_ids=None,
                    attention_mask=None, scale=None, logits_soft_cap=None,
                    local_window_size=None):
    mesh = request.get("mesh")
    # the kernels name themselves (``splash_mqa_*``); the scope is the
    # call site's name in a trace
    with jax.named_scope("splash"):
        if mesh is not None:
            # pallas_call must run per-shard under GSPMD
            return sharded_splash_attention(
                q, k, v, mesh, causal=causal, segment_ids=segment_ids,
                attention_mask=attention_mask, scale=scale,
                logits_soft_cap=logits_soft_cap,
                local_window_size=local_window_size)
        return splash_attention_bshd(
            q, k, v, causal=causal, segment_ids=segment_ids,
            attention_mask=attention_mask, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size)


def _sweep_key_fields(req):
    return autotune.attention_sweep_key_fields(
        req, causal=bool(req.get("causal", True)),
        window=int(req.get("local_window_size") or 0))


def _sweep_candidates(req):
    out = []
    for b in (1024, 512, 256):
        if req["q_seq"] % b or req["kv_seq"] % b:
            continue
        for bkvc in (b, b // 2):
            if bkvc >= _BLOCK:
                out.append((b, b, bkvc))
    return out or [(_BLOCK, _BLOCK, _BLOCK)]


def _sweep_run(req, choice) -> float:
    B = int(req.get("batch", 1))
    S, Skv = req["q_seq"], req["kv_seq"]
    Hq = int(req.get("num_q_heads", 8))
    Hk = int(req.get("num_kv_heads", Hq))
    D = req["head_dim"]
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, S, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(key, (B, Skv, Hk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(key, (B, Skv, Hk, D), jnp.float32).astype(dtype)

    def loss(q, k, v):
        return jnp.sum(splash_attention_bshd(
            q, k, v, causal=bool(req.get("causal", True)),
            local_window_size=req.get("local_window_size"),
        ).astype(jnp.float32))

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return autotune.time_call(fn, q, k, v)


from automodel_tpu.ops.kernel_lib.parity import sdpa_reference  # noqa: E402

registry.register_kernel(
    "attention.splash", probe=_attention_probe, impl=_attention_impl,
    fallback="attention.flash", reference=sdpa_reference)
autotune.register_sweep(
    "splash", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
# The backward-specific triple (block_q_dkv / block_kv_dkv / *_compute)
# sweeps independently: same key schema and candidate grid as the forward,
# but _sweep_run's forced("splash_bwd", ...) only moves the fused dq/dkv
# pass — the forward keeps its own plan, so the two winners compose.
autotune.register_sweep(
    "splash_bwd", key_fields=_sweep_key_fields,
    candidates=_sweep_candidates, run=_sweep_run)
