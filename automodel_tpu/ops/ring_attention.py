"""Ring attention: context-parallel attention over the ``cp`` mesh axis.

TPU-native replacement for the reference's torch-experimental
``context_parallel`` (``nemo_automodel/components/distributed/cp_utils.py:
34-149``, rotate method "allgather"/"alltoall"): here the canonical
blockwise-ring formulation — each cp shard holds a sequence slice of
q/k/v; k/v blocks rotate around the ring via ``jax.lax.ppermute`` while
every shard accumulates its queries' attention with numerically-stable
online-softmax (running max / sum) combination.  XLA overlaps the ppermute
with the local block's compute, so the ring rides the ICI at full duplex
(the scaling-book recipe).

Causality & layouts: every token carries an explicit POSITION taken from the
sequence layout (``_shard_positions``).  Under the default ``zigzag`` layout
(``ops/zigzag.py`` — shard i holds chunks ``i`` and ``2cp-1-i``) each shard
owns an equal mix of early and late positions, so causal work is balanced
across the ring; ``contiguous`` keeps the naive one-run-per-shard slicing
(shard 0 nearly idle under a causal mask, shard cp-1 doing cp blocks).

Tile skipping: the inner blockwise attention computes each kv tile's
validity from tile min/max position and segment bounds
(``kernel_lib/tiling.tile_skip_predicate``) and SKIPS wholly-masked tiles
with ``lax.cond`` — a causal ring does ~half the FLOPs of the
mask-to-zero formulation, and with the zig-zag layout that saving is
identical on every shard instead of concentrated on the early ones.

This module registers the ``attention.ring`` rung at the HEAD of the
attention fallback chain (``kernel_lib/registry``): an active sharding
context with cp > 1 takes unconditional precedence, because under the
zig-zag layout any fallback that assumes arange token order (SDPA's
built-in causal mask) would be silently wrong on a permuted stream.  Tile
edges route through the substrate autotuner (kernel key ``"ring"``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling
from automodel_tpu.ops.kernel_lib.tiling import ceil_pad as _ceil_pad

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Position sentinel for kv tile padding: any causal query masks it (and it
# can never be inside a trailing window), so padded kv tails are skippable
# by the same min-position test as real future tiles.
_PAD_POS = jnp.iinfo(jnp.int32).max // 2


# Default tile edges for the blockwise inner attention.  Peak transient
# memory per tile is B*Hk*G*cq*ckv fp32 logits (64 MiB at 32 heads)
# independent of the shard's sequence length — naive [S, S] logits would be
# 8.6 GiB at S_local=8k, an OOM before long context even starts.
_CQ, _CKV = 512, 1024


def _tile_plan(sq: int, skv: int, dtype) -> Tuple[int, int]:
    """(cq, ckv) inner tile edges: hand-tuned default, autotune override.
    Any pair is legal (ragged tails are padded), so no divisibility
    validation is needed."""
    default = (min(_CQ, sq), min(_CKV, skv))
    fields = autotune.attention_sweep_key_fields(
        {"q_seq": sq, "kv_seq": skv, "dtype": str(dtype)})
    return autotune.lookup("ring", fields, default,
                           validate=lambda c: len(c) == 2 and min(c) >= 1)


def _shard_positions(shard_index, s_local: int, cp: int,
                     layout: str) -> jnp.ndarray:
    """Global token positions [s_local] held by ``shard_index`` under the
    sequence layout.  ``shard_index`` may be traced (``lax.axis_index``)."""
    if layout == "zigzag":
        if s_local % 2:
            raise ValueError(
                f"zigzag layout needs an even local sequence length, got "
                f"{s_local} (global seq must divide 2*cp)")
        c = s_local // 2
        half = jnp.arange(c, dtype=jnp.int32)
        return jnp.concatenate([shard_index * c + half,
                                (2 * cp - 1 - shard_index) * c + half])
    if layout != "contiguous":
        raise ValueError(f"unknown cp layout {layout!r}")
    return shard_index * s_local + jnp.arange(s_local, dtype=jnp.int32)


def _block_attend(q, k, v, *, q_positions=None, kv_positions=None, causal,
                  seg_q, seg_kv, local_window_size=None,
                  logits_soft_cap=None, count_tiles=False
                  ) -> Tuple[jnp.ndarray, ...]:
    """One q-block x kv-block attention, double-chunked with online softmax
    (flash-style in XLA): returns (unnormalized out [B,Sq,Hk,G,D], row max
    [B,Hk,G,Sq], row sumexp [B,Hk,G,Sq]) in fp32 — plus the number of kv
    tiles actually executed when ``count_tiles`` (the skip probe).

    ``q_positions`` [Sq] / ``kv_positions`` [Skv] are explicit per-token
    global positions (None = arange): zig-zag shards hold NON-CONTIGUOUS
    positions, so scalar offset arithmetic cannot describe them.  Tile masks
    are computed from position/segment arithmetic on the fly
    (``tiling.tile_valid_mask``) — no [Sq, Skv] mask or logits tensor ever
    materializes — and a kv tile that ``tiling.tile_skip_predicate`` proves
    wholly masked is SKIPPED with ``lax.cond`` (state passes through
    untouched) instead of computed and zeroed.
    """
    B, Sq, Hk, G, D = q.shape
    Skv = k.shape[1]
    cq, ckv = _tile_plan(Sq, Skv, q.dtype)

    qp = _ceil_pad(q, cq, 1)
    kp = _ceil_pad(k, ckv, 1)
    vp = _ceil_pad(v, ckv, 1)
    # Distinct negative sentinels for tile padding: q pads get -1, kv pads
    # get -2 — they can never equal each other or any real segment id, and
    # the non-segment path masks kv pads via ``skvc >= 0`` (real data pads
    # use segment 0 per the framework convention).
    seg_q_arr = (jnp.zeros((B, Sq), jnp.int32) if seg_q is None else seg_q)
    seg_kv_arr = (jnp.zeros((B, Skv), jnp.int32) if seg_kv is None else seg_kv)
    seg_qp = _ceil_pad(seg_q_arr, cq, 1, value=-1)
    seg_kvp = _ceil_pad(seg_kv_arr, ckv, 1, value=-2)
    use_segs = seg_q is not None

    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv, dtype=jnp.int32)
    # q pads get position -1: causally masked against every real kv (and
    # their rows are sliced off below); kv pads get the far-future sentinel
    # so position arithmetic alone marks their tiles skippable.
    q_pos_p = _ceil_pad(q_positions.astype(jnp.int32), cq, 0, value=-1)
    kv_pos_p = _ceil_pad(kv_positions.astype(jnp.int32), ckv, 0,
                         value=_PAD_POS)

    nq, nkv = qp.shape[1] // cq, kp.shape[1] // ckv
    qt = qp.reshape(B, nq, cq, Hk, G, D).transpose(1, 0, 2, 3, 4, 5)
    kt = kp.reshape(B, nkv, ckv, Hk, D).transpose(1, 0, 2, 3, 4)
    vt = vp.reshape(B, nkv, ckv, Hk, D).transpose(1, 0, 2, 3, 4)
    sq_t = seg_qp.reshape(B, nq, cq).transpose(1, 0, 2)
    skv_t = seg_kvp.reshape(B, nkv, ckv).transpose(1, 0, 2)
    q_pos_t = q_pos_p.reshape(nq, cq)
    kv_pos_t = kv_pos_p.reshape(nkv, ckv)

    def q_tile(carry, xs):
        del carry
        qc, sqc, q_pos = xs                      # [B,cq,Hk,G,D],[B,cq],[cq]
        # Tile-wide bounds for the skip test.  q pads (pos -1 / seg -1) only
        # loosen the bounds — skipping stays SOUND (a skipped tile provably
        # has no valid (q, kv) pair), just conservative on ragged tails.
        q_pos_max = jnp.max(q_pos)
        q_pos_min = jnp.min(q_pos)
        sq_min, sq_max = jnp.min(sqc), jnp.max(sqc)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_tile(state, xs2):
            # remat: the backward recomputes this tile's logits/probs instead
            # of saving [nq*nkv, cq, ckv] fp32 tensors (which would cost as
            # much as the un-chunked logits)
            kc, vc, skvc, kv_pos = xs2

            # --- static-structure tile skip ------------------------------
            # (skvc bounds span all batch rows: conservative but sound.)
            skip = tiling.tile_skip_predicate(
                q_pos, kv_pos, sq_min, sq_max, skvc, causal=causal,
                local_window_size=local_window_size,
                q_pos_min=q_pos_min, q_pos_max=q_pos_max)

            def compute(state):
                acc, m_run, s_run, n_exec = state
                logits = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc
                                    ).astype(jnp.float32)  # [B,Hk,G,cq,ckv]
                if logits_soft_cap is not None:
                    # Gemma-style cap on the (already scale-folded) logits —
                    # applied per tile BEFORE the online softmax, so the ring
                    # matches SDPA's cap semantics exactly.
                    logits = logits_soft_cap * jnp.tanh(
                        logits / logits_soft_cap)
                valid = tiling.tile_valid_mask(
                    q_pos, kv_pos, sqc, skvc, causal=causal,
                    local_window_size=local_window_size, use_segs=use_segs,
                    batch=B, cq=cq, ckv=ckv)
                logits = jnp.where(valid[:, None, None], logits, _NEG_INF)
                m_b = jnp.maximum(jnp.max(logits, -1), -1e30)
                p = jnp.exp(logits - m_b[..., None])
                p = jnp.where(valid[:, None, None], p, 0.0)
                s_b = jnp.sum(p, -1)
                o_b = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vc.dtype), vc
                                 ).astype(jnp.float32)
                acc, m_new, s_new = tiling.combine_online_softmax(
                    acc, m_run, s_run, o_b, m_b, s_b)
                return (acc, m_new, s_new, n_exec + 1)

            return lax.cond(skip, lambda s: s, compute, state), None

        st0 = (jnp.zeros((B, cq, Hk, G, D), jnp.float32),
               jnp.full((B, Hk, G, cq), _NEG_INF, jnp.float32),
               jnp.zeros((B, Hk, G, cq), jnp.float32),
               jnp.int32(0))
        (acc, m_run, s_run, n_exec), _ = lax.scan(
            kv_tile, st0, (kt, vt, skv_t, kv_pos_t))
        return None, (acc, m_run, s_run, n_exec)

    _, (accs, ms, ss, n_execs) = lax.scan(
        q_tile, None, (qt, sq_t, q_pos_t))
    # [nq,B,cq,...] -> [B,Sq,...]
    out = accs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * cq, Hk, G, D)
    m = ms.transpose(1, 2, 3, 0, 4).reshape(B, Hk, G, nq * cq)
    s = ss.transpose(1, 2, 3, 0, 4).reshape(B, Hk, G, nq * cq)
    if count_tiles:
        return out[:, :Sq], m[..., :Sq], s[..., :Sq], jnp.sum(n_execs)
    return out[:, :Sq], m[..., :Sq], s[..., :Sq]


def ring_attention(
    q: jnp.ndarray,                       # [B, S_local, Hq, D] (per cp shard)
    k: jnp.ndarray,                       # [B, S_local, Hk, D]
    v: jnp.ndarray,
    *,
    axis_name: str = "cp",
    causal: bool = True,
    segment_ids: Optional[jnp.ndarray] = None,   # [B, S_local]
    scale: Optional[float] = None,
    local_window_size: Optional[jnp.ndarray] = None,
    logits_soft_cap: Optional[float] = None,
    layout: str = "contiguous",
) -> jnp.ndarray:
    """Blockwise ring attention; call inside ``shard_map`` with the sequence
    dim sharded over ``axis_name``.  GQA-native (no kv-head repeat).

    ``layout``: how global token positions map onto cp shards — must match
    the host-side batch permutation (``ops/zigzag.py``).  Positions are
    derived per shard from ``lax.axis_index``, so nothing extra rotates
    around the ring.
    """
    B, S, Hq, D = q.shape
    Hk = k.shape[2]
    G = Hq // Hk
    scale = D ** -0.5 if scale is None else scale

    cp = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)

    qg = (q * scale).reshape(B, S, Hk, G, D)
    q_pos = _shard_positions(my_idx, S, cp, layout)

    def attend_and_combine(state, k_t, v_t, seg_t, t):
        acc, m_run, s_run = state
        # the kv block arriving at ring step t left shard (my_idx - t) % cp
        kv_idx = (my_idx - t) % cp
        kv_pos = _shard_positions(kv_idx, S, cp, layout)
        out_b, m_b, s_b = _block_attend(
            qg, k_t, v_t, q_positions=q_pos, kv_positions=kv_pos,
            causal=causal, seg_q=segment_ids, seg_kv=seg_t,
            local_window_size=local_window_size,
            logits_soft_cap=logits_soft_cap)
        return tiling.combine_online_softmax(
            acc, m_run, s_run, out_b, m_b, s_b)

    def body(carry, t):
        k_t, v_t, seg_t, *state = carry
        state = attend_and_combine(tuple(state), k_t, v_t, seg_t, t)
        # rotate kv to the next shard (step t+1 sees neighbor's block)
        perm = [(i, (i + 1) % cp) for i in range(cp)]
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        if seg_t is not None:
            seg_t = lax.ppermute(seg_t, axis_name, perm)
        return (k_t, v_t, seg_t, *state), None

    acc0 = jnp.zeros((B, S, Hk, G, D), jnp.float32)
    m0 = jnp.full((B, Hk, G, S), _NEG_INF, jnp.float32)
    s0 = jnp.zeros((B, Hk, G, S), jnp.float32)
    if cp == 1:
        acc, m_run, s_run = attend_and_combine((acc0, m0, s0), k, v,
                                               segment_ids, 0)
    else:
        # scan the first cp-1 blocks (each ends with a rotation), then attend
        # the final arriving block without a wasted trailing ppermute
        carry = (k, v, segment_ids, acc0, m0, s0)
        (k_f, v_f, seg_f, *state), _ = lax.scan(
            body, carry, jnp.arange(cp - 1))
        acc, m_run, s_run = attend_and_combine(
            tuple(state), k_f, v_f, seg_f, cp - 1)

    denom = jnp.maximum(s_run, 1e-30)                   # [B,Hk,G,Sq]
    out = acc / tiling.rowscale(denom)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def sharded_ring_attention(
    q, k, v, mesh, *,
    causal: bool = True,
    segment_ids=None,
    scale=None,
    local_window_size=None,
    logits_soft_cap=None,
    layout: str = "contiguous",
    batch_axes=None,
    seq_axis: str = "cp",
    head_axis: str = "tp",
):
    """shard_map wrapper: [B, S, H, D] global arrays with S sharded over cp,
    heads over tp, batch over dp (incl. the cross-slice dcn_dp axis) ->
    ring attention per shard.  The caller is responsible for the arrays
    already being in ``layout`` order along S (the recipes permute batches
    host-side; see ``ops/zigzag.py``).  ``batch_axes=None`` (default) uses
    the dp-family axes PRESENT in the mesh; an explicit tuple is used
    verbatim (typos fail loudly)."""
    from jax.sharding import PartitionSpec as P

    from automodel_tpu.distributed.mesh import BATCH_AXES

    if batch_axes is None:
        batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    qspec = P(tuple(batch_axes), seq_axis, head_axis, None)
    sspec = P(tuple(batch_axes), seq_axis)

    fn = functools.partial(
        ring_attention, axis_name=seq_axis, causal=causal, scale=scale,
        local_window_size=local_window_size,
        logits_soft_cap=logits_soft_cap, layout=layout)

    if segment_ids is None:
        def wrapped(q, k, v):
            return fn(q, k, v, segment_ids=None)

        return jax.shard_map(
            wrapped, mesh=mesh, in_specs=(qspec, qspec, qspec),
            out_specs=qspec, check_vma=False)(q, k, v)

    def wrapped(q, k, v, seg):
        return fn(q, k, v, segment_ids=seg)

    return jax.shard_map(
        wrapped, mesh=mesh, in_specs=(qspec, qspec, qspec, sspec),
        out_specs=qspec, check_vma=False)(q, k, v, segment_ids)


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _attention_probe(request) -> bool:
    # context parallelism takes UNCONDITIONAL precedence: windows and soft
    # caps are both applied per tile inside the ring (position arithmetic /
    # tanh before the online softmax), so no cp>1 traffic ever falls
    # through to a path that would assume arange token order — under the
    # zig-zag layout SDPA's built-in causal mask would be silently wrong.
    return bool(request.get("cp_active"))


def _attention_impl(request, q, k, v, *, causal=True, segment_ids=None,
                    attention_mask=None, scale=None, logits_soft_cap=None,
                    local_window_size=None):
    from automodel_tpu.ops.attention import fold_padding_into_segments

    seg = fold_padding_into_segments(q.shape[:2], segment_ids,
                                     attention_mask)
    return sharded_ring_attention(
        q, k, v, request["mesh"], causal=causal, segment_ids=seg,
        scale=scale, local_window_size=local_window_size,
        logits_soft_cap=logits_soft_cap, layout=request.get("cp_layout"))


def _sweep_key_fields(req):
    return autotune.attention_sweep_key_fields(req)


def _sweep_candidates(req):
    out = []
    for cq in (1024, 512, 256):
        for ckv in (1024, 512):
            if cq <= req["q_seq"] and ckv <= req["kv_seq"]:
                out.append((cq, ckv))
    return out or [(min(512, req["q_seq"]), min(1024, req["kv_seq"]))]


def _sweep_run(req, choice) -> float:
    # single-device timing of the blockwise inner attention (the per-ring-
    # step unit of work); the ppermute rotation is tile-size independent
    B = int(req.get("batch", 1))
    S, Skv = req["q_seq"], req["kv_seq"]
    Hq = int(req.get("num_q_heads", 8))
    Hk = int(req.get("num_kv_heads", Hq))
    G, D = Hq // Hk, req["head_dim"]
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, S, Hk, G, D), jnp.float32).astype(dtype)
    k = jax.random.normal(key, (B, Skv, Hk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(key, (B, Skv, Hk, D), jnp.float32).astype(dtype)

    def loss(q, k, v):
        out, m, s = _block_attend(
            q, k, v, causal=bool(req.get("causal", True)),
            seg_q=None, seg_kv=None)
        return jnp.sum(out) + jnp.sum(m) + jnp.sum(s)

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return autotune.time_call(fn, q, k, v)


from automodel_tpu.ops.kernel_lib.parity import sdpa_reference  # noqa: E402

registry.register_kernel(
    "attention.ring", probe=_attention_probe, impl=_attention_impl,
    fallback="attention.splash", reference=sdpa_reference)
autotune.register_sweep(
    "ring", key_fields=_sweep_key_fields, candidates=_sweep_candidates,
    run=_sweep_run)
