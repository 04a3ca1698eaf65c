"""Pallas paged-decode attention — the ``attention.paged_decode`` rung.

Small-q decode/verify over the serving engine's block-paged KV cache
(``ops/paged_attention.py`` owns the family contract).  The per-request
block tables ride SCALAR PREFETCH, so each grid step's BlockSpec index map
steers the DMA at exactly the pool page a row owns for that position range
— the grouped-matmul schedule pattern (``ops/gmm_kernel.py``) applied to
attention.  Per (row, kv-head tile) the kernel walks the row's pages with
a flash-style online softmax in VMEM scratch; pages wholly past the row's
context length are compute-skipped (their DMA fetches the engine's null
page 0, which every pad table entry points at).

**A window layer's walk** (static ``local_window_size``) is
``window_span_blocks`` entries long, not the table's length, and STARTS at
the first entry the row's window touches: that index rides scalar prefetch
beside the context lengths (a full layer's program carries none) and is
added to the grid index in every page's index map.  So the time of a window layer does not
grow with the context past the window, and the entries before the start,
whose blocks the engine's window group has released, are never fetched.

**Chunked q**: the kernel serves any small query length ``S`` — plain
decode (S=1), the speculative verify step (S=spec_k+1) and chunked
prefill — by FOLDING the S query tokens into the query-group dim (one
``(kt, S*G, D) x (kt, BS, D)`` contraction per page; no second grid
axis, no new schedule).  Per-query causality needs one extra scalar:
each row's FIRST query position rides prefetch, and query ``s`` masks
``kv_pos <= pos0 + s`` — valid because the engine writes a row's step
tokens at CONSECUTIVE positions (the family contract; pad columns repeat
the last valid position and their outputs are discarded by the caller,
so the consecutive assumption only over-attends garbage columns).  At
S=1 the mask degenerates to the classic ``kv_pos < ctx`` decode mask
bit-exactly.

**Two bodies, chosen by the pool's rank.**  A pool of heads ``[L, NB, BS,
Hk, D]`` (OLMo's 16 kv heads, every int8 pool) reaches the body as a
``(BS, kt, D)`` block, upcast to float32 and transposed to ``(kt, BS, D)``
for one batched product a page.  A pool stored as ROWS ``[L, NB, BS, Hk *
D]`` (``paged_attention.stores_rows``: fewer kv heads than the dtype's
sublane packing, where a ``(kt, D)`` slice fills a fraction of a tile)
reaches it as a dense ``(BS, kt * D)`` block, and the body works on the
page as it lies: per kv head the lane-aligned slice ``[:, h*D:(h+1)*D]``,
``q . k^T`` as one NT product on the stored dtype with float32
accumulation, and ``p . v`` as two such products of ``p``'s bfloat16 head
and remainder, so ``p`` keeps float32 accuracy; no float32 page and no
transpose.

Quantized (int8) pools dequantize IN VMEM with the per-slot scale planes
(PR-10's ``quant_cast`` contract inverted), so the HBM traffic — the thing
decode is bound by — is 1 byte per cached element instead of 2.

Autotune (key ``"paged_decode"``): the kv-head tile ``kt`` — how many kv
heads (with their ``G`` query heads each) one grid step processes.  Larger
tiles amortize grid/DMA overhead, smaller ones bound the VMEM working set;
candidates are the divisors of ``Hk`` that fit the shared byte model.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling
from automodel_tpu.ops.paged_attention import (
    kv_heads,
    paged_reference,
    window_first_block,
    window_span_blocks,
)

# Pallas interpret mode: lets the CPU test suite execute the real kernel
# logic (tests monkeypatch this, mirroring ops/gmm_kernel.py).
_INTERPRET = False

_LANE = tiling.LANE
_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# q lengths the fold-into-groups schedule stays profitable (and VMEM-sane)
# for: decode (1), speculative verify (spec_k+1) and chunked prefill up to
# a chunk of 128 (nine query heads a kv head fold to 1,152 rows, a tile of
# 2.5 MB a kv head); longer prefill belongs to the dense-attention path.
_MAX_CHUNKED_Q = 128


def paged_decode_available(q_seq: int, head_dim: int) -> bool:
    """Kernel path requires small queries (1 <= S <= 128 — decode, the
    speculative verify width, chunked prefill), a lane-aligned head dim,
    and TPU (or interpret mode)."""
    if not 1 <= q_seq <= _MAX_CHUNKED_Q or head_dim % _LANE:
        return False
    if _INTERPRET:
        return True
    return registry.on_tpu()


def _tile_bytes(kt: int, ge: int, bs: int, d: int, kv_itemsize: int,
                quantized: bool) -> int:
    """VMEM working set of one (row, kv-head-tile) grid step: the
    double-buffered k/v page blocks (+ int8 scale planes), the resident q
    block, and the fp32 online-softmax scratch.  ``ge`` is the EFFECTIVE
    query-group size ``S * G`` — chunked q folds the S query tokens into
    the group dim, so they scale the q/scratch terms exactly like extra
    query heads.  ONE byte model — shared by the runtime default/validate
    AND the sweep's candidate filter."""
    pages = 2 * 2 * bs * kt * d * kv_itemsize          # k+v double-buffered
    if quantized:
        pages += 2 * 2 * bs * kt * 4                   # scale planes
    q = kt * ge * d * 4
    scratch = kt * ge * d * 4 + 2 * kt * ge * 128 * 4  # acc + m/l
    return pages + q + scratch


def _head_tile(hk: int, g: int, s: int, bs: int, d: int, kv_itemsize: int,
               quantized: bool, pages: int, dtype: str, rows: bool) -> int:
    """kv-head tile via divisor search under the VMEM budget, overridden
    by a persisted autotune winner (kernel key ``"paged_decode"``)."""
    budget = tiling.DEFAULT_TILE_BUDGET_BYTES

    def fits(kt: int) -> bool:
        return _tile_bytes(kt, s * g, bs, d, kv_itemsize, quantized) <= budget

    divisors = [kt for kt in range(hk, 0, -1) if hk % kt == 0]
    default = next((kt for kt in divisors if fits(kt)), 1)
    fields = {"hk": hk, "g": g, "s": s, "bs": bs, "d": d,
              "pages": autotune.shape_bucket(pages), "dtype": dtype,
              "quant": quantized, "rows": rows}
    choice = autotune.lookup(
        "paged_decode", fields, (default,),
        validate=lambda c: (len(c) == 1 and c[0] >= 1 and hk % c[0] == 0
                            and fits(c[0])))
    return int(choice[0])


def _rows_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, j, ctx, pos0,
               bs, kt, g, scale, soft_cap, window):
    """One page of a pool stored as rows: ``k_ref``/``v_ref`` hold the
    page's ``(BS, kt * D)`` block, ``q_ref`` the tile's ``(kt, S*G, D)``
    queries, the scratch ``(kt, S*G, .)`` rows a kv head.  Per kv head the
    page's lane-aligned ``(BS, D)`` slice enters both products as stored."""
    _, ge, d = q_ref.shape[1:]
    kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (ge, bs), 1)
    qpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (ge, bs), 0) // g
    valid = (kv_pos < ctx) & (kv_pos <= qpos)
    if window is not None:
        valid &= kv_pos > qpos - window
    dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    for h in range(kt):
        lanes = slice(h * d, (h + 1) * d)
        k = k_ref[0, :, lanes].astype(dt)                  # (BS, D)
        # (S*G, D) x (BS, D)^T -> (S*G, BS): NT, the page as it lies
        s = jax.lax.dot_general(
            q_ref[0, h].astype(dt), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_ref[h][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, :, lanes].astype(dt)                  # (BS, D)
        o = _probs_times(p, v)
        acc_ref[h] = acc_ref[h] * alpha + o
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _probs_times(p, v):
    """``p (S*G, BS) float32 . v (BS, D)`` with float32 accumulation.  A
    float32 page multiplies ``p`` as it is; a narrower one takes ``p`` as
    its head in ``v``'s dtype plus the remainder, two products on the
    stored page, so ``p`` is never rounded once to ``v``'s dtype."""
    dims = (((1,), (0,)), ((), ()))
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, dims,
                                   preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return (jax.lax.dot_general(hi, v, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(lo, v, dims,
                                  preferred_element_type=jnp.float32))


def _decode_kernel(bt_ref, cl_ref, p0_ref, ly_ref, *refs, bs, kt, g, s_q,
                   scale, soft_cap, window, quantized, rows):
    from jax.experimental import pallas as pl

    b, i = pl.program_id(0), pl.program_id(2)
    nj = pl.num_programs(2)
    if window is None:
        j = i                    # a full layer walks the table from 0
    else:
        # a window layer's walk starts at the row's own entry: one scalar
        # more on prefetch, which a full layer's program does not carry (a
        # grid step of a skipped page is all scalar work, and every load
        # in it shows: +9 % kernel time where the walk is mostly skips)
        st_ref, *refs = refs
        j = st_ref[b] + i
    (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
     acc_ref) = refs
    ge = s_q * g                 # S query tokens folded into the group dim

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = cl_ref[b]

    if rows:
        @pl.when(j * bs < ctx)
        def _compute_rows():
            _rows_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, j=j,
                       ctx=ctx, pos0=p0_ref[b], bs=bs, kt=kt, g=g,
                       scale=scale, soft_cap=soft_cap, window=window)

        @pl.when(i == nj - 1)
        def _finish_rows():
            l = l_ref[..., :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        return

    @pl.when(j * bs < ctx)
    def _compute():
        def page(ref, s_ref):
            x = ref[0].astype(jnp.float32)          # (BS, kt, D)
            if quantized:
                x = x * s_ref[0].astype(jnp.float32)[..., None]
            return jnp.swapaxes(x, 0, 1)            # (kt, BS, D)

        q = q_ref[0].astype(jnp.float32)            # (kt, S*G, D)
        k = page(k_ref, ks_ref)
        # (kt, S*G, D) x (kt, BS, D) -> (kt, S*G, BS), kv heads batched
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        kv_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (kt, ge, bs), 2)
        # per-query position: row r of the folded dim is query token
        # r // g at position pos0 + r // g (consecutive-position contract)
        qpos = p0_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (kt, ge, bs), 1) // g
        valid = (kv_pos < ctx) & (kv_pos <= qpos)
        if window is not None:
            valid &= kv_pos > qpos - window
        s = jnp.where(valid, s, _NEG_INF)

        s2 = s.reshape(kt * ge, bs)
        m_prev = m_ref[:, :1]
        m_b = jnp.max(s2, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_b)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s2 - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)

        v = page(v_ref, vs_ref)                     # (kt, BS, D)
        o_b = jax.lax.dot_general(
            p.reshape(kt, ge, bs), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # (kt, S*G, D)
        acc_ref[...] = acc_ref[...] * alpha + o_b.reshape(kt * ge, -1)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).reshape(o_ref.shape).astype(
            o_ref.dtype)


def paged_decode_pallas(q, k_pool, v_pool, k_scale, v_scale, layer,
                        block_tables, context_lens, positions=None, *,
                        scale=None, logits_soft_cap=None,
                        local_window_size=None):
    """``q [B, S, Hq, D]`` (small S — decode 1, verify spec_k+1, chunked
    prefill) over layer ``layer`` (int32 scalar, traced or not) of the
    stacked position-major pools ``[L, NB, BS, Hk, D]`` (+ optional int8
    scale planes ``[L, NB, BS, Hk]``), or pools stored as rows ``[L, NB,
    BS, Hk * D]``, -> ``[B, S, Hq, D]``; the pool's rank picks the body
    (module docstring).  The layer index rides scalar prefetch beside the
    block tables and leads every page's index map, so the kernel's operand
    is the whole stacked pool and only the pages a row owns in that layer
    are ever read.

    ``positions [B, S]``: each query token's absolute position.  The
    kernel prefetches only column 0 and derives the rest as ``pos0 + s``
    — the engine writes a row's step tokens at consecutive positions (pad
    columns repeat the last valid position; their outputs are garbage the
    caller discards).  None (legacy S=1 decode callers) means
    ``context_lens - 1``."""
    from jax.experimental import pallas as pl

    B, S, Hq, D = q.shape
    rows = k_pool.ndim == 4
    BS, Hk = k_pool.shape[2], kv_heads(k_pool, D)
    MB = block_tables.shape[1]
    assert S <= _MAX_CHUNKED_Q, "paged_decode is the small-q rung"
    G = Hq // Hk
    GE = S * G                    # S query tokens folded into the group dim
    scale = D ** -0.5 if scale is None else scale
    quantized = k_scale is not None
    kt = _head_tile(Hk, G, S, BS, D, k_pool.dtype.itemsize, quantized, MB,
                    str(q.dtype), rows)
    if positions is None:
        assert S == 1, "q_seq > 1 requires explicit positions"
        pos0 = context_lens.astype(jnp.int32) - 1
    else:
        pos0 = positions[:, 0].astype(jnp.int32)
    # a window layer walks the blocks its window spans, from the first one
    # the row's first query sees; a full layer walks the table from 0
    windowed = local_window_size is not None
    walk, start = MB, ()
    if windowed:
        walk = min(MB, window_span_blocks(local_window_size, S, BS))
        start = (window_first_block(pos0, local_window_size, BS),)

    # [B, S, Hq, D] -> [B, S, Hk, G, D] -> [B, Hk, S, G, D] -> fold (S, G)
    q4 = q.reshape(B, S, Hk, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, GE, D)
    if quantized:
        # The scale planes' minor dims (BS, Hk) are far under a lane tile,
        # so XLA keeps the planes NB-minor on the device while a Mosaic
        # operand is row-major: handing over the stacked plane would relay
        # out all L layers of it on every call.  One layer's slice costs
        # 1/L of that (a plane is 1/32 of its pool at D=128).
        k_scale, v_scale = (jax.lax.dynamic_slice_in_dim(x, layer, 1)
                            for x in (k_scale, v_scale))
    else:
        # uniform kernel signature: zero-page dummies the specs still index
        k_scale = jnp.ones((1, 1, BS, Hk), jnp.float32)
        v_scale = jnp.ones((1, 1, BS, Hk), jnp.float32)

    def entry(b, i, bt, st):
        if not windowed:
            return bt[b, i]
        # past the table's end the last entry again: no new fetch, and the
        # body skips it (its keys would lie past any context)
        return bt[b, jnp.minimum(st[0][b] + i, MB - 1)]

    def page_index(b, h, i, bt, cl, p0, ly, *st):
        if rows:
            return (ly[0], entry(b, i, bt, st), 0, h)
        return (ly[0], entry(b, i, bt, st), 0, h, 0)

    def scale_index(b, h, i, bt, cl, p0, ly, *st):
        return (0, entry(b, i, bt, st) if quantized else 0, 0, h)

    def q_index(b, h, i, bt, cl, p0, ly, *st):
        return (b, h, 0, 0)

    page = (None, 1, BS, kt * D) if rows else (None, 1, BS, kt, D)
    tile = (kt, GE) if rows else (kt * GE,)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, bs=BS, kt=kt, g=G, s_q=S, scale=scale,
            soft_cap=logits_soft_cap, window=local_window_size,
            quantized=quantized, rows=rows),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=4 + windowed,
            grid=(B, Hk // kt, walk),
            in_specs=[
                tiling.block_spec((1, kt, GE, D), q_index),
                # the layer axis is squeezed: the body sees one page
                tiling.block_spec(page, page_index),
                tiling.block_spec(page, page_index),
                tiling.block_spec((None, 1, BS, kt), scale_index),
                tiling.block_spec((None, 1, BS, kt), scale_index),
            ],
            out_specs=tiling.block_spec((1, kt, GE, D), q_index),
            # m, l and the accumulator: a rows body indexes them by kv head
            scratch_shapes=[
                _scratch((*tile, 128), jnp.float32),
                _scratch((*tile, 128), jnp.float32),
                _scratch((*tile, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hk, GE, D), q.dtype),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_INTERPRET,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      pos0, jnp.asarray(layer, jnp.int32).reshape(1), *start, q4, k_pool,
      v_pool, k_scale, v_scale)
    # unfold (S, G) and restore [B, S, Hq, D]
    return out.reshape(B, Hk, S, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, S, Hq, D)


def _scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Registry rung + autotune adapter
# ---------------------------------------------------------------------------
def _paged_decode_probe(request) -> bool:
    return paged_decode_available(request["q_seq"], request["head_dim"])


def _paged_decode_impl(request, q, k_pool, v_pool, k_scale, v_scale, layer,
                       block_tables, context_lens, positions, *,
                       scale=None, logits_soft_cap=None,
                       local_window_size=None, kernel_name=None):
    # ``paged_decode`` is the name to read in a trace.  XLA:TPU names a
    # Mosaic custom call after the innermost component of its scope path,
    # and ``benchmark/rooflines/paged_decode.py`` finds this kernel by the
    # name it had while nothing was scoped: ``closed_call``, the layer
    # scan's body.  The inner scope keeps that name until the reader is
    # repointed at ``paged_decode`` (ROADMAP Design 10); then it goes.  A
    # cache of several block groups runs one kernel a group in one program
    # and names each (``PagedKVView.attend``).
    with jax.named_scope("paged_decode"), \
            jax.named_scope(kernel_name or "closed_call"):
        return paged_decode_pallas(
            q, k_pool, v_pool, k_scale, v_scale, layer, block_tables,
            context_lens, positions, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size)


def _sweep_key_fields(req):
    g = req["num_q_heads"] // req["num_kv_heads"]
    return {"hk": req["num_kv_heads"], "g": g,
            # q length is a tiling dimension now (it folds into the group
            # dim): decode (1), the speculative verify width and chunked
            # prefill each get their own sweep entry
            "s": int(req.get("q_seq", 1)),
            "bs": req["block_size"], "d": req["head_dim"],
            "pages": autotune.shape_bucket(req["pages_per_seq"]),
            "dtype": str(req.get("dtype", "bfloat16")),
            "quant": bool(req.get("quantized")),
            "rows": bool(req.get("rows"))}


def _sweep_candidates(req):
    hk, d, bs = req["num_kv_heads"], req["head_dim"], req["block_size"]
    ge = (req["num_q_heads"] // hk) * int(req.get("q_seq", 1))
    item = 1 if req.get("quantized") else 2
    return [(kt,) for kt in range(hk, 0, -1)
            if hk % kt == 0
            and _tile_bytes(kt, ge, bs, d, item, bool(req.get("quantized")))
            <= tiling.DEFAULT_TILE_BUDGET_BYTES]


def _sweep_run(req, choice) -> float:
    hk, d, bs = req["num_kv_heads"], req["head_dim"], req["block_size"]
    hq, mb = req["num_q_heads"], req["pages_per_seq"]
    s = int(req.get("q_seq", 1))
    b = int(req.get("batch", 8))
    nb = b * mb + 1
    quant = bool(req.get("quantized"))
    key = jax.random.key(0)
    dtype = jnp.dtype(req.get("dtype", "bfloat16"))
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32).astype(dtype)
    if quant:
        kp = jax.random.randint(key, (1, nb, bs, hk, d), -127, 128, jnp.int8)
        vp = kp
        ks = jnp.full((1, nb, bs, hk), 0.01, jnp.float32)
        vs = ks
    else:
        slot = (hk * d,) if req.get("rows") else (hk, d)
        kp = jax.random.normal(key, (1, nb, bs, *slot), jnp.float32).astype(
            dtype)
        vp = kp
        ks = vs = None
    tables = jnp.arange(1, 1 + b * mb, dtype=jnp.int32).reshape(b, mb)
    ctx = jnp.full((b,), mb * bs, jnp.int32)
    pos = ctx[:, None] - s + jnp.arange(s, dtype=jnp.int32)[None, :]

    fn = jax.jit(functools.partial(paged_decode_pallas, scale=None))
    return autotune.time_call(fn, q, kp, vp, ks, vs, jnp.int32(0), tables,
                              ctx, pos)


registry.register_kernel(
    "attention.paged_decode", probe=_paged_decode_probe,
    impl=_paged_decode_impl, fallback="attention.paged_gather",
    reference=paged_reference)
autotune.register_sweep(
    "paged_decode", key_fields=_sweep_key_fields,
    candidates=_sweep_candidates, run=_sweep_run)
