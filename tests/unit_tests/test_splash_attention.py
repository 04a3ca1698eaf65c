"""Splash attention vs SDPA parity — runs the real kernel logic in Pallas
interpret mode on the CPU suite; on-hardware checks live in ``tpu_tests/``.

The common shape/segment/GQA matrix now lives in the SHARED parity harness
(``ops/kernel_lib/parity.py``, driven by ``test_kernel_substrate.py``);
this module keeps the splash-SPECIFIC edges: the pad-to-256 alignment
path, LocalMask window-boundary discrimination, and gradient parity.

D=128: this JAX's upstream MQA kernel requires ``head_dim % 128 == 0`` at
trace time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops import splash_attention as sa
from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.kernel_lib import parity

B, S, Hq, Hk, D = 1, 256, 4, 2, 128


@pytest.fixture(autouse=True)
def _interpret_mode():
    with parity.interpret_mode():
        yield


def _qkv(seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (B, S, Hq, D), jnp.float32),
            jax.random.normal(kk, (B, S, Hk, D), jnp.float32),
            jax.random.normal(kv, (B, S, Hk, D), jnp.float32))


def test_causal_matches_sdpa():
    q, k, v = _qkv()
    out = sa.splash_attention_bshd(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_segment_ids_isolate_documents():
    q, k, v = _qkv(1)
    seg = np.ones((B, S), np.int32)
    seg[:, S // 2:] = 2
    seg = jnp.asarray(seg)
    out = sa.splash_attention_bshd(q, k, v, causal=True, segment_ids=seg)
    ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_padding_mask_folds_to_segments():
    q, k, v = _qkv(2)
    pad = np.ones((B, S), np.int32)
    pad[:, -32:] = 0
    pad = jnp.asarray(pad)
    out = sa.splash_attention_bshd(q, k, v, causal=True, attention_mask=pad)
    ref = dot_product_attention(q, k, v, causal=True, attention_mask=pad)
    np.testing.assert_allclose(np.asarray(out)[:, :S - 32],
                               np.asarray(ref)[:, :S - 32],
                               atol=2e-3, rtol=2e-3)


def test_soft_cap():
    q, k, v = _qkv(3)
    out = sa.splash_attention_bshd(q, k, v, causal=True, logits_soft_cap=30.0)
    ref = dot_product_attention(q, k, v, causal=True, logits_soft_cap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_gradients_match_sdpa():
    q, k, v = _qkv(4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    gs = jax.grad(loss(sa.splash_attention_bshd), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-3


def test_seq_alignment_padding_matches_sdpa():
    """S = odd multiple of 128 routes through the internal pad-to-256 path
    (kernel blocks stay >= 256): outputs and gradients must equal SDPA on
    the unpadded shape, with and without segment ids."""
    S_odd = 384        # % 256 != 0 -> internal pad to 512
    kq, kk, kv = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(kq, (B, S_odd, Hq, D), jnp.float32)
    k = jax.random.normal(kk, (B, S_odd, Hk, D), jnp.float32)
    v = jax.random.normal(kv, (B, S_odd, Hk, D), jnp.float32)

    out = sa.splash_attention_bshd(q, k, v, causal=True)
    assert out.shape == (B, S_odd, Hq, D)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)

    seg = np.ones((B, S_odd), np.int32)
    seg[:, S_odd // 2:] = 2
    seg = jnp.asarray(seg)
    out = sa.splash_attention_bshd(q, k, v, causal=True, segment_ids=seg)
    ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    gs = jax.grad(loss(sa.splash_attention_bshd), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-3


def test_seq_alignment_padding_sliding_window():
    """Alignment padding composes with LocalMask sliding windows."""
    S_odd = 384
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (B, S_odd, Hq, D), jnp.float32)
    k = jax.random.normal(kk, (B, S_odd, Hk, D), jnp.float32)
    v = jax.random.normal(kv, (B, S_odd, Hk, D), jnp.float32)
    out = sa.splash_attention_bshd(q, k, v, causal=True,
                                   local_window_size=32)
    ref = dot_product_attention(q, k, v, causal=True, local_window_size=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_sliding_window_local_mask():
    """LocalMask wiring: window w must match SDPA's q - kv < w exactly
    (discriminates w from w±1)."""
    q, k, v = _qkv(5)
    for w in (7, 32):
        out = sa.splash_attention_bshd(q, k, v, causal=True,
                                       local_window_size=w)
        ref = dot_product_attention(q, k, v, causal=True,
                                    local_window_size=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)
        off = dot_product_attention(q, k, v, causal=True,
                                    local_window_size=w + 1)
        assert float(jnp.max(jnp.abs(out - off))) > 1e-2  # w+1 would differ


# ---------------------------------------------------------------------------
# The per-row block map (segment ids -> block_mask / data_next).  Marked
# ``core``: the map tests run no kernel, and the one interpret-mode run is
# small, so they stay in the fast tier though this module is a slow one.
# ---------------------------------------------------------------------------
def _row(lengths, S, ids=None):
    """[S] segment ids of documents of those lengths, padding (0) behind."""
    ids = ids or range(1, len(lengths) + 1)
    seg = np.zeros(S, np.int32)
    at = 0
    for n, i in zip(lengths, ids):
        seg[at:at + n] = i
        at += n
    return seg


_MAP_S = 4096
_RNG = np.random.default_rng(32)
_FINE = (512, 512, 256)        # an edge at which short documents skip blocks
_MAP_CASES = {
    # name: (rows, local window, blocks or None for the plan's, tight)
    "tail_padding": ([_row([700, 1300, 90, 1500], _MAP_S)], None, None, True),
    "tail_padding_fine_edge": (
        [_row([700, 1300, 90, 1500], _MAP_S)], None, _FINE, True),
    "one_4096_document": ([_row([4096], _MAP_S)], None, _FINE, True),
    "documents_end_on_block_edges": (
        [_row([512, 1024, 512, 1536, 512], _MAP_S)], None, _FINE, True),
    "ids_not_monotone": (
        [_row([600, 900, 300, 1200, 700], _MAP_S, ids=[3, 1, 7, 1, 2])],
        None, _FINE, False),
    "padding_in_front": (
        [np.roll(_row([1500, 1700], _MAP_S), 896)], None, _FINE, False),
    "two_rows_with_different_maps": (
        [_row([2500, 40, 1500], _MAP_S), _row([300] * 13, _MAP_S)],
        None, None, True),
    "random_packed_rows": (
        [_row(_RNG.integers(16, 900, 12).tolist()[:n], _MAP_S)
         for n in (5, 8, 12)], None, None, True),
    "random_packed_rows_fine_edge": (
        [_row(_RNG.integers(16, 900, 12).tolist()[:n], _MAP_S)
         for n in (5, 8, 12)], None, (256, 256, 128), True),
    "sliding_window": ([_row([1800, 200, 2000], _MAP_S)], 1024, None, True),
    "sliding_window_shrunk_grid": (
        [_row([1800, 200, 2000], _MAP_S)], 512, (256, 256, 128), True),
    "rectangular_blocks": (
        [_row([700, 1300, 90, 1500], _MAP_S)], None, (512, 256, 256), False),
}


def _dense(info, nkv_or_nq, dkv):
    """[q_blocks, kv_blocks] 0/1 of the blocks a MaskInfo runs (a grid that
    was shrunk to a window's width holds each block's own index in
    ``data_next``)."""
    mask = np.asarray(info.block_mask[0])
    own = np.asarray(info.data_next[0]).astype(int)
    out = np.zeros((nkv_or_nq, mask.shape[1]) if dkv
                   else (mask.shape[0], nkv_or_nq), int)
    for i, j in zip(*np.nonzero(mask)):
        out[(own[i, j], j) if dkv else (i, own[i, j])] = 1
    return out


def _walk_next(info, dkv):
    """data_next as the library defines it, from block_mask alone: the own
    index of the next block that runs in the order the grid is walked,
    wrapping to the first."""
    mask = np.asarray(info.block_mask[0])
    own = np.asarray(info.data_next[0]).astype(int)
    order = ([(i, j) for j in range(mask.shape[1])
              for i in range(mask.shape[0])] if dkv
             else [(i, j) for i in range(mask.shape[0])
                   for j in range(mask.shape[1])])
    running = [p for p in order if mask[p]]
    want = np.zeros_like(own)
    for at, p in enumerate(order):
        following = [r for r in running if order.index(r) >= at]
        want[p] = own[(following or running)[0]]
    return want


@pytest.mark.core
@pytest.mark.parametrize("case", sorted(_MAP_CASES))
def test_block_map_against_brute_force_mask(case):
    rows, window, blocks, tight = _MAP_CASES[case]
    S = _MAP_S
    plan = sa._block_plan(S, S, causal=True, local_window=window,
                          dtype=jnp.bfloat16)
    blocks = blocks or plan
    kernel = sa._build_kernel(S, S, 1, True, None, interpret=True,
                              local_window=window, blocks=blocks,
                              bwd_blocks=blocks)
    bq, bkv = blocks[:2]
    nq, nkv = S // bq, S // bkv
    pos = np.arange(S)
    allowed = pos[None, :] <= pos[:, None]
    if window is not None:
        allowed &= pos[:, None] - pos[None, :] < window
    traced_sum = 0
    seen = []
    for seg in rows:
        mapped = jax.jit(lambda s: sa._segment_block_maps(
            kernel, s, blocks, blocks))(jnp.asarray(seg))
        pairs = allowed & (seg[:, None] == seg[None, :])      # [S, S]
        holds = pairs.reshape(nq, bq, nkv, bkv).any(axis=(1, 3))
        for info, static, dkv in (
                (mapped.fwd_mask_info, kernel.fwd_mask_info, False),
                (mapped.dkv_mask_info, kernel.dkv_mask_info, True)):
            assert info.block_mask.dtype == static.block_mask.dtype
            assert info.data_next.dtype == static.data_next.dtype
            assert info.block_mask.shape == static.block_mask.shape
            runs = _dense(info, nq if dkv else nkv, dkv)
            # never 0 where a pair is unmasked
            assert not (holds & (runs == 0)).any()
            # only blocks of the static mask, with the static mask's value
            kept = np.asarray(info.block_mask) != 0
            assert (np.asarray(info.block_mask)[kept]
                    == np.asarray(static.block_mask)[kept]).all()
            # every query block keeps the block of its own (q, q) pairs
            for i in range(nq):
                assert runs[i, (i * bq) // bkv] == 1
                assert runs[i, ((i + 1) * bq - 1) // bkv] == 1
            if tight:
                assert (runs == holds).all()
            # a skipped block points at the next one that runs; a running
            # one at itself
            np.testing.assert_array_equal(
                np.asarray(info.data_next[0]), _walk_next(info, dkv))
        traced_sum += int((np.asarray(
            mapped.fwd_mask_info.block_mask) != 0).sum())
        seen.append(np.asarray(mapped.fwd_mask_info.block_mask))
    from automodel_tpu.ops.kernel_lib import autotune

    with autotune.forced("splash", blocks):
        run, static = sa.segment_block_counts(
            np.stack(rows), local_window_size=window)
    assert run == traced_sum
    assert static == len(rows) * int((np.asarray(
        kernel.fwd_mask_info.block_mask) != 0).sum())
    assert run <= static
    if case == "one_4096_document":
        # the worst case runs exactly the static blocks
        np.testing.assert_array_equal(
            seen[0], np.asarray(kernel.fwd_mask_info.block_mask))
    if case == "two_rows_with_different_maps":
        assert (seen[0] != seen[1]).any()


@pytest.mark.core
def test_skipped_blocks_leave_forward_and_gradients_unchanged():
    """A row where blocks ARE skipped (GQA, two rows with different maps,
    tail padding): against SDPA on every real position, and against the
    same kernel with its static map (the parent's) bit for bit."""
    from automodel_tpu.ops.kernel_lib import autotune

    S2, blocks = 1024, (256, 256, 128)
    kq, kk, kv = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(kq, (2, S2, Hq, D), jnp.float32)
    k = jax.random.normal(kk, (2, S2, Hk, D), jnp.float32)
    v = jax.random.normal(kv, (2, S2, Hk, D), jnp.float32)
    seg = np.stack([_row([300, 220, 380], S2), _row([100, 924], S2)])
    with autotune.forced("splash", blocks), autotune.forced("splash_bwd",
                                                            blocks):
        run, static = sa.segment_block_counts(seg)
        real = jnp.asarray(seg != 0, jnp.float32)[:, :, None, None]
        seg = jnp.asarray(seg)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                (fn(q, k, v, causal=True, segment_ids=seg) * real) ** 2)

        def unmapped(q, k, v, **kw):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(sa, "_segment_block_maps",
                           lambda kernel, *a: kernel)
                return sa.splash_attention_bshd(q, k, v, **kw)

        out = sa.splash_attention_bshd(q, k, v, causal=True, segment_ids=seg)
        ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out * real),
                                   np.asarray(ref * real),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_array_equal(
            np.asarray(out * real),
            np.asarray(unmapped(q, k, v, causal=True, segment_ids=seg)
                       * real))
        gs = jax.grad(loss(sa.splash_attention_bshd), (0, 1, 2))(q, k, v)
        gr = jax.grad(loss(dot_product_attention), (0, 1, 2))(q, k, v)
        gu = jax.grad(loss(unmapped), (0, 1, 2))(q, k, v)
    assert run < static         # blocks were skipped: 17 of 20
    for a, b, u in zip(gs, gr, gu):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < 5e-3
        np.testing.assert_array_equal(np.asarray(a), np.asarray(u))


@pytest.mark.core
def test_without_segment_ids_the_kernel_is_the_static_one(monkeypatch):
    """Dense calls never build a map: the program holds the cached kernel's
    own mask info and nothing of the map's arithmetic."""
    def refuse(*a, **k):
        raise AssertionError("a dense call built a block map")

    monkeypatch.setattr(sa, "_segment_block_maps", refuse)
    q, k, v = _qkv(9)
    jaxpr = jax.make_jaxpr(lambda q, k, v: sa.splash_attention_bshd(
        q, k, v, causal=True))(q, k, v)
    assert "cummin" not in str(jaxpr)
    kernel = sa._build_kernel(S, S, Hq // Hk, True, None, interpret=True,
                              local_window=None,
                              blocks=sa._block_plan(
                                  S, S, causal=True, local_window=None,
                                  dtype=q.dtype),
                              bwd_blocks=None)
    consts = {np.asarray(c).tobytes() for c in jaxpr.consts
              if np.asarray(c).dtype == np.int8}
    assert np.asarray(kernel.fwd_mask_info.block_mask).tobytes() in consts
    monkeypatch.undo()
    seg = jnp.ones((B, S), jnp.int32)
    assert "cummin" in str(jax.make_jaxpr(
        lambda q, k, v: sa.splash_attention_bshd(
            q, k, v, causal=True, segment_ids=seg))(q, k, v))


@pytest.mark.core
def test_train_loop_counts_the_blocks_its_rows_run():
    from automodel_tpu.recipes.llm import train_ft

    rows = np.stack([_row([700, 1300, 90, 1500], _MAP_S),
                     _row([4096], _MAP_S)])
    got = train_ft._attn_blocks([{"segment_ids": rows[:1]},
                                 {"segment_ids": rows[1:]}])
    run, static = sa.segment_block_counts(rows)
    assert got == {"attn_blocks_run": run, "attn_blocks_static": static}
    assert 0 < run < static
    assert train_ft._attn_blocks([{"input_ids": rows}]) == {}
    note = train_ft._attn_blocks_note([{"segment_ids": r} for r in rows])
    assert "attn_blocks_run_share %.4f" % (run / static) in note
