"""Splash attention on the real chip: parity vs SDPA and the sharded wrapper."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.attention import dot_product_attention
from automodel_tpu.ops.splash_attention import (
    sharded_splash_attention,
    splash_attention_bshd,
)

B, S, Hq, Hk, D = 2, 1024, 8, 2, 64


def _qkv():
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(kq, (B, S, Hq, D), jnp.bfloat16),
            jax.random.normal(kk, (B, S, Hk, D), jnp.bfloat16),
            jax.random.normal(kv, (B, S, Hk, D), jnp.bfloat16))


def test_forward_and_grads_match_sdpa():
    q, k, v = _qkv()
    seg = np.ones((B, S), np.int32)
    seg[:, S // 2:] = 2
    seg = jnp.asarray(seg)

    out = jax.jit(lambda q, k, v: splash_attention_bshd(
        q, k, v, causal=True, segment_ids=seg))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 0.05

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, segment_ids=seg).astype(jnp.float32) ** 2)

    gs = jax.jit(jax.grad(loss(splash_attention_bshd), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(dot_product_attention), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gs, gr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
        rel = float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale
        assert rel < 0.03


def test_sharded_wrapper_single_chip_mesh():
    from jax.sharding import Mesh

    q, k, v = _qkv()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                ("dp_replicate", "dp_shard", "cp", "tp"))
    out = jax.jit(lambda q, k, v: sharded_splash_attention(
        q, k, v, mesh, causal=True))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 0.05


def test_seq_alignment_padding_on_chip():
    """Odd-128 S (the internal pad-to-256 path) vs SDPA on hardware: the
    off-chip interpret-mode test cannot catch TPU-lowering issues in the
    padded kernel (block geometry, fused backward over padded rows)."""
    S_odd = 1152
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (B, S_odd, Hq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S_odd, Hk, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S_odd, Hk, D), jnp.bfloat16)
    out = jax.jit(lambda q, k, v: splash_attention_bshd(
        q, k, v, causal=True))(q, k, v)
    assert out.shape == (B, S_odd, Hq, D)
    ref = dot_product_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 0.05

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    gs = jax.jit(jax.grad(loss(splash_attention_bshd),
                          argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(dot_product_attention),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gs, gr):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
        assert float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale < 0.06


def _packed_rows(n_rows, S=4096, seed=32):
    """Rows of an SFT mix like the benchmark's ``packed-4k`` (lognormal
    lengths, median 600, sigma 1.2, 16-4096), whole documents laid first
    fit, padding (segment 0) behind."""
    rng = np.random.default_rng(seed)
    rows = np.zeros((n_rows, S), np.int32)
    for row in rows:
        at, seg = 0, 1
        for x in rng.standard_normal(16):
            n = int(np.clip(np.rint(600 * np.exp(1.2 * x)), 16, S))
            if at + n <= S:
                row[at:at + n] = seg
                at, seg = at + n, seg + 1
    return rows


@pytest.mark.parametrize("blocks", [None, (512, 512, 256)],
                         ids=["plan", "edge512"])
@pytest.mark.parametrize("n_rows", [1, 2])
def test_block_map_compiled_natively_at_the_training_cell_shape(
        n_rows, blocks, record_property):
    """S = 4096, 16 heads, D = 128, packed rows whose documents make the
    per-row block map skip blocks, at the plan's edge and at a finer one:
    forward and gradients against SDPA with the traced scalar-prefetch maps
    compiled by Mosaic (the interpret-mode tests prove the logic, not
    this)."""
    from automodel_tpu.ops import splash_attention as sa
    from automodel_tpu.ops.kernel_lib import autotune

    S_, H_, D_ = 4096, 16, 128
    rows = _packed_rows(n_rows)
    blocks = blocks or sa._block_plan(S_, S_, causal=True, local_window=None,
                                      dtype=jnp.bfloat16)
    with autotune.forced("splash", blocks), autotune.forced("splash_bwd",
                                                            blocks):
        run, static = sa.segment_block_counts(rows)
        _check_against_sdpa(rows, S_, H_, D_, record_property)
    record_property("blocks_run", run)
    record_property("blocks_static", static)
    assert run < 0.9 * static       # the rows DO skip blocks
    if n_rows == 2:
        assert (sa._blocks_meet(np, rows[0], rows[0], *blocks[:2])
                != sa._blocks_meet(np, rows[1], rows[1], *blocks[:2])).any()


def _check_against_sdpa(rows, S_, H_, D_, record_property):
    n_rows = len(rows)
    kq, kk, kv = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(kq, (n_rows, S_, H_, D_), jnp.bfloat16)
    k = jax.random.normal(kk, (n_rows, S_, H_, D_), jnp.bfloat16)
    v = jax.random.normal(kv, (n_rows, S_, H_, D_), jnp.bfloat16)
    real = jnp.asarray(rows != 0, jnp.float32)[:, :, None, None]
    seg = jnp.asarray(rows)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v, causal=True, segment_ids=seg)
            out = out.astype(jnp.float32) * real
            return jnp.sum(out ** 2), out
        return f

    (_, out), gs = jax.jit(jax.value_and_grad(
        loss(splash_attention_bshd), argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, ref), gr = jax.jit(jax.value_and_grad(
        loss(dot_product_attention), argnums=(0, 1, 2), has_aux=True))(q, k, v)
    err = float(jnp.max(jnp.abs(out - ref)))
    record_property("fwd_max_abs_err", err)
    assert err < 0.05
    for name, a, b in zip("qkv", gs, gr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
        rel = float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale
        record_property(f"d{name}_rel_err", rel)
        assert rel < 0.03
