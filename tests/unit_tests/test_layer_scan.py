"""The seam of ``models/layer_scan.py``: the two decode caches are one
protocol, and the one layer loop is the plain loop over the layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import DenseKVView
from automodel_tpu.models.layer_scan import (
    SubStack,
    dense_kv_state,
    scan_layers,
)
from automodel_tpu.serving.kv_cache import PagedKVView, init_paged_pools


@pytest.mark.parametrize("window", [None, 3], ids=["full", "window3"])
def test_dense_and_paged_views_attend_alike(window):
    """The same per-head k/v, written through ``DenseKVView`` and through
    ``PagedKVView`` at layer 1 of 2 — a prefill of five tokens, then three
    decode steps — attend to the same output: a model's attention cannot
    tell which cache it was handed."""
    L, B, Hq, Hk, D, BS, P, steps = 2, 2, 4, 2, 8, 4, 5, 3
    S_max = P + steps
    MB = -(-S_max // BS)
    rng = np.random.default_rng(3)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    layer = jnp.int32(1)

    dense = dense_kv_state(L, B, S_max, (Hk, D), jnp.float32)
    paged = init_paged_pools(
        num_layers=L, num_blocks=1 + B * MB, block_size=BS,
        cache_dtype=jnp.float32, quantized=False,
        planes={"k": (Hk, D), "v": (Hk, D)})
    # row b owns blocks 1 + b*MB ..; block 0 is the null page
    tables = 1 + np.arange(B * MB, dtype=np.int32).reshape(B, MB)

    @jax.jit
    def step(dense, paged, q, k, v, start):
        S = q.shape[1]
        pos = start + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        slots = jnp.take_along_axis(jnp.asarray(tables), pos // BS,
                                    axis=1) * BS + pos % BS
        views = (
            DenseKVView.at(dense, start, S),
            PagedKVView(paged, jnp.asarray(tables), slots,
                        jnp.full((B,), start + S, jnp.int32), pos,
                        block_size=BS))
        outs, states = [], []
        for view in views:
            assert view.positions.shape == (B, S)
            view = view.at_layer(view.pools, layer)
            state = view.write(k, v)
            outs.append(view.attend(q, state, scale=D ** -0.5,
                                    local_window_size=window))
            states.append(state)
        return outs, states

    start = 0
    for S in (P,) + (1,) * steps:
        (a, b), (dense, paged) = step(
            dense, paged, draw(B, S, Hq, D), draw(B, S, Hk, D),
            draw(B, S, Hk, D), jnp.int32(start))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-6)
        start += S
    # both wrote layer 1 and left layer 0 as it was
    assert not np.asarray(dense["k"][0]).any()
    assert not np.asarray(paged["k"][0]).any()
    assert np.asarray(dense["k"][1]).all()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("scan_block", [1, 2])
def test_scan_layers_is_the_plain_loop(scan_block, remat):
    """Two sub-stacks (2 and 4 layers, their own layer functions and one
    with per-layer ``xs``): hidden state, the stacked ``ys`` and the
    gradients of ``scan_layers`` equal the Python loop over the layers,
    with ONE layer index running across the sub-stacks."""
    H = 8
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    params = {"a": {"w": draw(2, H, H)},
              "b": {"w": draw(4, H, H), "u": draw(4, H)}}
    gains = draw(4)
    x = draw(3, H)

    def layer_a(h, p, xs, idx, cache):
        assert xs is None and cache is None
        h = jnp.tanh(h @ p["w"]) + 0.1 * idx
        return h, None, jnp.sum(h)

    def layer_b(h, p, gain, idx, cache):
        h = h + gain * jnp.sin(h @ p["w"]) * p["u"] + 0.01 * idx
        return h, None, jnp.mean(h)

    def scanned(params, x):
        h, state, ys = scan_layers(
            x, [SubStack(params["a"], layer_a),
                SubStack(params["b"], layer_b, gains)],
            remat=remat, remat_policy="nothing_saveable",
            scan_block=scan_block)
        assert state is None
        return h, ys

    def looped(params, x):
        h, ys, idx = x, ([], []), 0
        for i in range(2):
            h, _, y = layer_a(h, jax.tree.map(lambda a: a[i], params["a"]),
                              None, idx, None)
            ys[0].append(y)
            idx += 1
        for i in range(4):
            h, _, y = layer_b(h, jax.tree.map(lambda a: a[i], params["b"]),
                              gains[i], idx, None)
            ys[1].append(y)
            idx += 1
        return h, [jnp.stack(y) for y in ys]

    loss = lambda f: lambda p, x: jnp.sum(f(p, x)[0] ** 2) + sum(
        jnp.sum(y) for y in f(p, x)[1])
    (h1, ys1), (h2, ys2) = jax.jit(scanned)(params, x), looped(params, x)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-5, atol=1e-6)
    assert [y.shape for y in ys1] == [(2,), (4,)]
    for y1, y2 in zip(ys1, ys2):
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-6)
    g1 = jax.jit(jax.grad(loss(scanned), argnums=(0, 1)))(params, x)
    g2 = jax.grad(loss(looped), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_scan_layers_refuses_a_block_that_does_not_divide():
    params = {"w": jnp.zeros((3, 2, 2))}
    layer = lambda h, p, xs, idx, cache: (h @ p["w"], None, None)
    with pytest.raises(ValueError, match="scan_block=2 must divide"):
        scan_layers(jnp.zeros((1, 2)), [SubStack(params, layer)],
                    remat=False, remat_policy=None, scan_block=2)
