"""Power retention (``ops/power_retention.py`` and its Pallas rungs): the
embedding's inner product, the three writings of the function against each
other and against the benchmark's plain reference (the ATTENTION form,
``benchmark/reference/brumby.py``, which imports nothing of the program),
the rungs' operand contract over the stacked planes, and both kernels in
interpret mode over the shared parity matrix.  Small sizes, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops import power_retention as pr
from automodel_tpu.ops import power_retention_kernel as pk
from automodel_tpu.ops.kernel_lib import parity, registry
from benchmark.reference import brumby as ref

B, T, HQ, HK, D = 2, 37, 4, 2, 16


@pytest.fixture(scope="module")
def row():
    """q, k, v, log_g of two rows of 37 tokens and the reference's output."""
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, T, HQ, D))
    k = jax.random.normal(ks[1], (B, T, HK, D))
    v = jax.random.normal(ks[2], (B, T, HK, D))
    log_g = jax.nn.log_sigmoid(3.0 + 1.4 * jax.random.normal(
        ks[3], (B, T, HK)))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.retention(q[b], k[b], v[b], log_g[b], 2)
                          for b in range(B)])
    return q, k, v, log_g, np.asarray(want)


@pytest.mark.parametrize("d", [2, 16, 128])
def test_the_embedding_has_the_squared_inner_product(d):
    a, b = jax.random.normal(jax.random.key(d), (2, 5, d))
    got = jnp.sum(pr.phi_q(a) * pr.phi_k(b), axis=(-1, -2))
    np.testing.assert_allclose(got, jnp.sum(a * b, -1) ** 2, rtol=2e-5)
    assert pr.phi_k(a).shape == (5, d // 2 + 1, d)


def test_an_odd_head_size_is_refused():
    with pytest.raises(ValueError, match="even head size"):
        pr.num_offsets(15)


@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_chunked_form_is_the_attention_form(row, chunk):
    """Chunks of 1, 3 (a ragged tail: 37 = 12 x 3 + 1) and 64 (one chunk
    that is mostly padding... of nothing: the row is shorter)."""
    q, k, v, log_g, want = row
    with jax.default_matmul_precision("highest"):
        got = pr.retention_forward(q, k, v, log_g, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_recurrent_form_is_the_attention_form(row):
    q, k, v, log_g, want = row
    S, z = pr.init_state(B, HK, D, D)
    outs = []
    with jax.default_matmul_precision("highest"):
        for t in range(T):
            o, S, z = pr.recurrent_step(q[:, t], k[:, t], v[:, t],
                                        log_g[:, t], S, z)
            outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=2e-5,
                               rtol=2e-5)


def test_a_packed_rows_documents_do_not_share_state(row):
    q, k, v, log_g, want = row
    seg = jnp.asarray(np.array([[1] * 10 + [2] * 20 + [3] * 5 + [0] * 2,
                                [1] * T]))
    with jax.default_matmul_precision("highest"):
        got = pr.retention_forward(q, k, v, log_g, segment_ids=seg, chunk=8)
        docs = jnp.concatenate([
            ref.retention(q[0, a:b], k[0, a:b], v[0, a:b], log_g[0, a:b], 2)
            for a, b in ((0, 10), (10, 30), (30, 35))])
    np.testing.assert_allclose(got[0, :35], docs, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5, rtol=2e-5)


def test_padding_columns_leave_the_state_alone(row):
    """A ragged step: the chunk's valid prefix alone decides the state."""
    q, k, v, log_g, _ = row
    S, z = pr.init_state(B, HK, D, D)
    valid = jnp.arange(8)[None, :] < jnp.asarray([5, 8])[:, None]
    none = jnp.zeros((B, 8), bool)
    _, S1, z1 = pr.chunk_step(q[:, :8], k[:, :8], v[:, :8], log_g[:, :8],
                              S, z, valid, none)
    _, S2, z2 = pr.chunk_step(q[:1, :5], k[:1, :5], v[:1, :5], log_g[:1, :5],
                              S[:1], z[:1], jnp.ones((1, 5), bool),
                              none[:1, :5])
    np.testing.assert_allclose(S1[0], S2[0], atol=1e-6)
    np.testing.assert_allclose(z1[0], z2[0], atol=1e-6)


def test_the_forward_differentiates(row):
    q, k, v, log_g, _ = row
    g = jax.grad(lambda k_: jnp.sum(
        pr.retention_forward(q, k_, v, log_g, chunk=8) ** 2))(k)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0


# -- the rungs over the stacked planes -------------------------------------
def _planes(rows, layers=3):
    shapes = pr.state_shapes(HK, D, D)
    ks = jax.random.split(jax.random.key(5), 2)
    o = pr.num_offsets(D)
    norm = jnp.abs(jax.random.normal(ks[1], (layers, rows, *shapes["norm"])))
    return (jax.random.normal(ks[0], (layers, rows, *shapes["state"])),
            norm.at[..., o:, :].set(0.0))       # the pad rows stay zero


def test_resolution_and_the_xla_anchors_on_the_cpu(row):
    """Off the TPU, and at a head size the kernels are not written for, the
    chains end on their XLA anchors; a step through ``retention`` is the
    recurrent form for one token and the chunked form for more, and only
    the addressed layer of the planes changes."""
    q, k, v, log_g, want = row
    state, norm = (jnp.zeros_like(p) for p in _planes(B))
    outs, layer = [], 1
    spans = [(0, 8), (8, 16), (16, 17), (17, 18)] + [
        (t, t + 1) for t in range(18, T)]
    before = registry.resolved_rungs()
    with jax.default_matmul_precision("highest"):
        for a, b in spans:
            o, state, norm = pr.retention(
                q[:, a:b], k[:, a:b], v[:, a:b], log_g[:, a:b], state, norm,
                layer=layer, n_valid=jnp.full((B,), b - a),
                reset=jnp.full((B,), a == 0))
            outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5,
                               rtol=2e-5)
    assert not np.asarray(state[0]).any() and not np.asarray(state[2]).any()
    after = registry.resolved_rungs()
    for rung in ("attention.retention_decode_xla",
                 "attention.retention_chunk_xla"):
        assert after.get(rung, 0) > before.get(rung, 0)


def test_a_reset_row_forgets_and_an_idle_row_keeps(row):
    q, k, v, log_g, _ = row
    state, norm = _planes(3)
    qq, kk, vv, gg = (jnp.concatenate([x[:, :4], x[:1, :4]]) for x in
                      (q, k, v, log_g))
    n_valid = jnp.asarray([4, 0, 2])
    reset = jnp.asarray([True, False, False])
    for width in (1, 4):
        o, s, z = pr.retention(
            qq[:, :width], kk[:, :width], vv[:, :width], gg[:, :width],
            state, norm, layer=2, n_valid=jnp.minimum(n_valid, width),
            reset=reset)
        fresh = pr.retention(
            qq[:1, :width], kk[:1, :width], vv[:1, :width], gg[:1, :width],
            jnp.zeros_like(state[:, :1]), jnp.zeros_like(norm[:, :1]),
            layer=2, n_valid=jnp.asarray([width]), reset=jnp.asarray([False]))
        np.testing.assert_allclose(o[0], fresh[0][0], atol=1e-5)
        np.testing.assert_allclose(s[2, 0], fresh[1][2, 0], atol=1e-5)
        np.testing.assert_array_equal(s[2, 1], state[2, 1])     # idle
        np.testing.assert_array_equal(z[2, 1], norm[2, 1])
        assert np.isfinite(np.asarray(o)).all()


def test_probe_takes_the_kernels_widths_only():
    req = {"num_q_heads": 40, "num_kv_heads": 8, "head_dim": 128,
           "value_dim": 128, "state_dtype": "float32", "q_seq": 1}
    with parity.interpret_mode():
        assert pk.retention_available(req)
        assert pk.retention_available(dict(req, q_seq=64))
        assert not pk.retention_available(dict(req, q_seq=12))
        assert not pk.retention_available(dict(req, head_dim=64,
                                               value_dim=64))
        assert not pk.retention_available(dict(req, num_q_heads=80))
        assert not pk.retention_available(dict(req,
                                               state_dtype="bfloat16"))
    assert not pk.retention_available(req)       # the CPU is no TPU


@pytest.mark.parametrize("case", parity.retention_cases(),
                         ids=lambda c: c["name"])
@pytest.mark.parametrize("xla", [False, True], ids=["pallas", "xla"])
def test_retention_kernel_parity(case, xla):
    """Both Pallas kernels in interpret mode, and both XLA anchors, at head
    size 128 over the stacked planes against the chunked XLA form."""
    rung = ("attention.retention_decode" if case["q_seq"] == 1
            else "attention.retention_chunk") + ("_xla" if xla else "")
    assert parity.run_retention_parity(rung, case) < 2e-3


def test_state_sizes_as_the_mathematics_and_as_kept():
    """34.08 MB a row a layer as the mathematics needs it (8 kv heads x
    8,256 x 129 float32); kept: 65 offsets x 128 lanes = 8,320, 0.8 % more."""
    cfg = {"num_key_value_heads": 8, "num_attention_heads": 40,
           "head_dim": 128, "hidden_size": 5120}
    assert ref.embedding_dim(cfg) == 8256
    assert ref.state_bytes_per_row_layer(cfg) == 8 * 8256 * 129 * 4
    assert round(ref.state_bytes_per_row_layer(cfg) / 1e6, 2) == 34.08
    assert ref.retention_flops_per_position(cfg) == 48 * 2 * 8256 * 129
    kept = sum(int(np.prod(s)) * 4 for s in pr.state_shapes(
        8, 128, 128).values())
    assert kept == 8 * (65 * 128 + 72) * 128 * 4      # z: 65 rows in 72
    assert 1.0 < kept / ref.state_bytes_per_row_layer(cfg) < 1.01
