"""On-hardware numeric checks for the Pallas kernels and round-5 paths the
CPU suite can only interpret: the fused linear-CE kernel (real MXU fwd+bwd
vs an XLA reference) and sliding-window splash attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.ops.kernel_lib import parity


def test_linear_ce_kernel_matches_xla_reference():
    from automodel_tpu.ops.linear_ce_kernel import (
        linear_ce_kernel_available,
        lse_and_pick,
    )

    T, H, V = 1024, 256, 1000   # deliberately ragged vocab (pad path)
    assert linear_ce_kernel_available(T, H, V)
    key = jax.random.key(0)
    kh, kw = jax.random.split(key)
    h = jax.random.normal(kh, (T, H), jnp.bfloat16)
    w = jax.random.normal(kw, (H, V), jnp.bfloat16) * 0.05
    labels = jax.random.randint(jax.random.key(2), (T,), 0, V)

    def loss_kernel(h, w):
        lse, pick = lse_and_pick(h, w, labels)
        return jnp.sum(lse - pick)

    def loss_ref(h, w):
        logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - pick)

    (lk, gk), (lr, gr) = [
        jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(h, w)
        for f in (loss_kernel, loss_ref)
    ]
    lk, lr = float(jax.device_get(lk)), float(jax.device_get(lr))
    assert abs(lk - lr) / abs(lr) < 2e-3, (lk, lr)
    for a, b in zip(jax.device_get(gk), jax.device_get(gr)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() / denom < 3e-2


def test_sliding_window_splash_matches_sdpa():
    from automodel_tpu.ops.attention import (
        attention,
        dot_product_attention,
    )

    B, S, Hq, Hk, D = 2, 512, 4, 2, 64
    key = jax.random.key(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, Hk, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, Hk, D), jnp.bfloat16)
    window = 128
    out = jax.device_get(jax.jit(
        lambda q, k, v: attention(q, k, v, causal=True,
                                  local_window_size=window))(q, k, v))
    ref = jax.device_get(jax.jit(
        lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, local_window_size=window))(q, k, v))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("width", [1, 32])
def test_paged_decode_at_the_serving_cells_shapes(width, record_property):
    """The Pallas rung over stacked pools as large as the benchmark's
    serving cells hold (16 layers x 2,816 blocks x 16 x 16 heads x 128,
    bf16: 2.95 GB each for K and V), 64 rows, at both step widths, against
    ``paged_reference``.  Every layer holds other values, so a page read
    from another layer shows."""
    from automodel_tpu.ops.kernel_lib import parity
    from automodel_tpu.ops.paged_attention import paged_reference
    from automodel_tpu.ops.paged_attention_kernel import paged_decode_pallas

    L, NB, BS, Hk, D, B, MB = 16, 2816, 16, 16, 128, 64, 128
    layer = 11
    rng = np.random.default_rng(27 + width)
    ctx = rng.integers(width, 704, B)
    ctx[:2] = MB * BS, MB * BS - 1            # two rows at the full length
    need = -(-ctx // BS)
    assert need.sum() < NB
    free = rng.permutation(np.arange(1, NB))
    tables = np.zeros((B, MB), np.int32)      # pad entries: the null page
    for b in range(B):
        tables[b, :need[b]], free = free[:need[b]], free[need[b]:]
    positions = ctx[:, None] - width + np.arange(width)[None, :]

    @jax.jit
    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        per_layer = 1.0 + 0.25 * jnp.arange(L, dtype=jnp.float32)
        pool = lambda k: (jax.random.normal(k, (1, NB, BS, Hk, D))
                          * per_layer[:, None, None, None, None]
                          ).astype(jnp.bfloat16)
        return (jax.random.normal(kq, (B, width, Hk, D), jnp.bfloat16),
                pool(kk), pool(kv))

    q, k_pool, v_pool = make(jax.random.key(width))
    args = (q, k_pool, v_pool, None, None, jnp.int32(layer),
            jnp.asarray(tables), jnp.asarray(ctx, jnp.int32),
            jnp.asarray(positions, jnp.int32))
    out = jax.jit(paged_decode_pallas)(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: paged_reference({}, *a))(*args)
    record_property("max_err", parity._compare(
        out, ref, parity.NATIVE_TOL["bfloat16"], True,
        f"paged_decode at the cell's shapes, width {width}"))


@pytest.mark.parametrize("case", parity.chip_cases()["moe_decode.pallas"],
                         ids=lambda c: c["name"])
def test_decode_experts_at_the_serving_cells_widths(case, record_property):
    """``decode_expert_ffn`` itself (the dispatch, not the harness) at the
    two expert cells' published widths: the Pallas rung resolves, its call
    is named ``moe_decode`` under the caller's ``moe_experts`` scope, and it
    reads the addressed layer's experts: against every expert on every
    token in float32, ``tokens_per_expert`` equal to the count."""
    import re

    from automodel_tpu.ops import moe
    from automodel_tpu.ops.kernel_lib import registry

    args, kwargs, request = parity.build_moe_decode_case(case)

    def experts(*a):
        with jax.named_scope("moe_experts"):
            return moe.decode_expert_ffn(*a[:-1], layer=a[-1], **kwargs)

    before = registry.resolved_rungs().get("moe_decode.pallas", 0)
    compiled = jax.jit(experts).lower(*args).compile()
    assert registry.resolved_rungs()["moe_decode.pallas"] == before + 1
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if re.search(r"%?moe_decode(\.\d+)? = .*tpu_custom_call", line)]
    assert len(calls) == 1 and "/moe_experts/moe_decode/" in calls[0], calls
    out, counts = compiled(*args)
    with jax.default_matmul_precision("highest"):
        ref, ref_counts = jax.jit(lambda *a: registry.get_kernel(
            "moe_decode.pallas").reference(request, *a, **kwargs))(*args)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))
    assert 0 < int(np.sum(np.asarray(counts) > 0)) <= case["E"]
    record_property("max_err", parity._compare(
        out, ref, parity.NATIVE_TOL["bfloat16"], True,
        f"decode_expert_ffn on {case['name']}"))


def test_kernel_scopes_and_instruction_names():
    """The scopes only a Pallas rung reaches, as the chip's compiler sees
    them: ``linear_ce`` inside the custom-vjp's forward and backward,
    ``paged_decode`` round the paged kernel — and, innermost, the legacy
    names that keep the instructions called what
    ``benchmark/rooflines/{linear_ce,paged_decode}.py`` look for (XLA:TPU
    names a Mosaic custom call after the innermost scope component).  The
    CPU twin, compiled for a described v5e, is
    ``tests/unit_tests/test_program_spans.py``."""
    import re

    from automodel_tpu.ops import linear_ce_kernel, paged_attention_kernel

    def kernels(text):
        return {re.search(r"%?(\S+) = ", line).group(1):
                re.search(r'metadata=\{op_name="([^"]*)"', line).group(1)
                for line in text.splitlines()
                if "custom-call(" in line and "tpu_custom_call" in line}

    def loss(h, w, labels):
        lse, pick = linear_ce_kernel.lse_and_pick(h, w, labels, "pallas")
        return jnp.sum(lse - pick)

    ce = kernels(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jnp.zeros((1024, 256), jnp.bfloat16),
        jnp.zeros((256, 4096), jnp.bfloat16),
        jnp.zeros((1024,), jnp.int32)).compile().as_text())
    assert sorted(re.sub(r"\.\d+$", "", n) for n in ce) == [
        "jvp__", "transpose_jvp___", "transpose_jvp___"], ce
    # no scope outside the call here, so the transform wraps this one:
    # ``jit(loss)/jvp(linear_ce)/jvp__/pallas_call``
    assert all(re.search(r"[/(]linear_ce[/)]", scope)
               for scope in ce.values()), ce

    B, Hq, D, BS, MB, NB = 8, 4, 128, 16, 8, 64
    pool = jnp.zeros((2, NB, BS, Hq, D), jnp.bfloat16)
    paged = kernels(jax.jit(
        lambda q, kp, vp, layer, tables, lens:
        paged_attention_kernel._paged_decode_impl(
            {}, q, kp, vp, None, None, layer, tables, lens, None)).lower(
        jnp.zeros((B, 1, Hq, D), jnp.bfloat16), pool, pool, jnp.int32(1),
        jnp.zeros((B, MB), jnp.int32),
        jnp.ones((B,), jnp.int32)).compile().as_text())
    (name, scope), = paged.items()
    assert re.match(r"^closed_call(\.\d+)?$", name), paged
    assert re.search(r"[/(]paged_decode[/)]", scope), paged
