"""Shared parity harness: every registered kernel vs its XLA reference.

ONE case matrix (shape / dtype / GQA / packed-segment variants) and ONE
runner per kernel family, executed two ways:

* **interpret** (the CPU suite, ``tests/``): ``JAX_PLATFORMS=cpu`` with the
  Pallas kernels in interpret mode (:func:`interpret_mode`), so the REAL
  kernel logic — tiling, masking, online softmax, scalar-prefetch
  schedules — runs on the CPU and is held to the registry's ``reference``
  oracle (``kernel_lib/registry``) at small shapes;
* **native** (the chip suite, ``tpu_tests/``): ``native=True`` runs the same
  builders and runners with every ``_INTERPRET`` flag off — Mosaic compiles
  the kernel — at the published-width shapes of :func:`chip_cases`, against
  the same reference evaluated at highest matmul precision.

The harness bypasses probes deliberately: a probe answers "should dispatch
pick you HERE" (backend, alignment), while parity asks "is your math right
anywhere".  Tests declare which rungs execute off-TPU (``CPU_EXECUTABLE``);
the flash rung's upstream kernel exposes no interpret path.

Every runner returns the measured error (max |out - ref| / max |ref|) so
the chip suite can record it per rung.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.ops.kernel_lib import registry

# Rungs whose impl executes under JAX_PLATFORMS=cpu (+ interpret mode).
CPU_EXECUTABLE = {
    "attention.splash", "attention.ring", "attention.sdpa",
    "attention.paged_decode", "attention.paged_gather",
    "attention.mla_paged_decode", "attention.mla_paged_gather",
    "attention.retention_decode", "attention.retention_decode_xla",
    "attention.retention_chunk", "attention.retention_chunk_xla",
    "linear_ce.pallas", "linear_ce.chunked",
    "gmm.pallas", "gmm.xla_blocked", "gmm.ragged",
    "moe_decode.pallas", "moe_decode.loop",
    "qdot.pallas", "qdot.xla",
    "gmm_quant.pallas", "gmm_quant.xla_blocked", "gmm_quant.dense",
}

_INTERPRET_MODULES = (
    "automodel_tpu.ops.splash_attention",
    "automodel_tpu.ops.linear_ce_kernel",
    "automodel_tpu.ops.gmm_kernel",
    "automodel_tpu.ops.moe_decode_kernel",
    "automodel_tpu.ops.qdot_kernel",
    "automodel_tpu.ops.paged_attention_kernel",
    "automodel_tpu.ops.mla_paged_attention_kernel",
    "automodel_tpu.ops.power_retention_kernel",
)


@contextlib.contextmanager
def interpret_mode():
    """Flip every Pallas kernel module's ``_INTERPRET`` flag on (restored
    on exit): the CPU suite executes real kernel logic through the Pallas
    interpreter."""
    mods = [importlib.import_module(name) for name in _INTERPRET_MODULES]
    saved = [(m, m._INTERPRET) for m in mods]
    for m in mods:
        m._INTERPRET = True
    try:
        yield
    finally:
        for m, v in saved:
            m._INTERPRET = v


def interpret_flags_on() -> List[str]:
    """Kernel modules whose ``_INTERPRET`` flag is set right now — must be
    empty wherever a result is attributed to the chip."""
    return [name for name in _INTERPRET_MODULES
            if importlib.import_module(name)._INTERPRET]


# native tolerances (normalized max error, see _compare): bf16 operands
# with f32 accumulation against an f32 oracle differ by output rounding
# (2^-8) plus accumulation order; the exact-accumulate int8 rungs differ
# from their XLA spelling only where a quantization tie rounds the other
# way (one quantum in 127 on a few elements)
NATIVE_TOL = {"bfloat16": 2e-2, "float32": 2e-2, "int8": 2e-3,
              "float8": 5e-2}


def _tol(dtype: str, native: bool, interpret_tol: float) -> float:
    """The case's tolerance: the interpret run's own, or the native one of
    its operand dtype (any int8 / float8 flavour shares one entry)."""
    if not native:
        return interpret_tol
    for family in ("int8", "float8"):
        if family in dtype:
            return NATIVE_TOL[family]
    return NATIVE_TOL[dtype]


def _execute(spec, request, args, kwargs, native: bool, ref_args=None):
    """(out, ref) of one rung on one built case.  ``ref_args``: operands
    for the reference where they differ from the rung's (the paged family
    hands it the one layer the rung must address)."""
    assert spec.reference is not None, f"{spec.name} has no XLA reference"
    ref_args = args if ref_args is None else ref_args
    if not native:
        with interpret_mode():
            out = spec.impl(request, *args, **kwargs)
        return out, spec.reference(request, *ref_args, **kwargs)
    on = interpret_flags_on()
    assert not on, f"native parity with _INTERPRET on in {on}"
    out = jax.jit(lambda *a: spec.impl(request, *a, **kwargs))(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda *a: spec.reference(request, *a, **kwargs))(*ref_args)
    return out, ref


def _compare(out, ref, tol: float, native: bool, what: str) -> float:
    """Assert closeness and return the normalized max error.  Interpret
    runs keep the elementwise ``atol = rtol = tol`` they always had; native
    runs scale ``atol`` by the reference's magnitude (published-width
    outputs are not O(1))."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = float(np.max(np.abs(ref)))
    assert scale > 0.0, f"{what}: all-zero reference, nothing compared"
    np.testing.assert_allclose(
        out, ref, atol=tol * scale if native else tol, rtol=tol,
        err_msg=f"{what} diverged from its XLA reference")
    return float(np.max(np.abs(out - ref))) / scale


# ---------------------------------------------------------------------------
# Shared XLA oracles (single home — kernel modules register these so the
# per-family reference cannot drift between rungs)
# ---------------------------------------------------------------------------
def sdpa_reference(request, q, k, v, **kwargs):
    """The attention family's oracle: plain XLA SDPA on the same (global)
    arrays — splash/flash/ring all answer to it."""
    from automodel_tpu.ops.attention import dot_product_attention

    return dot_product_attention(q, k, v, **kwargs)


def dense_lse_pick_reference(request, h, w, labels):
    """The linear_ce family's oracle: dense-XLA (lse, picked) with the
    chain's out-of-range-label contract (ignore rows / other shards' vocab
    pick 0).  jnp-only: the chunked anchor rung registers it without
    importing the Pallas kernel module."""
    logits = jnp.dot(h, w.astype(h.dtype), preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    v_dim = w.shape[1]
    safe = jnp.clip(labels, 0, v_dim - 1)
    pick = jnp.where(
        (labels >= 0) & (labels < v_dim),
        jnp.take_along_axis(logits, safe[:, None], -1)[:, 0], 0.0)
    return lse, pick


# ---------------------------------------------------------------------------
# Attention family
# ---------------------------------------------------------------------------
def attention_cases() -> List[Dict]:
    """The shape/dtype/GQA/packed-segment matrix every attention rung is
    held to (one list — not five per-file copies).  Shape keys (``B S Hq
    Hk D``) default to the small interpret-mode shape; the chip cases set
    them to published widths."""
    return [
        dict(name="causal_gqa", causal=True, dtype="float32"),
        dict(name="causal_bf16", causal=True, dtype="bfloat16"),
        dict(name="packed_segments", causal=True, dtype="float32",
             segments=True),
        dict(name="padding_mask", causal=True, dtype="float32",
             padding=32),
        dict(name="soft_cap", causal=True, dtype="float32", soft_cap=30.0),
        dict(name="full_mask", causal=False, dtype="float32"),
        dict(name="sliding_window", causal=True, dtype="float32",
             window=64),
    ]


def build_attention_case(case: Dict, *, B=1, S=256, Hq=4, Hk=2, D=128):
    B, S = case.get("B", B), case.get("S", S)
    Hq, Hk, D = case.get("Hq", Hq), case.get("Hk", Hk), case.get("D", D)
    dtype = jnp.dtype(case.get("dtype", "float32"))
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, S, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, S, Hk, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, S, Hk, D), jnp.float32).astype(dtype)
    kwargs: Dict = dict(causal=case.get("causal", True))
    if case.get("segments"):
        seg = np.ones((B, S), np.int32)
        seg[:, S // 2:] = 2
        kwargs["segment_ids"] = jnp.asarray(seg)
    if case.get("padding"):
        pad = np.ones((B, S), np.int32)
        pad[:, -case["padding"]:] = 0
        kwargs["attention_mask"] = jnp.asarray(pad)
    if case.get("soft_cap"):
        kwargs["logits_soft_cap"] = float(case["soft_cap"])
    if case.get("window"):
        kwargs["local_window_size"] = int(case["window"])
    request = {
        "kind": "attention", "q_seq": S, "kv_seq": S, "head_dim": D,
        "num_q_heads": Hq, "num_kv_heads": Hk, "dtype": str(dtype),
        "causal": kwargs["causal"],
        "soft_cap": "logits_soft_cap" in kwargs,
        "window": "local_window_size" in kwargs,
        "traced_window": False, "cp_active": False, "mesh": None,
        "cp_layout": None,
    }
    return q, k, v, kwargs, request


def run_attention_parity(spec_name: str, case: Dict,
                         mesh=None, B: int = 1,
                         native: bool = False) -> float:
    """Execute one rung on one case and assert parity against its
    registered XLA reference.  ``mesh`` routes the sharded rungs (ring)
    through their shard_map wrapper on the test mesh."""
    spec = registry.get_kernel(spec_name)
    q, k, v, kwargs, request = build_attention_case(case, B=B)
    if mesh is not None:
        request.update(mesh=mesh, cp_active=True, cp_layout="contiguous")
    out, ref = _execute(spec, request, (q, k, v), kwargs, native)
    tol = _tol(str(q.dtype), native,
               2e-2 if q.dtype == jnp.bfloat16 else 2e-3)
    valid_rows = slice(None, -case["padding"]) if case.get("padding") \
        else slice(None)
    return _compare(np.asarray(out, np.float32)[:, valid_rows],
                    np.asarray(ref, np.float32)[:, valid_rows], tol, native,
                    f"{spec_name} on {case['name']}")


# ---------------------------------------------------------------------------
# paged attention family (the serving decode path)
# ---------------------------------------------------------------------------
def paged_attention_cases() -> List[Dict]:
    """Decode (q=1), speculative-verify (q=spec_k+1) and chunked-prefill
    (q>1) traffic over scrambled block tables with ragged per-row context
    lengths; the int8 cases exercise the quantized-KV dequant inside each
    rung.  Every case stacks ``L`` layers of pool with contents of their
    own and attends ``layer`` (default: the middle of three), so a rung
    that addresses another layer fails; two cases stand at the first and
    the last.  Shape keys: ``B Hq Hk D BS MB L layer``; ``rows`` stores
    the pools as rows ``[L, NB, BS, Hk * D]``, ``idle`` makes the last row
    an idle one as the engine assembles it (context 1, positions 0, every
    entry the null page)."""
    return [
        dict(name="decode_gqa", q_seq=1, dtype="float32"),
        dict(name="decode_bf16", q_seq=1, dtype="bfloat16"),
        dict(name="decode_int8_kv", q_seq=1, dtype="float32",
             quantized=True),
        dict(name="decode_window", q_seq=1, dtype="float32", window=24),
        dict(name="decode_soft_cap", q_seq=1, dtype="float32",
             soft_cap=30.0),
        dict(name="spec_verify_w3", q_seq=3, dtype="float32"),
        dict(name="spec_verify_w5_int8_kv", q_seq=5, dtype="float32",
             quantized=True),
        dict(name="spec_verify_window", q_seq=3, dtype="float32",
             window=24),
        dict(name="chunked_prefill", q_seq=8, dtype="float32"),
        dict(name="chunked_prefill_int8_kv", q_seq=8, dtype="float32",
             quantized=True),
        dict(name="decode_first_layer", q_seq=1, dtype="float32", layer=0),
        dict(name="chunked_prefill_last_layer_int8_kv", q_seq=8,
             dtype="float32", quantized=True, layer=2),
        # seven query heads a kv head (S * G = 7 rows at decode) and a
        # window layer's walk: it starts at the first block the window
        # touches, and ``released`` puts the null page in every entry
        # before it, as the engine's window group leaves them
        dict(name="decode_g7", q_seq=1, dtype="float32", Hq=7, Hk=1),
        dict(name="decode_g7_window_released", q_seq=1, dtype="float32",
             Hq=7, Hk=1, MB=8, window=24, released=True),
        dict(name="chunked_prefill_g7_window_released", q_seq=8,
             dtype="float32", Hq=14, Hk=2, MB=8, window=24, released=True),
        dict(name="decode_bf16_g7_window_released", q_seq=1,
             dtype="bfloat16", Hq=7, Hk=1, MB=8, window=40, released=True),
        # pools stored as rows [L, NB, BS, Hk * D] (fewer kv heads than the
        # dtype's sublane packing): SmallThinker's geometry (Hk = 4, G = 7,
        # blocks of 128, contexts on both sides of a 4,096 window, an idle
        # row), decode and the width-64 step, full and windowed; then
        # Hk = 8, G = 4 in blocks of 16, and a float32 pool
        *(dict(name=f"rows_g7_{kind}{'_window' if window else '_full'}",
               q_seq=q_seq, dtype="bfloat16", rows=True, idle=True, B=4,
               Hq=28, Hk=4, BS=128, MB=48, L=2, window=window,
               released=bool(window))
          for kind, q_seq in (("decode", 1), ("chunk64", 64))
          for window in (None, 4096)),
        dict(name="rows_hk8_g4_decode", q_seq=1, dtype="bfloat16",
             rows=True, idle=True, B=3, Hq=32, Hk=8, BS=16, MB=6),
        dict(name="rows_hk8_g4_verify_window_released", q_seq=3,
             dtype="bfloat16", rows=True, B=3, Hq=32, Hk=8, BS=16, MB=6,
             window=24, released=True),
        dict(name="rows_f32_soft_cap", q_seq=1, dtype="float32", rows=True,
             soft_cap=30.0),
        # nine and six query heads a kv head, decode and a chunk of 16
        # (S * G = 144 and 96 rows): Laguna's window and full layers over
        # one pool geometry, the window walk over released entries
        *(dict(name=f"rows_g{g}_{kind}{'_window' if window else '_full'}",
               q_seq=q_seq, dtype="bfloat16", rows=True, idle=True, B=3,
               Hq=2 * g, Hk=2, BS=16, MB=6, window=window,
               released=bool(window))
          for g, window in ((9, 24), (6, None))
          for kind, q_seq in (("decode", 1), ("chunk16", 16))),
    ]


def build_paged_attention_case(case: Dict, *, B=2, Hq=4, Hk=2, D=128,
                               BS=16, MB=4, L=3, layer=1):
    B, Hq, Hk = case.get("B", B), case.get("Hq", Hq), case.get("Hk", Hk)
    D, BS, MB = case.get("D", D), case.get("BS", BS), case.get("MB", MB)
    L, layer = case.get("L", L), case.get("layer", layer)
    rng = np.random.default_rng(7)
    dtype = jnp.dtype(case.get("dtype", "float32"))
    S = case["q_seq"]
    quantized = bool(case.get("quantized"))
    NB = B * MB + 1
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32).astype(
        dtype)
    if quantized:
        k_pool = jnp.asarray(
            rng.integers(-127, 128, (L, NB, BS, Hk, D), np.int8))
        v_pool = jnp.asarray(
            rng.integers(-127, 128, (L, NB, BS, Hk, D), np.int8))
        k_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (L, NB, BS, Hk)), jnp.float32)
        v_scale = jnp.asarray(
            rng.uniform(0.005, 0.02, (L, NB, BS, Hk)), jnp.float32)
    else:
        k_pool = jnp.asarray(
            rng.standard_normal((L, NB, BS, Hk, D), np.float32)).astype(dtype)
        v_pool = jnp.asarray(
            rng.standard_normal((L, NB, BS, Hk, D), np.float32)).astype(dtype)
        k_scale = v_scale = None
    # scrambled, per-row-disjoint block tables (block 0 = null page)
    perm = rng.permutation(np.arange(1, NB)).reshape(B, MB)
    # ragged contexts: even rows nearly full, odd rows short
    ctx = np.asarray([MB * BS - 7 - 3 * (b // 2) if b % 2 == 0
                      else 2 * BS + 3 + b // 2 for b in range(B)], np.int32)
    ctx = np.maximum(ctx, S)
    if case.get("idle"):
        ctx[-1], perm[-1] = 1, 0
    if case.get("released"):
        from automodel_tpu.ops.paged_attention import window_first_block

        for b in range(B):
            perm[b, :window_first_block(int(ctx[b]) - S, int(case["window"]),
                                        BS)] = 0
    block_tables = jnp.asarray(perm, jnp.int32)
    positions = np.maximum(ctx[:, None] - S + np.arange(S)[None, :], 0)
    positions = jnp.asarray(positions, jnp.int32)
    if case.get("rows"):
        k_pool, v_pool = (p.reshape(L, NB, BS, Hk * D)
                          for p in (k_pool, v_pool))
    kwargs: Dict = {}
    if case.get("soft_cap"):
        kwargs["logits_soft_cap"] = float(case["soft_cap"])
    if case.get("window"):
        kwargs["local_window_size"] = int(case["window"])
    from automodel_tpu.ops.paged_attention import build_paged_request

    request = build_paged_request(
        q, k_pool, quantized=quantized,
        soft_cap="logits_soft_cap" in kwargs,
        window="local_window_size" in kwargs)
    return (q, k_pool, v_pool, k_scale, v_scale, jnp.int32(layer),
            block_tables, jnp.asarray(ctx), positions), kwargs, request


def run_paged_attention_parity(spec_name: str, case: Dict,
                               native: bool = False) -> float:
    """The rung over the stacked pools at the case's layer against the
    reference over THAT layer's pools alone (``L = 1, layer = 0``): both
    speak the one contract, and neither can agree with the other by
    reading the same wrong layer."""
    spec = registry.get_kernel(spec_name)
    args, kwargs, request = build_paged_attention_case(case)
    q, *pools, layer, tables, ctx, positions = args
    own = slice(int(layer), int(layer) + 1)
    ref_args = (q, *(None if p is None else p[own] for p in pools),
                jnp.int32(0), tables, ctx, positions)
    out, ref = _execute(spec, request, args, kwargs, native, ref_args)
    tol = _tol(str(args[0].dtype), native,
               2e-2 if args[0].dtype == jnp.bfloat16 else 2e-3)
    return _compare(out, ref, tol, native,
                    f"{spec_name} on {case['name']}")


# ---------------------------------------------------------------------------
# MLA paged attention family (the latent serving cache)
# ---------------------------------------------------------------------------
def mla_paged_attention_cases() -> List[Dict]:
    """Decode (q=1) and chunked prefill (q>1) over ONE stacked latent plane
    ``[L, NB, BS, R]`` with scrambled block tables and ragged contexts;
    ``valid`` < ``q_seq`` makes the trailing columns padding (repeating the
    last valid position, as the engine assembles a decode row of a mixed
    step); ``q_rows`` shrinks the kernel's query tile so that a small case
    has several (one of them all padding).  Shape keys: ``B Hq R V BS MB L
    layer``; ``ctx``: explicit context lengths."""
    return [
        dict(name="decode", q_seq=1, dtype="float32"),
        dict(name="decode_bf16", q_seq=1, dtype="bfloat16"),
        dict(name="decode_first_layer", q_seq=1, dtype="float32", layer=0),
        dict(name="chunked_prefill", q_seq=8, dtype="float32"),
        dict(name="chunked_prefill_tiles", q_seq=8, dtype="float32",
             q_rows=16, layer=2),
        dict(name="mixed_step_padding", q_seq=8, dtype="float32",
             q_rows=16, valid=(1, 5)),
        dict(name="decode_two_chunks", q_seq=1, dtype="float32", MB=12,
             chunk=64),
        dict(name="chunked_prefill_bf16_two_chunks", q_seq=8,
             dtype="bfloat16", MB=12, chunk=64, q_rows=16),
    ]


def build_mla_paged_attention_case(case: Dict, *, B=2, Hq=4, R=256, V=128,
                                   BS=16, MB=4, L=3, layer=1):
    B, Hq, R, V = (case.get(k, d) for k, d in
                   (("B", B), ("Hq", Hq), ("R", R), ("V", V)))
    BS, MB = case.get("BS", BS), case.get("MB", MB)
    L, layer = case.get("L", L), case.get("layer", layer)
    rng = np.random.default_rng(11)
    dtype = jnp.dtype(case.get("dtype", "float32"))
    S = case["q_seq"]
    NB = B * MB + 1
    valid = np.asarray(case.get("valid") or [S] * B, np.int32)
    if "ctx_range" in case:
        # the chip cases: contexts spread geometrically over the range, each
        # row owning only the blocks its context needs (a pool that held
        # ``B * MB`` blocks of 16k-token rows would not fit the chip)
        ctx = np.maximum(np.geomspace(*case["ctx_range"], B).astype(np.int32),
                         valid)
        need = -(-ctx // BS)
        NB = int(need.sum()) + 1
        ids = rng.permutation(np.arange(1, NB))
        perm = np.zeros((B, MB), np.int64)
        for b, (n, at) in enumerate(zip(need, np.cumsum(need) - need)):
            perm[b, :n] = ids[at:at + n]
    else:       # ragged: even rows nearly full, odd rows short
        ctx = np.asarray([MB * BS - 7 - 3 * (b // 2) if b % 2 == 0
                          else 2 * BS + 3 + b // 2 for b in range(B)],
                         np.int32)
        ctx = np.maximum(ctx, valid)
        perm = rng.permutation(np.arange(1, NB)).reshape(B, MB)
    positions = (ctx - valid)[:, None] + np.minimum(
        np.arange(S)[None, :], valid[:, None] - 1)
    key = jax.random.key(11)
    q = jax.random.normal(key, (B, S, Hq, R), jnp.float32).astype(dtype)
    pool = jax.random.normal(jax.random.fold_in(key, 1), (L, NB, BS, R),
                             dtype)
    from automodel_tpu.ops.mla_paged_attention import build_mla_request

    kwargs = {"value_dim": V, "scale": float(R) ** -0.5}
    return ((q, pool, jnp.int32(layer), jnp.asarray(perm, jnp.int32),
             jnp.asarray(ctx), jnp.asarray(positions, jnp.int32)), kwargs,
            build_mla_request(q, pool, V), valid)


def run_mla_paged_attention_parity(spec_name: str, case: Dict,
                                   native: bool = False) -> float:
    """The rung over the stacked plane at the case's layer against the
    reference over THAT layer alone; only the valid columns are compared
    (a pad column's output is the caller's to discard)."""
    from automodel_tpu.ops import mla_paged_attention_kernel as mk

    spec = registry.get_kernel(spec_name)
    args, kwargs, request, valid = build_mla_paged_attention_case(case)
    q, pool, layer, tables, ctx, positions = args
    ref_args = (q, pool[int(layer):int(layer) + 1], jnp.int32(0), tables,
                ctx, positions)
    saved = mk._Q_ROWS, mk._CHUNK
    mk._Q_ROWS = case.get("q_rows", saved[0])
    mk._CHUNK = case.get("chunk", saved[1])
    try:
        out, ref = _execute(spec, request, args, kwargs, native, ref_args)
    finally:
        mk._Q_ROWS, mk._CHUNK = saved
    keep = (np.arange(q.shape[1])[None, :] < valid[:, None])[..., None, None]
    tol = _tol(str(q.dtype), native,
               2e-2 if q.dtype == jnp.bfloat16 else 2e-3)
    return _compare(np.where(keep, np.asarray(out, np.float32), 0.0),
                    np.where(keep, np.asarray(ref, np.float32), 0.0),
                    tol, native, f"{spec_name} on {case['name']}")


# ---------------------------------------------------------------------------
# power retention family (the per-sequence state planes)
# ---------------------------------------------------------------------------
def retention_cases() -> List[Dict]:
    """Decode (``q_seq`` 1) and a chunk over the stacked planes ``state [L,
    B, Hk, O, dv, d]`` / ``norm [L, B, Hk, O, d]``: ``valid`` < ``q_seq``
    makes a row's trailing columns padding (0: an idle row, whose state
    must come back as it went in), ``reset`` rows start from zero whatever
    their row of the planes held.  Shape keys: ``B Hq Hk L layer``;
    ``gate``: the gate's logit is ``gate + normal`` (8: slow decay)."""
    return [
        dict(name="decode", q_seq=1),
        dict(name="decode_first_layer", q_seq=1, layer=0),
        dict(name="decode_idle_and_reset", q_seq=1, B=4, valid=(1, 0, 1, 1),
             reset=(False, False, True, False)),
        dict(name="decode_mha", q_seq=1, Hq=2, Hk=2),
        dict(name="decode_fast_decay", q_seq=1, gate=0.0),
        dict(name="chunk", q_seq=8),
        dict(name="chunk_ragged_idle_reset", q_seq=8, B=4,
             valid=(8, 0, 3, 1), reset=(False, False, True, False)),
        dict(name="chunk_fast_decay", q_seq=16, gate=-2.0, layer=2),
    ]


def build_retention_case(case: Dict, *, B=2, Hq=4, Hk=2, D=128, L=3,
                         layer=1):
    B, Hq, Hk = (case.get(k, d) for k, d in
                 (("B", B), ("Hq", Hq), ("Hk", Hk)))
    L, layer = case.get("L", L), case.get("layer", layer)
    from automodel_tpu.ops import power_retention as pr

    C = case["q_seq"]
    dtype = jnp.dtype(case.get("dtype", "float32"))
    keys = iter(jax.random.split(jax.random.key(13), 8))
    q, k, v = (jax.random.normal(next(keys), (B, C, h, D), jnp.float32)
               .astype(dtype) for h in (Hq, Hk, Hk))
    log_g = jax.nn.log_sigmoid(
        case.get("gate", 6.0) + jax.random.normal(next(keys), (B, C, Hk)))
    # planes as a sequence of `ctx` tokens would have left them: phi of
    # random keys against random values, so read-outs are well conditioned
    ctx = case.get("ctx", 24)
    pk = pr.phi_k(jax.random.normal(next(keys), (L, B, Hk, ctx, D)))
    pv = jax.random.normal(next(keys), (L, B, Hk, ctx, D))
    state = jnp.einsum("nbhcol,nbhcv->nbhovl", pk, pv)
    norm = jnp.sum(pk, axis=3)
    norm = jnp.pad(norm, ((0, 0),) * 3 + (
        (0, pr.norm_rows(D) - norm.shape[3]), (0, 0)))
    valid = np.asarray(case.get("valid") or [C] * B, np.int32)
    reset = np.asarray(case.get("reset") or [False] * B, bool)
    args = (q, k, v, log_g, state, norm, jnp.int32(layer),
            jnp.asarray(valid), jnp.asarray(reset))
    return args, pr.build_retention_request(q, k, v, state), valid


def run_retention_parity(spec_name: str, case: Dict,
                         native: bool = False) -> float:
    """The rung over the stacked planes at the case's layer against the
    chunked XLA form: the valid columns of the output, the layer's state
    and normaliser after the step, and every OTHER layer untouched."""
    spec = registry.get_kernel(spec_name)
    args, request, valid = build_retention_case(case)
    (o, s, z), (ro, rs, rz) = _execute(spec, request, args, {}, native)
    C, layer = args[0].shape[1], int(args[6])
    keep = (np.arange(C)[None, :] < valid[:, None])[..., None, None]
    tol = _tol(str(args[0].dtype), native, 2e-3)
    what = f"{spec_name} on {case['name']}"
    others = [i for i in range(s.shape[0]) if i != layer]
    np.testing.assert_array_equal(
        np.asarray(s)[others], np.asarray(args[4])[others],
        err_msg=f"{what}: another layer's state changed")
    np.testing.assert_array_equal(
        np.asarray(z)[others], np.asarray(args[5])[others],
        err_msg=f"{what}: another layer's normaliser changed")
    errs = [_compare(np.where(keep, np.asarray(o, np.float32), 0.0),
                     np.where(keep, np.asarray(ro, np.float32), 0.0),
                     tol, native, what + " (output)"),
            _compare(s[layer], rs[layer], tol, native, what + " (state)"),
            _compare(z[layer], rz[layer], tol, native,
                     what + " (normaliser)")]
    return max(errs)


# ---------------------------------------------------------------------------
# linear_ce family
# ---------------------------------------------------------------------------
def linear_ce_cases() -> List[Dict]:
    return [
        dict(name="aligned", t=256, h=128, v=256),
        dict(name="ragged_rows_vocab_tail", t=24, h=128, v=300),
        dict(name="out_of_range_labels", t=64, h=128, v=256,
             label_lo=-5, label_hi=400),
    ]


def run_linear_ce_parity(spec_name: str, case: Dict,
                         native: bool = False) -> float:
    spec = registry.get_kernel(spec_name)
    rng = np.random.default_rng(0)
    t, h, v = case["t"], case["h"], case["v"]
    dtype = jnp.dtype(case.get("dtype", "float32"))
    hid = jnp.asarray(rng.normal(size=(t, h)), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.05,
                    jnp.float32).astype(dtype)
    labels = jnp.asarray(
        rng.integers(case.get("label_lo", 0), case.get("label_hi", v), t),
        jnp.int32)
    request = {"kind": "linear_ce", "t": t, "h": h, "v": v,
               "bwd_mode": "pallas"}
    (lse, pick), (ref_lse, ref_pick) = _execute(
        spec, request, (hid, w, labels), {}, native)
    tol = _tol(str(dtype), native, 1e-5)
    return max(
        _compare(lse, ref_lse, tol, native,
                 f"{spec_name} lse on {case['name']}"),
        _compare(pick, ref_pick, tol, native,
                 f"{spec_name} pick on {case['name']}"))


# ---------------------------------------------------------------------------
# gmm family
# ---------------------------------------------------------------------------
def gmm_cases() -> List[Dict]:
    return [
        dict(name="even_groups", m=256, k=128, n=128,
             sizes=(64, 64, 64, 64)),
        dict(name="ragged_with_dropped_tail", m=256, k=128, n=128,
             sizes=(96, 0, 100, 32)),       # 28 tail rows -> zeros
        dict(name="block_aligned", m=512, k=128, n=128,
             sizes=(128, 256, 0, 128), block_aligned=True),
    ]


def _gmm_operands(case: Dict, seed: int):
    rng = np.random.default_rng(seed)
    m, k, n = case["m"], case["k"], case["n"]
    dtype = jnp.dtype(case.get("dtype", "float32"))
    sizes = jnp.asarray(case["sizes"], jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(m, k)) * case.get("lhs_scale", 0.1),
                      jnp.float32).astype(dtype)
    rhs = jnp.asarray(rng.normal(size=(len(case["sizes"]), k, n)) * 0.1,
                      jnp.float32).astype(dtype)
    return lhs, rhs, sizes


def run_gmm_parity(spec_name: str, case: Dict, native: bool = False,
                   grads: bool = False):
    """Forward parity; with ``grads`` also the custom VJP — ``dlhs`` is a
    second gmm and ``drhs`` the transposed kernel (``tgmm``), which has no
    registry rung of its own — returning ``(fwd, dlhs, drhs)`` errors."""
    spec = registry.get_kernel(spec_name)
    lhs, rhs, sizes = _gmm_operands(case, seed=1)
    request = {"kind": "gmm", "m": case["m"], "k": case["k"],
               "n": case["n"],
               "block_aligned": bool(case.get("block_aligned")),
               "block_rows": 128, "dtype": str(lhs.dtype)}
    if spec_name == "gmm.xla_blocked" and not request["block_aligned"]:
        return None     # that rung's contract requires block-aligned groups
    if spec.reference is None:
        spec = dataclasses.replace(
            spec, reference=registry.get_kernel("gmm.pallas").reference)
    tol = _tol(str(lhs.dtype), native, 2e-4)
    what = f"{spec_name} on {case['name']}"
    out, ref = _execute(spec, request, (lhs, rhs, sizes), {}, native)
    err = _compare(out, ref, tol, native, what)
    if not grads:
        return err

    cot = jnp.asarray(np.random.default_rng(4).normal(
        size=(case["m"], case["n"])), jnp.float32)

    def as_loss(fn):
        return lambda req, lhs, rhs, sizes: jax.grad(
            lambda a, b: jnp.sum(fn(req, a, b, sizes).astype(jnp.float32)
                                 * cot), argnums=(0, 1))(lhs, rhs)

    gspec = dataclasses.replace(spec, impl=as_loss(spec.impl),
                                reference=as_loss(spec.reference))
    (dl, dr), (rl, rr) = _execute(gspec, request, (lhs, rhs, sizes), {},
                                  native)
    return (err, _compare(dl, rl, tol, native, what + " dlhs"),
            _compare(dr, rr, tol, native, what + " drhs (tgmm)"))


# ---------------------------------------------------------------------------
# moe_decode family (the serving step's routed experts)
# ---------------------------------------------------------------------------
def moe_decode_cases() -> List[Dict]:
    """One layer of ``E`` experts out of a stack of ``L`` on ``T`` tokens
    that each choose ``k`` of ``total`` experts, the first ``E`` of them held
    (an assignment to another goes to the sentinel, as
    ``ops/moe.held_experts_local`` sends it).  ``nobody``: experts whose
    assignments are taken away; ``pad_rows``: rows that choose nothing;
    ``everyone``: every token chooses every expert; ``tiles``: ``(tH, tI)``
    forced on the Pallas rung, so that an expert spans several grid steps
    at a toy's widths."""
    base = dict(T=24, H=256, I=256, E=8, k=3, L=3, layer=1,
                tiles=(128, 128))
    return [
        dict(base, name="relu", activation="relu"),
        dict(base, name="silu_bf16", dtype="bfloat16"),
        dict(base, name="relu_bf16_whole_matrices", dtype="bfloat16",
             activation="relu", tiles=None),
        dict(base, name="experts_nobody_chose", nobody=(0, 5, 7)),
        dict(base, name="every_expert_chosen", E=4, k=4, everyone=True),
        dict(base, name="one_token", T=1, k=2),
        dict(base, name="padded_rows", pad_rows=(0, 7, 23)),
        dict(base, name="experts_held_elsewhere", total=24, k=6),
        dict(base, name="nothing_routed_here", total=24, k=2, E=2,
             nobody=(0, 1)),
        dict(base, name="rows_48", T=48, tiles=(256, 128)),
        dict(base, name="rows_64_last_layer", T=64, layer=2,
             tiles=(128, 256)),
        dict(base, name="rows_21_first_layer", T=21, layer=0),
    ]


def build_moe_decode_case(case: Dict, seed: int = 5):
    """``(args, kwargs, request)`` of a ``moe_decode`` rung: ``x, weights,
    idx, w_gate, w_up, w_down, layer`` with every layer of the stacks
    holding other values, so that an expert read from another layer
    shows."""
    T, H, I, E, k, L = (case[n] for n in "T H I E k L".split())
    total = case.get("total", E)
    dtype = jnp.dtype(case.get("dtype", "float32"))
    keys = iter(jax.random.split(jax.random.key(seed), 8))
    x = jax.random.normal(next(keys), (T, H), jnp.float32).astype(dtype)
    w_gate, w_up = (
        (jax.random.normal(next(keys), (L, E, H, I), jnp.float32)
         * H ** -0.5).astype(dtype) for _ in range(2))
    w_down = (jax.random.normal(next(keys), (L, E, I, H), jnp.float32)
              * I ** -0.5).astype(dtype)
    scores = jax.nn.softmax(
        jax.random.normal(next(keys), (T, total), jnp.float32))
    if case.get("everyone"):
        weights = scores[:, :k]
        idx = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (T, k))
    else:
        weights, idx = jax.lax.top_k(scores, k)
    gone = ((idx >= E) | jnp.isin(idx, jnp.asarray(case.get("nobody", ()),
                                                   jnp.int32))
            | jnp.isin(jnp.arange(T), jnp.asarray(case.get("pad_rows", ()),
                                                  jnp.int32))[:, None])
    idx = jnp.where(gone, E, idx).astype(jnp.int32)
    weights = jnp.where(gone, 0.0, weights)
    request = {"kind": "moe_decode", "rows": T, "hidden": H, "inter": I,
               "experts": E, "quantized": False, "devices": 1,
               "dtype": str(dtype)}
    kwargs = dict(compute_dtype=dtype,
                  activation=case.get("activation", "silu"))
    return ((x, weights, idx, w_gate, w_up, w_down,
             jnp.int32(case["layer"])), kwargs, request)


def run_moe_decode_parity(spec_name: str, case: Dict,
                          native: bool = False) -> float:
    """The rung under ``jit`` (``layer`` is traced, as a layer scan hands
    it) against the dense float32 oracle, and its ``tokens_per_expert``
    equal to the oracle's count."""
    from automodel_tpu.ops.kernel_lib import autotune

    spec = registry.get_kernel(spec_name)
    args, kwargs, request = build_moe_decode_case(case)
    tiles = case.get("tiles")
    with contextlib.ExitStack() as stack:
        if tiles:
            stack.enter_context(autotune.forced("moe_decode", tiles))
        if native:
            assert not interpret_flags_on()
        else:
            stack.enter_context(interpret_mode())
        out, counts = jax.jit(
            lambda *a: spec.impl(request, *a, **kwargs))(*args)
    with jax.default_matmul_precision("highest"):
        ref, ref_counts = jax.jit(
            lambda *a: spec.reference(request, *a, **kwargs))(*args)
    what = f"{spec_name} on {case['name']}"
    np.testing.assert_array_equal(
        np.asarray(counts), np.asarray(ref_counts),
        err_msg=f"{what}: tokens_per_expert")
    assert out.shape == ref.shape and out.dtype == ref.dtype, what
    if not np.any(np.asarray(ref_counts)):
        assert not np.any(np.asarray(out, np.float32)), what
        return 0.0
    tol = _tol(str(args[0].dtype), native,
               2e-2 if args[0].dtype == jnp.bfloat16 else 2e-5)
    return _compare(out, ref, tol, native, what)


# ---------------------------------------------------------------------------
# qdot family (quantized matmul)
# ---------------------------------------------------------------------------
def qdot_cases() -> List[Dict]:
    """Recipe x dtype matrix for the fused quantized matmul — every case
    pins the Pallas rung's in-VMEM quantize/dot/rescale against the XLA
    rung's three-step spelling of the SAME math (int8 is bit-exact: both
    accumulate int8 products in int32)."""
    return [
        dict(name="int8_tensorwise", m=128, k=128, n=256,
             a_dtype="int8", b_dtype="int8", rowwise=False),
        dict(name="int8_rowwise", m=200, k=128, n=256,
             a_dtype="int8", b_dtype="int8", rowwise=True),
        dict(name="fp8_tensorwise", m=128, k=128, n=128,
             a_dtype="float8_e4m3fn", b_dtype="float8_e4m3fn",
             rowwise=False),
        dict(name="fp8_rowwise_e5m2_grad", m=128, k=128, n=128,
             a_dtype="float8_e5m2", b_dtype="float8_e4m3fn", rowwise=True),
    ]


def run_qdot_parity(spec_name: str, case: Dict,
                    native: bool = False) -> float:
    from automodel_tpu.ops.quant import _operand_scales

    spec = registry.get_kernel(spec_name)
    rng = np.random.default_rng(2)
    m, k, n = case["m"], case["k"], case["n"]
    dtype = jnp.dtype(case.get("dtype", "float32"))
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32).astype(dtype)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.1,
                    jnp.float32).astype(dtype)
    sa, sb = _operand_scales(a, b, jnp.dtype(case["a_dtype"]),
                             jnp.dtype(case["b_dtype"]), case["rowwise"])
    request = {"kind": "qdot", "m": m, "k": k, "n": n,
               "a_dtype": case["a_dtype"], "b_dtype": case["b_dtype"],
               "rowwise": case["rowwise"]}
    out, ref = _execute(spec, request, (a, b, sa, sb), {}, native)
    return _compare(out, ref, _tol(case["a_dtype"], native, 1e-5),
                    native, f"{spec_name} on {case['name']}")


# ---------------------------------------------------------------------------
# gmm_quant family (quantized grouped matmul)
# ---------------------------------------------------------------------------
def gmm_quant_cases() -> List[Dict]:
    return [
        dict(name="int8_tensorwise_ragged", m=256, k=128, n=128,
             sizes=(96, 0, 100, 32), dtype="int8", recipe="tensorwise"),
        dict(name="int8_rowwise_block_aligned", m=512, k=128, n=128,
             sizes=(128, 256, 0, 128), dtype="int8", recipe="rowwise",
             block_aligned=True),
        dict(name="fp8_tensorwise_block_aligned", m=256, k=128, n=128,
             sizes=(128, 0, 128, 0), dtype="float8", recipe="tensorwise",
             block_aligned=True),
    ]


def run_gmm_quant_parity(spec_name: str, case: Dict,
                         native: bool = False) -> Optional[float]:
    from automodel_tpu.ops.gmm_quant_kernel import lhs_scales, rhs_scales
    from automodel_tpu.ops.quant import _gemm_dtypes, quant_cast

    spec = registry.get_kernel(spec_name)
    lhs, rhs, sizes = _gmm_operands(
        {**case, "dtype": "float32", "lhs_scale": 0.5}, seed=3)
    a_q, b_q = _gemm_dtypes(case["dtype"], None)
    lhs_q = quant_cast(lhs, lhs_scales(lhs, sizes, a_q, case["recipe"]), a_q)
    rhs_q = quant_cast(rhs, rhs_scales(rhs, b_q, case["recipe"]), b_q)
    request = {"kind": "gmm_quant", "m": case["m"], "k": case["k"],
               "n": case["n"],
               "a_dtype": str(jnp.dtype(a_q)), "b_dtype": str(jnp.dtype(b_q)),
               "block_aligned": bool(case.get("block_aligned")),
               "block_rows": 128}
    if spec_name == "gmm_quant.xla_blocked" and not request["block_aligned"]:
        return None     # that rung's contract requires block-aligned groups
    out, ref = _execute(spec, request, (lhs_q, rhs_q, sizes), {}, native)
    return _compare(out, ref, _tol(case["dtype"], native, 2e-4),
                    native, f"{spec_name} on {case['name']}")


# ---------------------------------------------------------------------------
# The chip matrix: the same case schema at published widths, one or two
# shapes per Pallas rung (``tpu_tests/`` runs these with ``native=True``)
# ---------------------------------------------------------------------------
def _ragged_sizes(m: int, groups: int) -> tuple:
    """Ragged but deterministic group sizes summing under ``m`` (a dropped
    tail and one empty group, like the small ragged case)."""
    base = m // groups
    sizes = [base + (17 if g % 2 else -17) for g in range(groups)]
    sizes[1] = 0
    return tuple(sizes)


def chip_cases() -> Dict[str, List[Dict]]:
    """rung family -> cases.  Widths: Llama-3.2-1B (the trainer the smoke
    runs; head_dim 64), Llama-3.2-3B (the server; head_dim 128, G=3),
    Llama-3.1-8B dense projections for the quantized matmul, Mixtral-8x7B
    and Moonlight-16B-A3B experts for the grouped matmuls — whole-K tiles
    at K=14336 are the VMEM-heaviest shape any of them sees."""
    l3b = dict(B=8, Hq=24, Hk=8, D=128, BS=16, MB=64)
    kimi = dict(Hq=64, R=640, V=512, BS=16, MB=1056, L=7, layer=5)
    # SmallThinker-21B-A3B's cache as the serving cell holds it: seven query
    # heads a kv head, blocks of 128, tables of 128 blocks (16,384
    # positions), contexts to 16k; a window layer's walk starts at the first
    # block the window (4,096) touches, the entries before it released; the
    # engine stores its pools as rows (four kv heads fill a quarter tile)
    small = dict(B=8, Hq=28, Hk=4, D=128, BS=128, MB=128, L=2, rows=True)
    brumby = dict(B=16, Hq=40, Hk=8, L=2, layer=1, ctx=64)
    # Laguna-S-2.1's cache as the serving cell holds it: eight kv heads
    # (rows), blocks of 128, tables of 256 blocks (32,768 positions); nine
    # query heads a kv head on window (512) layers, six on full layers, and
    # the width-128 step that carries prefill chunks
    laguna = dict(B=8, Hk=8, D=128, BS=128, MB=256, L=2, rows=True)
    mixtral_up = dict(m=4096, k=4096, n=14336, sizes=_ragged_sizes(4096, 8))
    mixtral_down = dict(m=4096, k=14336, n=4096, sizes=_ragged_sizes(4096, 8))
    moonlight_up = dict(m=4096, k=2048, n=1408, sizes=_ragged_sizes(4096, 64))
    return {
        "attention.splash": [
            dict(name="llama3_2_1b_packed_2k", B=2, S=2048, Hq=32, Hk=8,
                 D=64, dtype="bfloat16", causal=True, segments=True),
            dict(name="llama3_2_3b_2k", B=1, S=2048, Hq=24, Hk=8, D=128,
                 dtype="bfloat16", causal=True),
            # the 512-edge diagonal default (S >= 8192); two heads keep the
            # dense reference's [S, S] logits inside one chip
            dict(name="long_16k_diag512", B=1, S=16384, Hq=2, Hk=1, D=64,
                 dtype="bfloat16", causal=True),
        ],
        "attention.paged_decode": [
            dict(name="llama3_2_3b_decode", q_seq=1, dtype="bfloat16",
                 **l3b),
            dict(name="llama3_2_3b_decode_int8_kv", q_seq=1,
                 dtype="bfloat16", quantized=True, **l3b),
            dict(name="llama3_2_3b_verify_w5", q_seq=5, dtype="bfloat16",
                 **l3b),
            dict(name="llama3_2_3b_verify_w5_int8_kv", q_seq=5,
                 dtype="bfloat16", quantized=True, **l3b),
            dict(name="llama3_2_3b_prefill_chunk32", q_seq=32,
                 dtype="bfloat16", **l3b),
            dict(name="smallthinker_decode_full_g7", q_seq=1,
                 dtype="bfloat16", **small),
            dict(name="smallthinker_decode_window_released", q_seq=1,
                 dtype="bfloat16", window=4096, released=True, **small),
            dict(name="smallthinker_prefill_chunk64_window_released",
                 q_seq=64, dtype="bfloat16", window=4096, released=True,
                 **small),
            *(dict(name=f"laguna_{kind}_w{q_seq}", q_seq=q_seq,
                   dtype="bfloat16", Hq=hq, **extra, **laguna)
              for q_seq in (1, 128)
              for kind, hq, extra in (
                  ("window_g9_released", 72,
                   dict(window=512, released=True)),
                  ("full_g6", 48, {}))),
        ],
        # Kimi-K2's latent plane as the serving cell holds it: 7 layers,
        # 576 values a token stored 640 wide, 64 heads, value 512, tables
        # of 1,056 blocks (16,896 positions), contexts 200 to 16k
        "attention.mla_paged_decode": [
            dict(name="kimi_k2_decode_64rows", q_seq=1, dtype="bfloat16",
                 ctx_range=(200, 16384), B=64, **kimi),
            dict(name="kimi_k2_prefill_chunk64", q_seq=64, dtype="bfloat16",
                 ctx_range=(300, 16384), B=8, **kimi),
            dict(name="kimi_k2_mixed_step_w64", q_seq=64, dtype="bfloat16",
                 ctx_range=(300, 16384), B=8,
                 valid=(1, 64, 1, 1, 37, 1, 64, 1), **kimi),
        ],
        # Brumby-14B's state planes as the serving cell holds them (40
        # query heads in 8 groups of 5, head size 128, 16 rows), two layers
        # of the eight: a run keeps the planes three times over (in, out,
        # reference)
        "attention.retention_decode": [
            dict(name="brumby_14b_decode_16rows", q_seq=1, dtype="bfloat16",
                 valid=(1,) * 12 + (0, 1, 1, 1),
                 reset=(False,) * 5 + (True,) + (False,) * 10, **brumby),
        ],
        "attention.retention_chunk": [
            dict(name="brumby_14b_chunk64_mixed", q_seq=64, dtype="bfloat16",
                 valid=(64, 1, 37, 1, 64, 0, 1, 20, 1, 1, 64, 1, 1, 8, 1, 1),
                 reset=(True, False, True) + (False,) * 13, **brumby),
        ],
        "linear_ce.pallas": [
            dict(name="llama3_2_1b_vocab128256", t=4096, h=2048, v=128256,
                 dtype="bfloat16"),
        ],
        "gmm.pallas": [
            dict(name="mixtral_8x7b_up", dtype="bfloat16", **mixtral_up),
            dict(name="mixtral_8x7b_down", dtype="bfloat16", **mixtral_down),
            dict(name="moonlight_16b_up", dtype="bfloat16", **moonlight_up),
        ],
        # the routed experts of a decode step as the two serving cells hold
        # them: Kimi-K2's 12 held experts of 88 MB on 64 rows (8 of 384
        # chosen: most assignments go elsewhere), SmallThinker's 64 of
        # 11.8 MB on 48 rows (6 of 64); two layers of the stack, the second
        # addressed
        "moe_decode.pallas": [
            dict(name="kimi_k2_12_held_64rows", T=64, H=7168, I=2048, E=12,
                 k=8, total=384, L=2, layer=1, dtype="bfloat16"),
            dict(name="smallthinker_64_experts_48rows", T=48, H=2560, I=768,
                 E=64, k=6, L=2, layer=1, dtype="bfloat16",
                 activation="relu", pad_rows=(5, 40)),
        ],
        "qdot.pallas": [
            dict(name="llama3_1_8b_up_int8_tensorwise", m=4096, k=4096,
                 n=14336, dtype="bfloat16", a_dtype="int8", b_dtype="int8",
                 rowwise=False),
            dict(name="llama3_1_8b_down_int8_rowwise", m=4096, k=14336,
                 n=4096, dtype="bfloat16", a_dtype="int8", b_dtype="int8",
                 rowwise=True),
            dict(name="llama3_1_8b_up_fp8_tensorwise", m=4096, k=4096,
                 n=14336, dtype="bfloat16", a_dtype="float8_e4m3fn",
                 b_dtype="float8_e4m3fn", rowwise=False),
        ],
        "gmm_quant.pallas": [
            dict(name="mixtral_8x7b_up_int8_tensorwise", dtype="int8",
                 recipe="tensorwise", **mixtral_up),
            dict(name="mixtral_8x7b_down_int8_rowwise", dtype="int8",
                 recipe="rowwise", **mixtral_down),
            dict(name="moonlight_16b_up_fp8_tensorwise", dtype="float8",
                 recipe="tensorwise", **moonlight_up),
        ],
    }
