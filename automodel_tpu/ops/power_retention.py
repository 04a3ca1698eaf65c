"""Power retention — a gated linear attention whose feature map is the
symmetric power embedding of degree 2 (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239; Brumby-14B's layer).

Three writings of ONE function of ``q, k [.., d]``, ``v [.., dv]`` and a
gate ``log g <= 0`` per token and kv head:

* attention form (the benchmark's reference, nothing here): ``a_ts =
  exp(G_t - G_s) (q_t . k_s)^2`` for ``s <= t``, ``G_t = sum_{j<=t} log
  g_j``, ``o_t = sum_s a_ts v_s / sum_s a_ts``;
* recurrent form (:func:`recurrent_step`, a decode step): ``S_t = g_t
  S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} + phi(k_t)``, ``o_t =
  phi(q_t)^T S_t / phi(q_t)^T z_t`` with ``phi(a) . phi(b) = (a . b)^2``;
* chunked form (:func:`chunk_step`, a prefill chunk and the plain
  forward): the attention form on a chunk's own pairs plus ``phi(q_t)^T
  exp(G_t) S_prev``, then the state advances by the chunk.

**The embedding kept here** is circulant, not the packed triangle: for
offsets ``o = 0 .. d/2`` and lanes ``l < d``

    ``phi_k(a)[o, l] = a[l] a[(l + o) % d]``,  ``phi_q = w[o] phi_k``,
    ``w = 1`` at ``o = 0`` and ``o = d/2``, ``2`` between,

because ``sum_{o<d} sum_l a_l a_{l+o} b_l b_{l+o} = (a . b)^2`` and the
terms of ``o`` and ``d - o`` are equal.  A row of it is ``a * roll(a, -o)``:
one lane rotation on the TPU, no gather, and ``(d/2 + 1) d`` values (8,320
at ``d`` = 128) where the packed triangle has ``d (d + 1) / 2`` (8,256).

**The state** of a sequence, per layer and kv head: ``S [O, dv, d]`` and
``z [O, d]`` float32, ``O = d/2 + 1`` (value rows on sublanes, the key's
lanes on lanes: the update ``v (x) phi_k`` broadcasts a key row over
sublanes and ONE lane-broadcast of ``v`` serves every offset).  The engine
keeps them stacked ``state [L, rows, Hk, O, dv, d]`` / ``norm [L, rows, Hk,
O8, d]`` (``serving/kv_cache.StatePlaneView``), a row per step-buffer row;
``O8`` is ``O`` rounded up to the 8-sublane tile (:func:`norm_rows`: with 65
rows XLA:TPU laid the plane out kv-heads-minor and the step copied it whole
on its way into and out of the kernel; the pad rows stay zero).

Rungs on the ``kernel_lib`` registry, one operand contract
(:func:`retention`): ``q [B, C, Hq, d]``, ``k, v [B, C, Hk, d]``, ``log_g
[B, C, Hk]`` float32, the stacked planes with ``layer`` an int32 scalar,
``n_valid [B]`` (a row's leading columns that hold a token; the rest are
padding and contribute ``k = 0``, ``log g = 0``) and ``reset [B]`` (the
row's state starts from zero at this step).  Returns ``(o [B, C, Hq, dv],
state, norm)``.

* ``attention.retention_decode`` (``C`` = 1) and
  ``attention.retention_chunk`` — Pallas, ``ops/power_retention_kernel.py``;
* ``attention.retention_decode_xla`` / ``attention.retention_chunk_xla`` —
  the XLA anchors registered HERE (CPU, tests, other head sizes).

:func:`retention_forward` is the chunked form scanned over a whole row from
an empty state, segment-aware — what the family's forward runs without a
cache; XLA differentiates it (no backward kernel: ROADMAP Reach A4).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from automodel_tpu.ops.kernel_lib import registry

F32 = jnp.float32
FORWARD_CHUNK = 64       # the plain forward's chunk


# ---------------------------------------------------------------------------
# The embedding and the state's shape
# ---------------------------------------------------------------------------
def num_offsets(d: int) -> int:
    if d % 2:
        raise ValueError(f"power retention needs an even head size, got {d}")
    return d // 2 + 1


def offset_weights(d: int) -> np.ndarray:
    """``w[o]``: how often offset ``o`` stands for an ordered pair."""
    w = np.full((num_offsets(d),), 2.0, np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi_k(a: jnp.ndarray) -> jnp.ndarray:
    """``[..., d] -> [..., O, d]``: ``a[l] a[(l + o) % d]``."""
    d = a.shape[-1]
    idx = (np.arange(d)[None, :] + np.arange(num_offsets(d))[:, None]) % d
    return a[..., None, :] * a[..., idx]


def phi_q(a: jnp.ndarray) -> jnp.ndarray:
    return phi_k(a) * offset_weights(a.shape[-1])[:, None]


def norm_rows(d: int) -> int:
    """Rows of the stored normaliser: the offsets, to a sublane tile."""
    return -(-num_offsets(d) // 8) * 8


def state_shapes(num_kv_heads: int, head_dim: int, value_dim: int
                 ) -> Dict[str, Tuple[int, ...]]:
    """Per-row shapes of the two planes a retention layer keeps."""
    return {"state": (num_kv_heads, num_offsets(head_dim), value_dim,
                      head_dim),
            "norm": (num_kv_heads, norm_rows(head_dim), head_dim)}


def init_state(batch: int, num_kv_heads: int, head_dim: int, value_dim: int):
    """An empty ``(S, z)`` as the mathematics below takes them (``z`` of
    ``O`` rows: :func:`read_state` / :func:`write_state` keep the pad)."""
    o = num_offsets(head_dim)
    return (jnp.zeros((batch, num_kv_heads, o, value_dim, head_dim), F32),
            jnp.zeros((batch, num_kv_heads, o, head_dim), F32))


# ---------------------------------------------------------------------------
# The mathematics (XLA), on one layer's state
# ---------------------------------------------------------------------------
def _grouped(q, num_kv_heads: int):
    """``[B, C, Hq, d] -> [B, C, Hk, G, d]`` float32, scaled by ``1 / sqrt
    d``: the score is ``(q . k / sqrt d)^2`` (the scale cancels in the
    quotient and keeps the numbers in range)."""
    B, C, Hq, d = q.shape
    return (q.astype(F32) * d ** -0.5).reshape(
        B, C, num_kv_heads, Hq // num_kv_heads, d)


def recurrent_step(q, k, v, log_g, S, z):
    """One token a row, the recurrent form as written: ``q [B, Hq, d]``,
    ``k, v [B, Hk, d]``, ``log_g [B, Hk]``, ``S [B, Hk, O, dv, d]``, ``z [B,
    Hk, O, d]`` -> ``(o [B, Hq, dv], S, z)``."""
    B, Hq, d = q.shape
    Hk = k.shape[1]
    g = jnp.exp(log_g.astype(F32))
    pk = phi_k(k.astype(F32))                               # [B, Hk, O, d]
    S = g[..., None, None, None] * S + jnp.einsum(
        "bhol,bhv->bhovl", pk, v.astype(F32))
    z = g[..., None, None] * z + pk
    pq = phi_q(_grouped(q[:, None], Hk)[:, 0])              # [B, Hk, G, O, d]
    num = jnp.einsum("bhgol,bhovl->bhgv", pq, S)
    den = jnp.einsum("bhgol,bhol->bhg", pq, z)
    return (num / den[..., None]).reshape(B, Hq, -1), S, z


def chunk_step(q, k, v, log_g, S, z, valid, boundary):
    """A chunk of ``C`` tokens a row.  ``valid [B, C]``: the column holds a
    token; ``boundary [B, C]``: the token starts from an EMPTY state
    (a new document in a packed row, a row's first chunk) — it and what
    follows see nothing before it, and the state that leaves the chunk
    holds nothing before the last boundary.  Shapes as the module
    docstring; ``S, z`` one layer's."""
    B, C, Hq, d = q.shape
    Hk = k.shape[2]
    qg = _grouped(q, Hk)                                    # [B, C, Hk, G, d]
    k = jnp.where(valid[..., None, None], k.astype(F32), 0.0)
    v = v.astype(F32)
    boundary = boundary & valid
    lg = jnp.where((valid & ~boundary)[..., None], log_g.astype(F32), 0.0)
    nb = jnp.cumsum(boundary.astype(jnp.int32), axis=1)     # [B, C]
    G = jnp.cumsum(lg, axis=1)                              # [B, C, Hk]
    t = jnp.arange(C)
    # the chunk's own pairs: s <= t, a token, no boundary in (s, t]
    pair = ((t[:, None] >= t[None, :])[None] & valid[:, None, :]
            & (nb[:, :, None] == nb[:, None, :]))           # [B, t, s]
    diff = G[:, :, None] - G[:, None, :]                    # [B, t, s, Hk]
    decay = jnp.exp(jnp.where(pair[..., None], diff, -jnp.inf))
    score = jnp.einsum("bthgd,bshd->bthgs", qg, k)
    a = score * score * decay.transpose(0, 1, 3, 2)[:, :, :, None, :]
    num = jnp.einsum("bthgs,bshv->bthgv", a, v)
    den = jnp.sum(a, axis=-1)
    # through the state: tokens before the chunk's first boundary
    sees = (nb == 0)[..., None] * jnp.exp(G)                # [B, C, Hk]
    pq = phi_q(qg)                                          # [B,C,Hk,G,O,d]
    num = num + sees[..., None, None] * jnp.einsum(
        "bthgol,bhovl->bthgv", pq, S)
    den = den + sees[..., None] * jnp.einsum("bthgol,bhol->bthg", pq, z)
    # a padding column (and every column of an idle row) has nothing to
    # divide by; its output is the caller's to discard
    den = jnp.where(valid[..., None, None], den, 1.0)
    out = (num / den[..., None]).reshape(B, C, Hq, -1)
    # the state after the chunk
    last = nb[:, -1]
    keeps = (last == 0)[:, None] * jnp.exp(G[:, -1])        # [B, Hk]
    w = jnp.where(((nb == last[:, None]) & valid)[..., None],
                  jnp.exp(G[:, -1:, :] - G), 0.0)           # [B, C, Hk]
    pk = phi_k(k) * w[..., None, None]                      # [B,C,Hk,O,d]
    S = keeps[..., None, None, None] * S + jnp.einsum(
        "bshol,bshv->bhovl", pk, v)
    z = keeps[..., None, None] * z + jnp.sum(pk, axis=1)
    return out.astype(q.dtype), S, z


def retention_scan(q, k, v, log_g, S, z, valid, boundary,
                   chunk: int = FORWARD_CHUNK):
    """:func:`chunk_step` over a row of any length, ``chunk`` columns at a
    time (the row is padded to a multiple with invalid columns): ``(o, S,
    z)``."""
    B, T, Hq, _ = q.shape
    C = min(chunk, T)
    pad = -T % C

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(B, -1, C, *x.shape[2:]).swapaxes(0, 1)

    def body(carry, xs):
        o, S, z = chunk_step(*xs[:4], *carry, *xs[4:])
        return (S, z), o

    (S, z), out = lax.scan(body, (S, z), tuple(
        chunks(x) for x in (q, k, v, log_g, valid, boundary)))
    out = out.swapaxes(0, 1).reshape(B, T + pad, Hq, -1)[:, :T]
    return out, S, z


def retention_forward(q, k, v, log_g, segment_ids=None, attention_mask=None,
                      chunk: int = FORWARD_CHUNK):
    """The whole row from an empty state: ``q [B, T, Hq, d]``, ``k, v [B, T,
    Hk, d]``, ``log_g [B, T, Hk]`` -> ``[B, T, Hq, dv]``.  ``segment_ids``
    (0 = padding) keep a packed row's documents apart; ``attention_mask
    [B, T]`` marks padding where there are no segments."""
    B, T = q.shape[:2]
    if segment_ids is not None:
        valid = segment_ids != 0
        prev = jnp.pad(segment_ids[:, :-1], ((0, 0), (1, 0)))
        boundary = segment_ids != prev
    else:
        valid = (jnp.ones((B, T), bool) if attention_mask is None
                 else attention_mask.astype(bool))
        boundary = jnp.zeros((B, T), bool)
    state = init_state(B, k.shape[2], q.shape[3], v.shape[3])
    return retention_scan(q, k, v, log_g, *state, valid, boundary, chunk)[0]


# ---------------------------------------------------------------------------
# The rungs' operand contract over the stacked planes
# ---------------------------------------------------------------------------
def read_state(state, norm, layer):
    """``(S, z)`` of one layer of the stacked planes."""
    o = state.shape[3]
    return (lax.dynamic_index_in_dim(state, layer, 0, keepdims=False),
            lax.dynamic_index_in_dim(norm, layer, 0,
                                     keepdims=False)[..., :o, :])


def write_state(state, norm, layer, S, z):
    """The stacked planes with ``(S, z)`` at ``layer``."""
    z = jnp.pad(z, ((0, 0), (0, 0), (0, norm.shape[3] - z.shape[2]), (0, 0)))
    return (lax.dynamic_update_index_in_dim(state, S, layer, 0),
            lax.dynamic_update_index_in_dim(norm, z, layer, 0))


def _decode_xla_impl(request, q, k, v, log_g, state, norm, layer, n_valid,
                     reset):
    """XLA anchor of a decode step: the recurrent form, one token a row.
    An idle row (``n_valid`` 0) keeps its state; a reset row starts at 0."""
    live = (n_valid > 0)[:, None]
    lg = jnp.where(live, log_g[:, 0].astype(F32), 0.0)
    kk = jnp.where(live[..., None], k[:, 0], 0)
    keep = jnp.where(reset, 0.0, 1.0).astype(F32)
    S, z = read_state(state, norm, layer)
    o, S, z = recurrent_step(q[:, 0], kk, v[:, 0], lg,
                             S * keep[:, None, None, None, None],
                             z * keep[:, None, None, None])
    o = jnp.where(live[..., None], o, 0.0)       # an idle row divides 0 by 0
    return (o[:, None].astype(q.dtype),
            *write_state(state, norm, layer, S, z))


def _chunk_xla_impl(request, q, k, v, log_g, state, norm, layer, n_valid,
                    reset):
    """XLA anchor of a prefill chunk: :func:`chunk_step` with the row's
    leading ``n_valid`` columns valid and a boundary at column 0 of a reset
    row."""
    C = q.shape[1]
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = col < n_valid[:, None]
    boundary = (col == 0) & reset[:, None]
    # a reset row that holds no token still forgets: zero it outright
    keep = jnp.where(reset & (n_valid == 0), 0.0, 1.0).astype(F32)
    S, z = read_state(state, norm, layer)
    o, S, z = chunk_step(q, k, v, log_g,
                         S * keep[:, None, None, None, None],
                         z * keep[:, None, None, None], valid, boundary)
    return (o, *write_state(state, norm, layer, S, z))


# The family's parity oracle is the chunked XLA form (for a decode step too:
# the recurrent rung is held to another writing of the function).
retention_reference = _chunk_xla_impl


def build_retention_request(q, k, v, state) -> Dict[str, Any]:
    return {
        "kind": "power_retention",
        "q_seq": q.shape[1], "num_q_heads": q.shape[2],
        "num_kv_heads": k.shape[2], "head_dim": q.shape[3],
        "value_dim": v.shape[3], "rows": state.shape[1],
        "dtype": str(q.dtype), "state_dtype": str(state.dtype),
    }


def retention(q, k, v, log_g, state, norm, *, layer, n_valid, reset):
    """The serving path's entry point: one request, resolved down
    ``attention.retention_decode -> _xla`` for a step of one token a row
    and ``attention.retention_chunk -> _xla`` for a wider one."""
    request = build_retention_request(q, k, v, state)
    head = ("attention.retention_decode" if q.shape[1] == 1
            else "attention.retention_chunk")
    spec = registry.resolve(head, request)
    return spec.impl(request, q, k, v, log_g, state, norm,
                     jnp.asarray(layer, jnp.int32),
                     n_valid.astype(jnp.int32), reset.astype(bool))


def _xla_probe(request: Mapping[str, Any]) -> bool:
    return True          # the chains' always-available anchors


registry.register_kernel(
    "attention.retention_decode_xla", probe=_xla_probe,
    impl=_decode_xla_impl, fallback=None, reference=retention_reference)
registry.register_kernel(
    "attention.retention_chunk_xla", probe=_xla_probe,
    impl=_chunk_xla_impl, fallback=None, reference=retention_reference)
