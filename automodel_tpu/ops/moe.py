"""Mixture-of-experts routing + expert compute, the TPU way.

What the reference gets from HF transformers' ``MixtralSparseMoeBlock``
(eager per-expert gather/scatter driven by ``torch.where`` — fine on GPU,
shape-dynamic and serial) is here TWO static-shape formulations behind one
``moe.dispatch`` knob:

* ``sorted`` (default) — sort-based dropless dispatch in the MegaBlocks /
  MaxText-megablox mold: argsort the ``[T*k]`` routed assignments by expert
  id, run the SwiGLU expert FFNs as ONE grouped matmul over the sorted
  token buffer (``ops/gmm_kernel.py`` — Pallas on TPU, block-segment einsum
  fallback elsewhere), scatter-add back with the combine weights.
  ``O(T*k)`` matmul rows and no tensor carries an ``E`` dim, so compute is
  independent of the expert count — the integer-factor win at Qwen3-scale
  E=128 where dispatch/combine einsums otherwise dwarf the FFN FLOPs.
* ``onehot`` — the GShard/Switch dispatch-combine formulation: routing
  builds ``[G, M, E, C]`` dispatch/combine one-hots contracted with
  einsums.  Kept as the parity ORACLE (bit-for-bit the semantics HF
  reproduces) and for debugging; the sorted path must match it exactly,
  drops included.

Parity target: ``transformers`` Mixtral routing semantics
(``modeling_mixtral.py``: softmax over all experts in fp32 -> top-k ->
renormalize) and its ``load_balancing_loss_func``.  With
``capacity_factor=None`` both dispatches are exactly the reference's
dropless computation; under a finite ``capacity_factor`` tokens over
capacity are dropped (GShard slot-major priority — identical drop decisions
on both paths) and the residual stream passes them through unchanged.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.ops.kernel_lib import registry
from automodel_tpu.ops.moe_decode_kernel import moe_decode_reference

# ``moe.dispatch`` knob (config-load enum-validated like cp_layout; null
# spellings mean "use the default").
MOE_DISPATCHES = ("sorted", "onehot")
DEFAULT_MOE_DISPATCH = "sorted"


def normalize_moe_dispatch(dispatch: Optional[str]) -> Optional[str]:
    """Map YAML null spellings to None (single rule:
    ``config/loader.normalize_null_spelling``)."""
    from automodel_tpu.config.loader import normalize_null_spelling

    return normalize_null_spelling(dispatch)


def validate_moe_dispatch(dispatch: Optional[str]) -> Optional[str]:
    """None (defer to the default) or a member of MOE_DISPATCHES."""
    if dispatch is None:
        return None
    if dispatch not in MOE_DISPATCHES:
        raise ValueError(
            f"moe.dispatch must be one of {list(MOE_DISPATCHES)}, "
            f"got {dispatch!r}")
    return dispatch


def resolve_moe_dispatch(dispatch: Optional[str]) -> str:
    validate_moe_dispatch(dispatch)
    return dispatch if dispatch is not None else DEFAULT_MOE_DISPATCH


# An expert is ``(act(x W_gate) * x W_up) W_down``: SwiGLU (``silu``) for
# Mixtral, Qwen3-MoE and DeepSeek; ReGLU (``relu``) for SmallThinker.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def topk_routing(router_logits: jnp.ndarray, k: int, norm_topk: bool = True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """HF Mixtral routing: fp32 softmax over all experts, top-k, renormalize.

    ``norm_topk=False`` (Qwen3-MoE's ``norm_topk_prob: false``) keeps the raw
    softmax mass of the selected experts instead of renormalizing to 1.

    Returns ``(weights [..., k], expert_idx [..., k], probs [..., E])``.
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, idx = lax.top_k(probs, k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx, probs


def routing_stats(probs: jnp.ndarray, expert_idx: jnp.ndarray,
                  num_experts: int,
                  valid_tokens: Optional[jnp.ndarray] = None,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-call routing statistics for the Switch aux loss:
    ``(tokens_per_expert [k, E], router_prob [E])``, means over tokens.

    ``valid_tokens`` (same shape as the token dims, 0/1) excludes padding
    rows added by :func:`group_tokens` — sentinel expert ids already one-hot
    to zero, and the means divide by the REAL token count so pad rows can
    never dilute the loss.

    Kept separate from the loss product because HF's
    ``load_balancing_loss_func`` concatenates ALL layers' tokens before the
    ``sum_e f_e * P_e`` product — so multi-layer callers must average the
    stats across layers first (mean of products != product of means)."""
    mask = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.float32)
    token_axes = tuple(range(mask.ndim - 2))        # all but (k, E)
    probs = probs.astype(jnp.float32)
    if valid_tokens is None:
        tokens_per_expert = jnp.mean(mask, axis=token_axes)          # [k, E]
        router_prob = jnp.mean(probs,
                               axis=tuple(range(probs.ndim - 1)))    # [E]
    else:
        v = valid_tokens.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(v), 1.0)
        tokens_per_expert = jnp.sum(mask, axis=token_axes) / denom
        router_prob = jnp.sum(
            probs * v[..., None],
            axis=tuple(range(probs.ndim - 1))) / denom
    return tokens_per_expert, router_prob


def load_balancing_loss(tokens_per_expert: jnp.ndarray,
                        router_prob: jnp.ndarray) -> jnp.ndarray:
    """``E * sum_{k,e} f_{k,e} * P_e`` (HF ``load_balancing_loss_func``)."""
    num_experts = router_prob.shape[-1]
    return jnp.sum(tokens_per_expert * router_prob[None, :]) * num_experts


def _group_size(tokens: int, requested: int) -> int:
    """Tokens per group.  The token dim is PADDED up to a multiple of the
    result (:func:`group_tokens`), so the requested size is honored exactly
    whenever ``tokens >= requested`` — the old largest-divisor search
    collapsed M toward 1 for prime/awkward token counts (G -> T one-token
    groups, catastrophic dispatch overhead)."""
    return min(requested, tokens)


def group_tokens(x2d: jnp.ndarray, group_size: int
                 ) -> Tuple[jnp.ndarray, int]:
    """``[T, H] -> ([G, M, H], pad)`` with ``M = _group_size(T, group_size)``
    and ``pad = G*M - T`` zero rows appended.  Callers must mask pad tokens
    out of routing (:func:`mask_padded_tokens`) and slice them off the
    output."""
    T, H = x2d.shape
    M = _group_size(T, group_size)
    G = -(-T // M)
    pad = G * M - T
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d.reshape(G, M, H), pad


def mask_padded_tokens(weights: jnp.ndarray, idx: jnp.ndarray, pad: int,
                       num_experts: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                  Optional[jnp.ndarray]]:
    """Route the ``pad`` trailing tokens of the flattened ``[G*M]`` stream
    to the SENTINEL expert id E with zero combine weight: a sentinel
    one-hots to the zero vector (consumes no capacity, joins no dispatch)
    and the sorted path sorts it past every real segment.  Returns
    ``(weights, idx, valid [G, M] | None)``."""
    if not pad:
        return weights, idx, None
    G, M = idx.shape[:2]
    valid = (jnp.arange(G * M, dtype=jnp.int32) < G * M - pad).reshape(G, M)
    idx = jnp.where(valid[..., None], idx, num_experts)
    weights = jnp.where(valid[..., None], weights,
                        jnp.zeros((), weights.dtype))
    return weights, idx, valid


def group_and_capacity(tokens: int, group_size: int, num_experts: int,
                       k: int, capacity_factor: Optional[float]
                       ) -> Tuple[int, int]:
    """(tokens-per-group M, per-group expert capacity C) for the dispatch
    tensors.  ``capacity_factor=None`` -> lossless (C = M)."""
    M = _group_size(tokens, group_size)
    if capacity_factor is None:
        return M, M
    C = min(M, max(int(math.ceil(k * M / num_experts
                                 * float(capacity_factor))), 1))
    return M, C


def moe_mlp_block(
    x: jnp.ndarray,                 # [B, S, H]
    gate_kernel: jnp.ndarray,       # [H, E]
    w_gate: jnp.ndarray,            # [E, H, I]  (HF mixtral w1)
    w_up: jnp.ndarray,              # [E, H, I]  (HF mixtral w3)
    w_down: jnp.ndarray,            # [E, I, H]  (HF mixtral w2)
    *,
    num_experts_per_tok: int,
    capacity_factor: Optional[float] = 2.0,
    group_size: int = 512,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    norm_topk: bool = True,
    dispatch: Optional[str] = None,
    quant=None,
    router_input: Optional[jnp.ndarray] = None,     # [B, S, H]; None: x
    activation: str = "silu",
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Top-k routed gated expert FFN.  Returns ``(out [B, S, H],
    (tokens_per_expert [k, E], router_prob [E]))`` — see
    :func:`routing_stats` for how to fold the stats into the aux loss.

    ``capacity_factor=None`` means lossless: per-group expert capacity is the
    group size itself, so no assignment can overflow — exact HF parity at
    the minimal expert FLOPs.  The finite default (2.0) is the standard
    train-time trade: capacity ``C = ceil(k*M/E * cf)``.

    ``dispatch``: ``sorted`` (default) | ``onehot`` — see the module
    docstring and :func:`expert_ffn`.

    ``quant``: an enabled :class:`~automodel_tpu.ops.quant.QuantConfig`
    routes the sorted path's grouped matmuls through the int8/fp8
    ``gmm_quant`` chain (models pass theirs through
    ``quant_for(self.quant, "<experts fqn>")`` so ``filter_fqns`` applies).

    ``router_input``: the tensor the ROUTER reads where it is not the
    experts' input (SmallThinker routes on the pre-attention normed stream
    and feeds the experts the post-attention one).  ``activation``: the
    gate's, a key of :data:`ACTIVATIONS`.
    """
    B, S, H = x.shape
    E = gate_kernel.shape[-1]
    k = int(num_experts_per_tok)
    cd = compute_dtype
    T = B * S
    M, C = group_and_capacity(T, group_size, E, k, capacity_factor)

    xg, pad = group_tokens(x.reshape(T, H), M)
    # Token dim gathers every batch-ish mesh axis (dp x cp): routing is
    # per-token, so the merged [B*S] layout keeps dispatch local to shards.
    xg = constrain(xg, ("act_tokens", None, None))

    # Router in fp32 (HF computes gating in float32 for stability).
    rg = xg
    if router_input is not None:
        rg, _ = group_tokens(router_input.reshape(T, H), M)
        rg = constrain(rg, ("act_tokens", None, None))
    router_logits = rg.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)
    weights, idx, probs = topk_routing(router_logits, k,
                                       norm_topk=norm_topk)     # [G, M, k]
    weights, idx, valid = mask_padded_tokens(weights, idx, pad, E)
    aux = routing_stats(probs, idx, E, valid_tokens=valid)
    out = expert_ffn(xg, weights, idx, w_gate, w_up, w_down,
                     capacity=C, dispatch=dispatch, compute_dtype=cd,
                     quant=quant, activation=activation)
    out = out.reshape(-1, H)
    if pad:
        out = out[:T]
    return out.reshape(B, S, H), aux


def expert_ffn(
    xg: jnp.ndarray,          # [G, M, H] grouped tokens
    weights: jnp.ndarray,     # [G, M, k] combine weights
    idx: jnp.ndarray,         # [G, M, k] expert assignment (E = pad sentinel)
    w_gate: jnp.ndarray,      # [E, H, I]
    w_up: jnp.ndarray,        # [E, H, I]
    w_down: jnp.ndarray,      # [E, I, H]
    *,
    capacity: int,
    dispatch: Optional[str] = None,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    quant=None,
    activation: str = "silu",
) -> jnp.ndarray:
    """Routing-agnostic expert-FFN dispatcher (shared by Mixtral softmax
    top-k and the DeepSeek sigmoid/softmax gates): ``sorted`` grouped-matmul
    path by default, ``onehot`` GShard dispatch/combine as the oracle.

    ``quant`` applies to the sorted path only: the onehot formulation is
    kept as the bf16 parity ORACLE the quantized run is measured against,
    so it never quantizes."""
    if resolve_moe_dispatch(dispatch) == "onehot":
        return expert_dispatch_ffn(xg, weights, idx, w_gate, w_up, w_down,
                                   capacity=capacity,
                                   compute_dtype=compute_dtype,
                                   activation=activation)
    return sorted_expert_ffn(xg, weights, idx, w_gate, w_up, w_down,
                             capacity=capacity, compute_dtype=compute_dtype,
                             quant=quant, activation=activation)


def expert_dispatch_ffn(
    xg: jnp.ndarray,          # [G, M, H] grouped tokens
    weights: jnp.ndarray,     # [G, M, k] combine weights
    idx: jnp.ndarray,         # [G, M, k] expert assignment
    w_gate: jnp.ndarray,      # [E, H, I]
    w_up: jnp.ndarray,        # [E, H, I]
    w_down: jnp.ndarray,      # [E, I, H]
    *,
    capacity: int,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    activation: str = "silu",
) -> jnp.ndarray:
    """Static-shape dispatch/combine + expert-batched gated FFN — the
    GShard one-hot formulation, kept as the sorted path's parity oracle."""
    G, M, H = xg.shape
    E = w_gate.shape[0]
    k = idx.shape[-1]
    C = capacity
    cd = compute_dtype

    # Dispatch/combine build, slot-major priority (GShard): slot j's
    # assignments claim capacity after all slots < j.
    dispatch = jnp.zeros((G, M, E, C), cd)
    combine = jnp.zeros((G, M, E, C), cd)
    counts = jnp.zeros((G, 1, E), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(idx[..., j], E, dtype=jnp.int32)    # [G, M, E]
        pos = jnp.cumsum(oh, axis=1) - oh + counts              # [G, M, E]
        counts = counts + jnp.sum(oh, axis=1, keepdims=True)
        keep = (oh * (pos < C)).astype(cd)                      # [G, M, E]
        d = keep[..., None] * jax.nn.one_hot(pos, C, dtype=cd)  # [G, M, E, C]
        dispatch = dispatch + d
        combine = combine + weights[..., j, None, None].astype(cd) * d

    # Expert-batched FFN: E leading so the expert dim can shard (EP).
    expert_in = jnp.einsum("gmec,gmh->egch", dispatch, xg.astype(cd))
    expert_in = constrain(expert_in, ("experts", "act_tokens", None, None))
    h_gate = jnp.einsum("egch,ehi->egci", expert_in, w_gate.astype(cd))
    h_up = jnp.einsum("egch,ehi->egci", expert_in, w_up.astype(cd))
    h_act = ACTIVATIONS[activation](h_gate) * h_up
    expert_out = jnp.einsum("egci,eih->egch", h_act, w_down.astype(cd))
    expert_out = constrain(expert_out, ("experts", "act_tokens", None, None))
    return jnp.einsum("egch,gmec->gmh", expert_out, combine)


def _assignment_positions(idx: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """``[G, M, k] -> [G, M, k]`` GShard slot-major position of each routed
    assignment in its (group, expert) capacity queue — the EXACT priority
    ``expert_dispatch_ffn`` uses, so capacity drops are decided identically
    on both dispatch paths.  ``O(T*E*k)`` int ops, no ``[.., E, C]``
    tensors."""
    G, M, k = idx.shape
    counts = jnp.zeros((G, 1, num_experts), jnp.int32)
    pos = []
    for j in range(k):
        oh = jax.nn.one_hot(idx[..., j], num_experts, dtype=jnp.int32)
        pj = jnp.cumsum(oh, axis=1) - oh + counts               # [G, M, E]
        counts = counts + jnp.sum(oh, axis=1, keepdims=True)
        pos.append(jnp.sum(pj * oh, axis=-1))                   # [G, M]
    return jnp.stack(pos, axis=-1)


def sorted_expert_ffn(
    xg: jnp.ndarray,          # [G, M, H] grouped tokens
    weights: jnp.ndarray,     # [G, M, k] combine weights
    idx: jnp.ndarray,         # [G, M, k] expert assignment (E = pad sentinel)
    w_gate: jnp.ndarray,      # [E, H, I]
    w_up: jnp.ndarray,        # [E, H, I]
    w_down: jnp.ndarray,      # [E, I, H]
    *,
    capacity: Optional[int] = None,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    block_rows: int = 128,
    quant=None,
    activation: str = "silu",
) -> jnp.ndarray:
    """Sort-based expert FFN: ``O(T*k*H*I)`` compute, no ``[.., E, C]``
    tensors.

    1. Decide capacity drops with the oracle's slot-major priority
       (``capacity >= M`` — the lossless/dropless case — skips this
       entirely) and send dropped/pad assignments to the sentinel id E.
    2. Stable-argsort the ``[G*M*k]`` assignments by expert id and build
       per-expert group sizes; each expert's segment is placed at a
       ``block_rows``-aligned offset (static ``N + E*block_rows`` buffer) so
       the grouped matmul tiles never straddle a ragged boundary and the
       XLA fallback stays ``O(N)`` (``gmm_kernel._gmm_xla_blocked``).
    3. Run the SwiGLU expert FFNs as grouped matmuls over the sorted buffer.
    4. Scatter-add back through the combine weights (dropped/pad slots carry
       weight 0 and rows past the segments are zeroed by ``gmm``).

    Sharding: the sorted token buffer keeps the merged-token layout
    (``act_tokens`` over dp/cp mesh axes); expert weights keep their
    ``experts``/``expert_mlp`` parameter axes, so the existing
    ``expert_parallel`` rules in ``distributed/shardings.py`` apply
    unchanged.
    """
    G, M, H = xg.shape
    E = w_gate.shape[0]
    I_mlp = w_gate.shape[-1]
    k = idx.shape[-1]
    T = G * M
    N = T * k
    cd = compute_dtype
    B = int(block_rows)

    if capacity is not None and capacity < M:
        pos = _assignment_positions(idx, E)
        eid = jnp.where(pos < capacity, idx, E)
    else:
        eid = idx
    eid_flat = eid.reshape(N)
    order = jnp.argsort(eid_flat)           # stable: ties keep token order
    sizes = jnp.bincount(eid_flat, length=E + 1)[:E].astype(jnp.int32)

    # Block-aligned segment layout: expert e's rows live at
    # [seg_off[e], seg_off[e] + sizes[e]) with seg_off a multiple of B.
    padded = -(-sizes // B) * B
    n_pad = -(-(N + E * B) // B) * B        # static upper bound
    seg_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]])
    raw_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)[:-1]])

    rows = jnp.arange(n_pad, dtype=jnp.int32)
    gid = jnp.searchsorted(seg_off + padded, rows, side="right")
    gid_c = jnp.minimum(gid, E - 1)
    rank = rows - jnp.take(seg_off, gid_c)
    in_seg = (gid < E) & (rank < jnp.take(sizes, gid_c))
    slot = jnp.take(raw_off, gid_c) + jnp.minimum(
        rank, jnp.maximum(jnp.take(sizes, gid_c) - 1, 0))
    src = jnp.take(order, jnp.clip(slot, 0, N - 1))     # assignment index
    tok = src // k                                      # source token row

    x_flat = xg.reshape(T, H)
    x_sorted = jnp.where(in_seg[:, None], jnp.take(x_flat, tok, axis=0),
                         jnp.zeros((), x_flat.dtype)).astype(cd)
    x_sorted = constrain(x_sorted, ("act_tokens", None))

    from automodel_tpu.ops.gmm_kernel import gmm

    wg, wu, wd = (w.astype(cd) for w in (w_gate, w_up, w_down))
    # Quantized compute (``fp8.enabled``): the three grouped matmuls run on
    # the int8/fp8 path with per-group dynamic scales.  The 16-alignment
    # gate mirrors maybe_qdot's torchao rule; the combine/scatter stays in
    # compute dtype either way.
    if (quant is not None and getattr(quant, "enabled", False)
            and H % 16 == 0 and I_mlp % 16 == 0):
        from automodel_tpu.ops.gmm_quant_kernel import gmm_quant

        def _mm(lhs, rhs):
            return gmm_quant(lhs, rhs, padded, quant.dtype,
                             quant.recipe_name, block_aligned=True,
                             block_rows=B)
    else:
        def _mm(lhs, rhs):
            return gmm(lhs, rhs, padded, block_aligned=True, block_rows=B)

    h_gate = _mm(x_sorted, wg)
    h_up = _mm(x_sorted, wu)
    h_act = constrain(ACTIVATIONS[activation](h_gate) * h_up,
                      ("act_tokens", "expert_mlp"))
    out_sorted = _mm(h_act, wd)
    out_sorted = constrain(out_sorted, ("act_tokens", None))

    w_sorted = jnp.where(in_seg, jnp.take(weights.reshape(N), src),
                         jnp.zeros((), weights.dtype)).astype(cd)
    out = jnp.zeros((T, H), cd).at[tok].add(out_sorted * w_sorted[:, None])
    return constrain(out.reshape(G, M, H), ("act_tokens", None, None))


def held_experts_local(weights: jnp.ndarray, idx: jnp.ndarray, first: int,
                       count: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routing over ALL experts -> routing over the ``count`` experts held
    from index ``first``: an assignment to an expert held elsewhere goes to
    the sentinel id ``count`` with weight zero (it joins no dispatch, like a
    padded token's), a held one keeps its weight — already normalised over
    everything the token chose — under its local index."""
    local = idx - first
    held = (local >= 0) & (local < count)
    return (jnp.where(held, weights, jnp.zeros((), weights.dtype)),
            jnp.where(held, local, count))


DECODE_CHUNK = 256      # rows of one expert's segment multiplied at a time


def _devices_spanned(stack) -> int:
    """The devices an expert stack lies on, as far as the call can see: an
    array says so itself; a traced one lies on the mesh of the sharding
    context its forward is built under, on one device where none is
    active."""
    if isinstance(stack, jax.Array) and not isinstance(stack, jax.core.Tracer):
        return len(stack.sharding.device_set)
    from automodel_tpu.distributed.shardings import current_sharding

    ctx = current_sharding()
    return 1 if ctx is None else ctx[0].size


def decode_expert_ffn(
    x: jnp.ndarray,           # [T, H] tokens
    weights: jnp.ndarray,     # [T, k] combine weights
    idx: jnp.ndarray,         # [T, k] expert assignment (E = sentinel: none)
    w_gate: jnp.ndarray,      # [L, E, H, I]: the stacks of ALL layers
    w_up: jnp.ndarray,        # [L, E, H, I]
    w_down: jnp.ndarray,      # [L, E, I, H]
    *,
    layer,                    # int32 scalar: which layer's experts
    compute_dtype: jnp.dtype = jnp.bfloat16,
    activation: str = "silu",
    quant=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serving step's expert FFN: dropless, no capacity tiles, work in
    proportion to the assignments there ARE.  A decode step routes a few
    dozen tokens, so most experts see one or two and some none; what an
    expert costs is the read of its weights, and an expert nobody chose is
    never read.  Forward only: training keeps :func:`expert_ffn`.

    Two forms behind one registry chain, chosen by what the call sees:

    * ``moe_decode.pallas`` (``ops/moe_decode_kernel.py``): ONE Mosaic call
      a layer that walks the hit experts and streams their weights back to
      back.  It takes decode-width steps (every hit expert multiplies all
      ``T`` rows, so ``T`` is bounded there) of unquantized, lane-aligned
      experts on a TPU whose stacks lie whole on the device of the step;
    * ``moe_decode.loop``: the assignments sorted by expert, each expert
      taking its own segment in ``DECODE_CHUNK``-row pieces under a loop
      whose trip count is the segment's (none for an expert nobody chose):
      a gather, the gated FFN (``activation``), a scatter-add.  The wide
      (mixed) step, where an expert's rows are a small share of ``T``; a
      CPU; an enabled ``quant``
      (:class:`~automodel_tpu.ops.quant.QuantConfig`), whose three products
      go through ``maybe_qdot`` as the model's projections do.

    ``layer`` may be traced (a layer scan's index): the weights are the
    stacks of ALL layers and an expert's matrices are addressed at ``(layer,
    expert)`` right where they are multiplied.  Handed one layer's ``[E, H,
    I]`` slice, either form would take it as an operand, and the scan would
    first copy the layer's whole stack out of its ``xs`` to make it one (a
    gigabyte a layer at Kimi-K2's widths).

    Returns ``(out [T, H], tokens_per_expert [E] int32)``."""
    request = {
        "kind": "moe_decode", "rows": x.shape[0], "hidden": x.shape[1],
        "inter": w_gate.shape[-1], "experts": w_gate.shape[1],
        "quantized": bool(quant is not None
                          and getattr(quant, "enabled", False)),
        "devices": _devices_spanned(w_gate),
        "dtype": str(jnp.dtype(compute_dtype))}
    return registry.dispatch(
        "moe_decode.pallas", request, x, weights, idx, w_gate, w_up, w_down,
        jnp.asarray(layer, jnp.int32), compute_dtype=compute_dtype,
        activation=activation, quant=quant)


def _decode_loop_impl(request, x, weights, idx, w_gate, w_up, w_down, layer,
                      *, compute_dtype, activation, quant=None):
    """The ``moe_decode.loop`` rung (:func:`decode_expert_ffn`)."""
    from automodel_tpu.ops.quant import maybe_qdot

    T, H = x.shape
    E = w_gate.shape[1]
    k = idx.shape[-1]
    N = T * k
    cd = compute_dtype
    chunk = min(DECODE_CHUNK, -(-T // 16) * 16)  # a decode step: one small piece
    eid = idx.reshape(N)
    order = jnp.argsort(eid)            # stable: the sentinel sorts last
    sizes = jnp.bincount(eid, length=E + 1)[:E].astype(jnp.int32)
    offs = jnp.cumsum(sizes) - sizes
    tok_sorted = (order // k).astype(jnp.int32)
    w_sorted = jnp.take(weights.reshape(N), order).astype(jnp.float32)
    lane = jnp.arange(chunk, dtype=jnp.int32)
    act = ACTIVATIONS[activation]

    def piece(e, c, out):
        rank = c * chunk + lane
        at = jnp.minimum(offs[e] + rank, N - 1)
        tok = jnp.take(tok_sorted, at)
        w = jnp.where(rank < sizes[e], jnp.take(w_sorted, at), 0.0)
        xs = jnp.take(x, tok, axis=0).astype(cd)
        wg, wu, wd = (lax.dynamic_slice(
            m, (layer, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0].astype(cd)
            for m in (w_gate, w_up, w_down))
        mm = lambda a, w: maybe_qdot(a, w, quant, "experts")
        y = mm(act(mm(xs, wg)) * mm(xs, wu), wd)
        return out.at[tok].add(y.astype(jnp.float32) * w[:, None])

    def expert(e, out):
        return lax.fori_loop(0, (sizes[e] + chunk - 1) // chunk,
                             lambda c, o: piece(e, c, o), out)

    out = lax.fori_loop(0, E, expert, jnp.zeros((T, H), jnp.float32))
    return out.astype(cd), sizes


registry.register_kernel(
    "moe_decode.loop", probe=lambda request: True, impl=_decode_loop_impl,
    fallback=None, reference=moe_decode_reference)


def noaux_topk_routing(
    scores: jnp.ndarray,      # [..., E] f32 sigmoid scores
    bias: jnp.ndarray,        # [E] e_score_correction_bias (selection only)
    k: int,
    *,
    n_group: int = 1,
    topk_group: int = 1,
    norm_topk: bool = True,
    routed_scaling_factor: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3 aux-loss-free router (HF ``DeepseekV3TopkRouter``).

    The correction bias shifts SELECTION only; combine weights gather from
    the raw sigmoid scores (so the bias carries no gradient path, matching
    HF's ``@torch.no_grad`` index computation).  Group-limited routing:
    per-group score = sum of its top-2 biased scores, only the top
    ``topk_group`` groups stay eligible (the rest masked to 0.0 exactly as
    HF ``masked_fill(..., 0.0)`` — NOT -inf, preserving tie behavior with
    negative biased scores).

    Returns ``(weights [..., k] scaled, idx [..., k])``.
    """
    E = scores.shape[-1]
    biased = scores + bias.astype(scores.dtype)
    if n_group > 1:
        gs = biased.reshape(*biased.shape[:-1], n_group, E // n_group)
        group_score = jnp.sum(lax.top_k(gs, 2)[0], axis=-1)   # [..., n_group]
        _, gidx = lax.top_k(group_score, topk_group)
        gmask = jnp.sum(
            jax.nn.one_hot(gidx, n_group, dtype=scores.dtype), axis=-2)
        biased = jnp.where(gmask[..., :, None] > 0, gs, 0.0).reshape(
            biased.shape)
    _, idx = lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * routed_scaling_factor, idx


def softmax_group_topk_routing(
    scores: jnp.ndarray,      # [..., E] f32 SOFTMAX scores
    k: int,
    *,
    topk_method: str = "greedy",
    n_group: int = 1,
    topk_group: int = 1,
    routed_scaling_factor: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V2 gate (HF ``DeepseekV2MoEGate``): softmax scores;
    ``greedy`` = plain top-k (V2-Lite), ``group_limited_greedy`` = per-group
    MAX score ranks groups, only the top ``topk_group`` groups stay
    eligible (masked to 0.0, matching HF ``masked_fill``).  Combine
    weights are the selected scores times ``routed_scaling_factor`` —
    V2 does NOT renormalize the top-k mass.

    Returns ``(weights [..., k], idx [..., k])``.
    """
    E = scores.shape[-1]
    if topk_method == "greedy":
        weights, idx = lax.top_k(scores, k)
    elif topk_method == "group_limited_greedy":
        gs = scores.reshape(*scores.shape[:-1], n_group, E // n_group)
        group_score = jnp.max(gs, axis=-1)                    # [..., n_group]
        _, gidx = lax.top_k(group_score, topk_group)
        gmask = jnp.sum(
            jax.nn.one_hot(gidx, n_group, dtype=scores.dtype), axis=-2)
        masked = jnp.where(gmask[..., :, None] > 0, gs, 0.0).reshape(
            scores.shape)
        weights, idx = lax.top_k(masked, k)
    else:
        raise NotImplementedError(f"topk_method {topk_method!r}")
    return weights * routed_scaling_factor, idx
