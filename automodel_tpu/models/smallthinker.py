"""SmallThinker family (HF ``model_type: smallthinker``, e.g.
SmallThinker-21BA3B-Instruct, arXiv:2507.20984): a Llama-shaped decoder
whose layers are of two KINDS in one stack, every layer's feed-forward a
routed block of small ReLU-gated experts.

Per layer ``l`` (``config.json``'s per-layer lists decide the kind):

* ``rope_layout[l]``: 1 = rotary embedding on q and k; 0 = none (NoPE);
* ``sliding_window_layout[l]``: 1 = a query sees the last
  ``sliding_window_size`` keys; 0 = every key before it.

The published 21B stack is periodic: layer ``4i`` is full attention without
rotary embedding, layers ``4i+1..4i+3`` are window attention with it.

The block, on a stream ``x``::

    u = rmsnorm(x)            q, k, v = u Wq, u Wk, u Wv     (no bias, no q/k norm)
    h = x + attention(q, k, v) Wo
    m = rmsnorm(h)
    r = u Wr                  # the router reads u, the PRE-attention stream
    S = top-k of r;  p = softmax_float32(r[S])
    x' = h + sum_{e in S} p_e (relu(m G_e) * (m U_e)) D_e

**Two kinds, static, in ONE scan.**  The layer parameters stay stacked
``[L, ...]``; the forward finds the shortest period ``P`` of the two lists,
views every leaf as ``[L/P, P, ...]`` and hands ``scan_layers`` ONE
sub-stack whose body runs the period's ``P`` layers in order, each with its
kind as Python constants.  So the window reaches ``attention()`` and
``paged_attention()`` as a static int (the Pallas rungs resolve), no
``lax.cond`` traces both kinds, and the program holds one compiled body a
KIND of layer, not one a layer.  A stack with no period is one body of
``L`` layers.

**A cache of two block groups.**  ``paged_cache_planes()`` declares the
cache PER GROUP of layers (``serving/kv_cache.cache_groups``): ``full``
(every key kept) and ``window`` (blocks wholly behind the window are
released while the request runs).  Inside the period body a layer stands at
``cache.at_layer(state, l, group=(name, index in the group))``.

Not in this family's ``config.json`` and so not here: secondary experts and
the LM-head sparsity predictor that the paper describes.  The parameter
NAMES of the key map (``models/hf_io.smallthinker_key_map``) follow the
published ``modeling_smallthinker.py`` as remembered, not as read.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.layer_scan import (
    SubStack,
    default_position_ids,
    norm_and_head,
    scan_layers,
)
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.moe import (
    decode_expert_ffn,
    moe_mlp_block,
    topk_routing,
)
from automodel_tpu.ops.quant import quant_for
from automodel_tpu.ops.remat import checkpoint_name

FULL, WINDOW = "full", "window"


@dataclasses.dataclass
class SmallThinkerConfig(LlamaConfig):
    """The published ``config.json`` keys, as they are named there."""

    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: Tuple[int, ...] = ()           # () -> rotary everywhere
    sliding_window_layout: Tuple[int, ...] = ()  # () -> full everywhere
    sliding_window_size: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    tie_word_embeddings: bool = False
    # TPU-side knobs of the training dispatch (``ops/moe.py``); serving is
    # dropless whatever these say
    moe_capacity_factor: Optional[float] = None
    moe_group_size: int = 512
    moe_dispatch: Optional[str] = None

    def __post_init__(self):
        super().__post_init__()
        self.model_type = "smallthinker"
        L = self.num_hidden_layers
        self.rope_layout = tuple(int(x) for x in self.rope_layout) or (1,) * L
        self.sliding_window_layout = tuple(
            int(x) for x in self.sliding_window_layout) or (0,) * L
        for name in ("rope_layout", "sliding_window_layout"):
            lst = getattr(self, name)
            if len(lst) != L or set(lst) - {0, 1}:
                raise ValueError(
                    f"smallthinker: {name} must hold num_hidden_layers={L} "
                    f"entries of 0 or 1, got {lst}")
        if not (self.moe_primary_router_apply_softmax
                and self.norm_topk_prob):
            raise NotImplementedError(
                "smallthinker: only the published routing is implemented "
                "(moe_primary_router_apply_softmax and norm_topk_prob both "
                "true: softmax over the chosen experts' logits)")
        from automodel_tpu.ops.moe import (
            normalize_moe_dispatch,
            validate_moe_dispatch,
        )

        self.moe_dispatch = validate_moe_dispatch(
            normalize_moe_dispatch(self.moe_dispatch))

    def layer_kinds(self) -> Tuple[Tuple[bool, bool], ...]:
        """Per layer ``(rotary, window)``."""
        return tuple((bool(r), bool(w)) for r, w in
                     zip(self.rope_layout, self.sliding_window_layout))

    def period(self) -> int:
        """The shortest ``P`` dividing ``L`` with ``kind[l] == kind[l % P]``
        (``L`` itself for a stack with no period)."""
        kinds, L = self.layer_kinds(), self.num_hidden_layers
        return next(P for P in range(1, L + 1) if L % P == 0 and all(
            kinds[l] == kinds[l % P] for l in range(L)))


class SmallThinkerForCausalLM(LlamaForCausalLM):
    """Llama's embedding, projections, rope tables and head round a stack
    of window and full (NoPE) layers with routed ReGLU experts.

    Param tree per layer (stacked over ``L``), the rest as Llama's:
      ``block_sparse_moe/primary_router/kernel``   [L, H, E]
      ``block_sparse_moe/experts/gate/kernel``     [L, E, H, I]
      ``block_sparse_moe/experts/up/kernel``       [L, E, H, I]
      ``block_sparse_moe/experts/down/kernel``     [L, E, I, H]
    """

    # the forward is its own (a period scan): the pipelined step's replay of
    # Llama's embed -> uniform scan -> head does not describe it
    pp_safe = False

    def _init_ffn(self, keys, dense):
        cfg = self.config
        H, I, E = (cfg.hidden_size, cfg.moe_ffn_hidden_size,
                   cfg.moe_num_primary_experts)
        return {
            "block_sparse_moe": {
                "primary_router": {"kernel": dense(next(keys), (H, E))},
                "experts": {
                    "gate": {"kernel": dense(next(keys), (E, H, I))},
                    "up": {"kernel": dense(next(keys), (E, H, I))},
                    "down": {"kernel": dense(next(keys), (E, I, H))},
                },
            },
        }

    def _ffn_axes(self):
        wide = ("layers", "experts", "embed", "expert_mlp")
        return {
            "block_sparse_moe": {
                "primary_router": {"kernel": ("layers", "embed", None)},
                "experts": {
                    "gate": {"kernel": wide},
                    "up": {"kernel": wide},
                    "down": {"kernel": ("layers", "experts", "expert_mlp",
                                        "embed")},
                },
            },
        }

    # -- the cache, per group of layers ------------------------------------
    def cache_group_of(self) -> Tuple[Tuple[str, int], ...]:
        """Per layer ``(group name, index among the group's layers)``."""
        seen = {FULL: 0, WINDOW: 0}
        out = []
        for _, window in self.config.layer_kinds():
            name = WINDOW if window else FULL
            out.append((name, seen[name]))
            seen[name] += 1
        return tuple(out)

    def paged_cache_planes(self) -> Dict[str, Any]:
        """The serving cache, declared per GROUP of layers that share a
        pool, an allocator and a block table a request: per-head keys and
        values in both, and in ``window`` the number of keys behind a query
        that its layers may still see (what lies wholly behind it is
        released while the request runs)."""
        cfg = self.config
        per_head = (cfg.num_key_value_heads, cfg.head_dim)
        counts: Dict[str, int] = {}
        for name, _ in self.cache_group_of():
            counts[name] = counts.get(name, 0) + 1
        return {name: {"planes": {"k": per_head, "v": per_head},
                       "layers": n,
                       "window": (int(cfg.sliding_window_size)
                                  if name == WINDOW else None)}
                for name, n in counts.items()}

    # -- one layer, its kind static ----------------------------------------
    def _attention_block(self, hidden, p, rotary: bool, window: bool,
                         position_ids, segment_ids, attention_mask,
                         inv_freq, rope_scale, cache):
        """``(h, u32, cache_state)``: the attention block's output stream, the
        pre-attention normed stream in float32 (the router reads it) and the
        cache's new state."""
        cfg = self.config
        B, S, _ = hidden.shape
        D, Hq, Hk = (cfg.head_dim, cfg.num_attention_heads,
                     cfg.num_key_value_heads)
        proj = self._make_proj(None, 1.0, 0.0, "post", None)
        att = p["self_attn"]
        with jax.named_scope("attn_window" if window else "attn_full"):
            # the norm's float32 result goes to the router as it is and to
            # the projections in the compute dtype: rounding it first moves
            # a router logit by ~1e-3 of their rms, and top-k is a step
            # function.  Layer 0's logits are a function of the token id
            # alone, so a token whose k-th and (k+1)-th logit lie that close
            # would take the other expert at EVERY position that holds it
            u32 = self._norm(hidden.astype(jnp.float32), p["input_layernorm"],
                             cfg.rms_norm_eps)
            u = u32.astype(hidden.dtype)
            q = proj(u, att["q_proj"], "self_attn.q_proj").reshape(B, S, Hq, D)
            k = proj(u, att["k_proj"], "self_attn.k_proj").reshape(B, S, Hk, D)
            v = proj(u, att["v_proj"], "self_attn.v_proj").reshape(B, S, Hk, D)
            if rotary:
                q, k = self._apply_rope(q, k, position_ids, inv_freq,
                                        rope_scale)
            attn, state = self._attention_core(
                q, k, v, segment_ids, attention_mask, cache,
                local_window_size=(int(cfg.sliding_window_size)
                                   if window else None))
            attn = checkpoint_name(attn, "attn_core")
            attn = proj(attn.reshape(B, S, Hq * D), att["o_proj"],
                        "self_attn.o_proj")
            return hidden + attn, u32, state

    def _experts_block(self, u, m, moe, experts, layer, valid):
        """The routed block: the router on ``u`` (float32), the experts on
        ``m``.
        Decoding (``experts``: the stacks of ALL layers, sliced at ``layer``
        where they are multiplied; ``valid [B, S]``: the forward's real
        columns): dropless and decode-shaped, returns the tokens each
        expert got.  Otherwise ``ops/moe.moe_mlp_block`` and its routing
        statistics."""
        cfg = self.config
        B, S, H = m.shape
        k = cfg.moe_num_active_primary_experts
        router = moe["primary_router"]["kernel"]
        if experts is None:
            ex = moe["experts"]
            return moe_mlp_block(
                m, router, ex["gate"]["kernel"], ex["up"]["kernel"],
                ex["down"]["kernel"], num_experts_per_tok=k,
                capacity_factor=cfg.moe_capacity_factor,
                group_size=cfg.moe_group_size,
                compute_dtype=self.compute_dtype, norm_topk=True,
                dispatch=cfg.moe_dispatch,
                quant=quant_for(self.quant, "block_sparse_moe.experts"),
                router_input=u.astype(m.dtype), activation="relu")
        with jax.named_scope("moe_router"):
            # float32 in earnest: on a TPU a default-precision float32
            # product is one bfloat16 pass, and a choice among near-tied
            # logits should not turn on that
            logits = jnp.matmul(
                u.reshape(B * S, H),
                router.astype(jnp.float32), precision=lax.Precision.HIGHEST)
            weights, idx, _ = topk_routing(logits, k, norm_topk=True)
            idx = jnp.where(valid.reshape(-1, 1), idx,
                            cfg.moe_num_primary_experts)
        with jax.named_scope("moe_experts"):
            out, counts = decode_expert_ffn(
                m.reshape(B * S, H), weights, idx,
                experts["gate"]["kernel"], experts["up"]["kernel"],
                experts["down"]["kernel"], layer=layer,
                compute_dtype=self.compute_dtype, activation="relu",
                quant=quant_for(self.quant, "block_sparse_moe.experts"))
        return out.reshape(B, S, H), counts

    def forward_embeds(
        self,
        params: Dict[str, Any],
        hidden: jnp.ndarray,
        position_ids: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        return_hidden: bool = False,
        adapters: Optional[Dict[str, Any]] = None,
        adapter_scale: float = 1.0,
        adapter_dropout: float = 0.0,
        adapter_dropout_position: str = "post",
        dropout_rng: Optional[jax.Array] = None,
        kv_cache: Optional[Any] = None,
    ) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        if adapters is not None:
            raise NotImplementedError(
                "rank-r LoRA bypass is not wired for the smallthinker "
                "period scan; use peft merge mode")
        B, S = hidden.shape[:2]
        # a forward with a decode cache runs no backward: its experts take
        # the dropless decode-shaped dispatch, and the cache says which
        # columns hold a token (the others stay out of the routing)
        decoding = kv_cache is not None
        if position_ids is None:
            position_ids = default_position_ids(kv_cache, B, S)
        hidden = constrain(hidden.astype(self.compute_dtype),
                           ("act_batch", "act_seq", "act_embed"))
        inv_freq, rope_scale = self._rope_tables(position_ids)
        valid = kv_cache.valid_tokens() if decoding else None

        P = cfg.period()
        kinds, groups = cfg.layer_kinds()[:P], self.cache_group_of()
        per_period = {name: sum(1 for g, _ in groups[:P] if g == name)
                      for name in (FULL, WINDOW)}
        stack = params["layers"]
        experts = None
        if decoding:
            # the expert stacks stay OUT of the scan's xs: the step slices
            # one expert's matrices at (layer, expert) where it multiplies
            # them (``decode_expert_ffn``)
            moe = stack["block_sparse_moe"]
            experts = moe["experts"]
            stack = dict(stack, block_sparse_moe={
                k: v for k, v in moe.items() if k != "experts"})
        # [L, ...] -> [L / P, P, ...]: a view, the layers stay stacked
        stack = jax.tree.map(
            lambda a: a.reshape(a.shape[0] // P, P, *a.shape[1:]), stack)

        def period(h, p, _, idx, cache):
            state, ys = (cache.pools if cache is not None else None), []
            for j, (rotary, window) in enumerate(kinds):
                pj = jax.tree.map(lambda a: a[j], p)
                layer = idx * P + j
                name, rank = groups[j]
                at = (None if cache is None else cache.at_layer(
                    state, layer,
                    group=(name, idx * per_period[name] + rank)))
                with jax.named_scope("attn"):
                    h, u, state = self._attention_block(
                        h, pj, rotary, window, position_ids, segment_ids,
                        attention_mask, inv_freq, rope_scale, at)
                with jax.named_scope("mlp"):
                    m = self._norm(h, pj["post_attention_layernorm"],
                                   cfg.rms_norm_eps)
                    out, y = self._experts_block(
                        u, m, pj["block_sparse_moe"], experts, layer, valid)
                    h = constrain(h + out, ("act_batch", "act_seq",
                                            "act_embed"))
                ys.append(y)
            return h, state, jax.tree.map(lambda *a: jnp.stack(a), *ys)

        hidden, cache_state, (ys,) = scan_layers(
            hidden, [SubStack(stack, period)], kv_cache, remat=self.remat,
            remat_policy=self.remat_policy, scan_unroll=self.scan_unroll)
        # [L / P, P, ...] -> [L, ...]
        ys = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)

        out = norm_and_head(
            hidden, params,
            lambda h, p: self._norm(h, p, cfg.rms_norm_eps),
            tied=cfg.tie_word_embeddings, compute_dtype=self.compute_dtype,
            return_hidden=return_hidden)
        if decoding:
            out["expert_tokens"] = ys       # [L, E]: tokens each expert got
        if kv_cache is not None:
            out["kv_cache"] = cache_state
        return out

    def flops_per_token(self) -> float:
        cfg = self.config
        attn = (2 * cfg.hidden_size
                * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads)
                * cfg.head_dim
                + 2 * cfg.num_attention_heads * cfg.head_dim
                * cfg.hidden_size)
        ffn = (cfg.moe_num_active_primary_experts * 6 * cfg.hidden_size
               * cfg.moe_ffn_hidden_size)
        router = 2 * cfg.hidden_size * cfg.moe_num_primary_experts
        embed = 2 * cfg.vocab_size * cfg.hidden_size
        return 3.0 * (cfg.num_hidden_layers * (attn + ffn + router) + embed)
