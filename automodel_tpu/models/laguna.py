"""Laguna family (HF ``model_type: laguna``, e.g. poolside's Laguna-S-2.1):
a decoder whose attention layers are of two KINDS that differ in SHAPE, a
leading dense layer, then routed SwiGLU experts beside a shared one.

Per layer ``l`` (``config.json``'s per-layer lists decide):

* ``layer_types[l]``: ``full_attention`` (every key before a query) or
  ``sliding_attention`` (the last ``sliding_window`` keys);
* ``num_attention_heads_per_layer[l]``: the query heads, one count a kind
  (48 on full layers and 72 on window layers at the published widths, over
  the same 8 key/value heads);
* ``rope_parameters[kind]``: each kind its own rotary table (YaRN over the
  first half of the head on full layers, default RoPE over all of it on
  window layers);
* ``mlp_layer_types[l]`` / ``mlp_only_layers``: a dense SwiGLU MLP or the
  routed block.

The block, on a stream ``x``::

    u = rmsnorm(x)
    q, k, v = u Wq, u Wk, u Wv;  q, k = rmsnorm_head(q), rmsnorm_head(k)
    q, k = rope_kind(q, k)                   # the kind's table and width
    a_h = attention(q_h, k, v)               # window layers: the last W keys
    g = sigmoid(u Wg)  [T, Hq]               # one gate a query head
    h = x + concat_h(g_h * a_h) Wo
    m = rmsnorm(h)
    dense:  y = SwiGLU(m)
    routed: r = m Wr (float32); S = top-k of r; p = softmax(r[S]) * scale
            y = sum_{e in S, held} p_e SwiGLU_e(m) + SwiGLU_shared(m)
    x' = h + y

**Stacks by kind, in ONE scan.**  A kind's q, o and gate kernels differ in
shape from the other kind's, so the layers cannot share one stack.  The
routed layers' parameters live in one stack a kind (``layers_full``,
``layers_window``); the forward finds the shortest period ``P`` of the
routed layers' kinds and hands ``scan_layers`` a sub-stack whose leaves are
each kind's stack viewed ``[periods, count of the kind in a period, ...]``
and whose body runs the period's ``P`` layers in order, each with its kind
(and so its head count, window and rotary table) as Python constants: one
compiled body a kind of layer, no ``lax.cond``.  The leading dense layers
are a sub-stack of their own before it (``dense_layers``), as
``deepseek_v3.py`` runs its dense layer, and routed layers past the last
whole period a third (a copy of their slice of the stacks: a stack whose
routed layers are whole periods has none).

**A cache of two block groups** (``serving/kv_cache.cache_groups``):
``full`` and ``window``, per-head keys and values in both.

``held_experts: [first, count]`` makes this model one expert-parallel
share of a layer: the router stays ``num_experts`` wide and picks
``num_experts_per_tok``, the combine weights are normalised over ALL the
chosen, and a layer computes the held experts' part and the shared expert
(``ops/moe.held_experts_local``).

What ``config.json`` does not settle is inferred, and the benchmark's
configuration lists it under ``assumed``: the gate is the headwise gate of
"Gated Attention for Large Language Models" (arXiv:2505.06708), a sigmoid
of ``u`` without bias; softmax routing over the chosen logits (the Qwen-MoE
key family's), no selection bias, no cap, the shared expert ungated; a
per-head RMSNorm on q and k (Qwen3's convention); rotate-half pairing over
the rotating channels, YaRN's ``attention_factor`` on their cos and sin.
No published checkpoint layout is known here, so the family has no key
map.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.layer_scan import (
    SubStack,
    default_position_ids,
    norm_and_head,
    scan_layers,
)
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.moe import (
    expert_ffn,
    group_and_capacity,
    group_tokens,
    held_experts_local,
    routed_decode_block,
    router_logits,
    topk_routing,
)
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.quant import quant_for
from automodel_tpu.ops.remat import checkpoint_name
from automodel_tpu.ops.rotary import apply_rope, rope_parameters

FULL, WINDOW = "full", "window"
KIND_OF = {"full_attention": FULL, "sliding_attention": WINDOW}
ROPE_KEY = {FULL: "full_attention", WINDOW: "sliding_attention"}
MOE_GROUP = 512     # tokens a group of the cache-less forward's dispatch


@dataclasses.dataclass
class LagunaConfig(LlamaConfig):
    """The published ``config.json`` keys, as they are named there."""

    intermediate_size: int = 12288        # the dense layers' MLP
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    moe_routed_scaling_factor: float = 1.0
    moe_router_logit_softcapping: float = 0.0
    moe_apply_router_weight_on_input: bool = False
    gating: Optional[str] = None
    gating_types: Tuple[str, ...] = ()
    layer_types: Tuple[str, ...] = ()      # () -> full attention everywhere
    num_attention_heads_per_layer: Tuple[int, ...] = ()   # () -> one count
    rope_parameters: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    # [first, count]: the routed experts THIS model holds, as one share of
    # an expert-parallel layer; None = all of num_experts
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        super().__post_init__()
        self.model_type = "laguna"
        self.qk_norm = True
        L = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types) or ("full_attention",) * L
        self.num_attention_heads_per_layer = tuple(
            int(h) for h in self.num_attention_heads_per_layer) or (
            self.num_attention_heads,) * L
        gate = (self.gating or "").replace("-", "_") or None
        self.gating_types = tuple(
            (g or "").replace("-", "_") or None
            for g in self.gating_types) or (gate,) * L
        self.mlp_only_layers = tuple(int(l) for l in self.mlp_only_layers)
        if not self.mlp_layer_types:
            step = max(int(self.decoder_sparse_step), 1)
            self.mlp_layer_types = tuple(
                "dense" if l in self.mlp_only_layers or (l + 1) % step
                else "sparse" for l in range(L))
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if any(self.mlp_layer_types[l] != "dense" for l in self.mlp_only_layers
               if l < L):
            raise ValueError("laguna: mlp_only_layers and mlp_layer_types "
                             "disagree")
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "gating_types", "mlp_layer_types"):
            if len(getattr(self, name)) != L:
                raise ValueError(f"laguna: {name} must hold num_hidden_layers"
                                 f"={L} entries, got {getattr(self, name)}")
        if set(self.layer_types) - set(KIND_OF):
            raise ValueError(f"laguna: layer_types takes {sorted(KIND_OF)}, "
                             f"got {sorted(set(self.layer_types))}")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("laguna: mlp_layer_types takes 'dense' and "
                             "'sparse'")
        kd = self.dense_layers()
        if "dense" in self.mlp_layer_types[kd:]:
            raise NotImplementedError(
                "laguna: dense layers are run as a leading sub-stack; a "
                f"dense layer after a routed one ({self.mlp_layer_types}) "
                "is not wired")
        if len({self.kinds()[l] for l in range(kd)}) > 1:
            raise NotImplementedError(
                "laguna: the leading dense layers share one stack, so they "
                "must be of one kind")
        for kind in (FULL, WINDOW):
            counts = {h for h, t in zip(self.num_attention_heads_per_layer,
                                        self.kinds()) if t == kind}
            if len(counts) > 1:
                raise NotImplementedError(
                    f"laguna: the {kind} layers have query-head counts "
                    f"{sorted(counts)}; a kind's layers share one stack")
        for h in self.num_attention_heads_per_layer:
            if h % self.num_key_value_heads:
                raise ValueError(f"laguna: {h} query heads do not divide "
                                 f"into {self.num_key_value_heads} kv heads")
        if set(self.gating_types) - {"per_head", None} \
                or len(set(self.gating_types)) > 1:
            raise NotImplementedError(
                "laguna: the output gate is per_head on every layer or on "
                f"none, got {self.gating_types}")
        if self.attention_bias:
            raise NotImplementedError("laguna: attention without biases "
                                      "only (attention_bias false)")
        if self.moe_router_logit_softcapping or \
                self.moe_apply_router_weight_on_input or \
                not self.norm_topk_prob:
            raise NotImplementedError(
                "laguna: only the published routing is implemented (softmax "
                "over the chosen logits, no cap, the weight on the output)")
        if WINDOW in self.kinds() and not self.sliding_window:
            raise ValueError("laguna: window layers need sliding_window")
        if self.held_experts is not None:
            held = tuple(int(v) for v in self.held_experts)
            if len(held) != 2 or held[0] < 0 or held[1] < 1 \
                    or held[0] + held[1] > self.num_experts:
                raise ValueError(
                    f"laguna: held_experts={self.held_experts!r} must be "
                    f"[first, count] within num_experts={self.num_experts}")
            self.held_experts = held

    def kinds(self) -> Tuple[str, ...]:
        """Per layer ``full`` or ``window``."""
        return tuple(KIND_OF[t] for t in self.layer_types)

    def dense_layers(self) -> int:
        """How many leading layers run the dense MLP."""
        return next((l for l, t in enumerate(self.mlp_layer_types)
                     if t != "dense"), self.num_hidden_layers)

    def heads(self, kind: str) -> int:
        return next((h for h, t in zip(self.num_attention_heads_per_layer,
                                       self.kinds()) if t == kind),
                    self.num_attention_heads)

    @property
    def gated(self) -> bool:
        return self.gating_types[0] == "per_head"

    @property
    def n_held_experts(self) -> int:
        return (self.num_experts if self.held_experts is None
                else self.held_experts[1])

    def period(self) -> int:
        """The shortest ``P`` with ``kind[l] == kind[l % P]`` over the
        routed layers (the last period may be cut short)."""
        kinds = self.kinds()[self.dense_layers():]
        return next((P for P in range(1, len(kinds) + 1) if all(
            kinds[l] == kinds[l % P] for l in range(len(kinds)))), 1)

    def rope(self, kind: str) -> Tuple[int, float, Optional[dict]]:
        """``(rotary dim, theta, scaling)`` of a kind's rotary table."""
        rp = (self.rope_parameters or {}).get(ROPE_KEY[kind])
        if rp is None:
            return self.head_dim, float(self.rope_theta), self.rope_scaling
        dim = int(self.head_dim * float(rp.get("partial_rotary_factor", 1.0)))
        kind_of_table = rp.get("rope_type", rp.get("type", "default"))
        return (dim, float(rp.get("rope_theta", self.rope_theta)),
                None if kind_of_table == "default" else dict(rp))


class LagunaForCausalLM(LlamaForCausalLM):
    """Llama's embedding, projections and head round per-kind stacks of
    gated attention layers (window and full, each its own head count and
    rotary table), a leading dense layer and routed SwiGLU experts beside a
    shared one.

    Param tree (``n``: layers of the stack; per layer as Llama's, plus):
      ``*/self_attn/{q,k}_norm/weight``        [n, D]
      ``*/self_attn/attn_gate/kernel``         [n, H, Hq]  (gated configs)
      ``dense_layers/mlp/{gate,up,down}_proj``  as Llama's MLP
      ``layers_{full,window}/mlp/router/kernel``            [n, H, E]
      ``layers_{full,window}/mlp/experts/{gate,up}_proj``   [n, held, H, I]
      ``layers_{full,window}/mlp/experts/down_proj``        [n, held, I, H]
      ``layers_{full,window}/mlp/shared_expert/...``        as Llama's MLP
    """

    # the forward is its own (stacks by kind): the pipelined step's replay
    # of Llama's embed -> uniform scan -> head does not describe it
    pp_safe = False

    def __init__(self, config: LagunaConfig, **kwargs):
        super().__init__(config, **kwargs)
        self._rope = {}
        for kind in (FULL, WINDOW):
            dim, theta, scaling = config.rope(kind)
            inv, scale = rope_parameters(
                dim, theta, scaling,
                max_position_embeddings=config.max_position_embeddings)
            self._rope[kind] = (inv, scale, dim)

    # -- the layout of the stack -------------------------------------------
    def stack_counts(self) -> Dict[str, int]:
        """Layers in each stack: the dense one and the routed ones a kind."""
        cfg = self.config
        kd = cfg.dense_layers()
        routed = cfg.kinds()[kd:]
        return {"dense_layers": kd,
                **{f"layers_{k}": routed.count(k) for k in (FULL, WINDOW)}}

    def cache_group_of(self) -> Tuple[Tuple[str, int], ...]:
        """Per layer ``(group name, index among the group's layers)``."""
        seen = {FULL: 0, WINDOW: 0}
        out = []
        for kind in self.config.kinds():
            out.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(out)

    def paged_cache_planes(self) -> Dict[str, Any]:
        """The serving cache, declared per GROUP of layers (``full`` and
        ``window``): per-head keys and values in both, and in ``window``
        the keys a query may still see."""
        cfg = self.config
        per_head = (cfg.num_key_value_heads, cfg.head_dim)
        kinds = cfg.kinds()
        return {kind: {"planes": {"k": per_head, "v": per_head},
                       "layers": kinds.count(kind),
                       "window": (int(cfg.sliding_window) if kind == WINDOW
                                  else None)}
                for kind in (FULL, WINDOW) if kind in kinds}

    # -- init ----------------------------------------------------------------
    def _attn_params(self, dense, n: int, hq: int) -> Dict[str, Any]:
        cfg = self.config
        H, D, Hk = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
        ones = lambda shape: jnp.ones((n, *shape), self.param_dtype)
        attn = {
            "q_proj": {"kernel": dense((H, hq * D), n)},
            "k_proj": {"kernel": dense((H, Hk * D), n)},
            "v_proj": {"kernel": dense((H, Hk * D), n)},
            "o_proj": {"kernel": dense((hq * D, H), n)},
            "q_norm": {"weight": ones((D,))},
            "k_norm": {"weight": ones((D,))},
        }
        if cfg.gated:
            attn["attn_gate"] = {"kernel": dense((H, hq), n)}
        return attn

    def _mlp_params(self, dense, n: int, inter: int) -> Dict[str, Any]:
        H = self.config.hidden_size
        return {"gate_proj": {"kernel": dense((H, inter), n)},
                "up_proj": {"kernel": dense((H, inter), n)},
                "down_proj": {"kernel": dense((inter, H), n)}}

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        H, V = cfg.hidden_size, cfg.vocab_size
        E, Eh, I = (cfg.num_experts, cfg.n_held_experts,
                    cfg.moe_intermediate_size)
        keys = iter(jax.random.split(key, 64))

        def dense(shape, n=None):
            full = shape if n is None else (n, *shape)
            return (jax.random.normal(next(keys), full, jnp.float32)
                    * 0.02).astype(self.param_dtype)

        def block(n: int, kind: str) -> Dict[str, Any]:
            return {
                "input_layernorm": {"weight": jnp.ones((n, H),
                                                       self.param_dtype)},
                "self_attn": self._attn_params(dense, n, cfg.heads(kind)),
                "post_attention_layernorm": {
                    "weight": jnp.ones((n, H), self.param_dtype)},
            }

        params: Dict[str, Any] = {
            "embed_tokens": {"embedding": dense((V, H))},
            "norm": {"weight": jnp.ones((H,), self.param_dtype)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": dense((H, V))}
        counts = self.stack_counts()
        kd = counts["dense_layers"]
        if kd:
            params["dense_layers"] = dict(
                block(kd, cfg.kinds()[0]),
                mlp=self._mlp_params(dense, kd, cfg.intermediate_size))
        for kind in (FULL, WINDOW):
            n = counts[f"layers_{kind}"]
            if not n:
                continue
            params[f"layers_{kind}"] = dict(block(n, kind), mlp={
                "router": {"kernel": dense((H, E), n)},
                "experts": {"gate_proj": {"kernel": dense((Eh, H, I), n)},
                            "up_proj": {"kernel": dense((Eh, H, I), n)},
                            "down_proj": {"kernel": dense((Eh, I, H), n)}},
                "shared_expert": self._mlp_params(
                    dense, n, cfg.shared_expert_intermediate_size)})
        return params

    def param_axes(self) -> Dict[str, Any]:
        cfg = self.config
        attn = {
            "q_proj": {"kernel": ("layers", "embed", "heads")},
            "k_proj": {"kernel": ("layers", "embed", "heads")},
            "v_proj": {"kernel": ("layers", "embed", "heads")},
            "o_proj": {"kernel": ("layers", "heads", "embed")},
            "q_norm": {"weight": ("layers", "head_dim")},
            "k_norm": {"weight": ("layers", "head_dim")},
        }
        if cfg.gated:
            attn["attn_gate"] = {"kernel": ("layers", "embed", None)}
        mlp = {"gate_proj": {"kernel": ("layers", "embed", "mlp")},
               "up_proj": {"kernel": ("layers", "embed", "mlp")},
               "down_proj": {"kernel": ("layers", "mlp", "embed")}}
        wide = ("layers", "experts", "embed", "expert_mlp")
        block = {"input_layernorm": {"weight": ("layers", "norm")},
                 "self_attn": attn,
                 "post_attention_layernorm": {"weight": ("layers", "norm")}}
        axes: Dict[str, Any] = {
            "embed_tokens": {"embedding": ("vocab", "embed")},
            "norm": {"weight": ("norm",)},
        }
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = {"kernel": ("embed", "vocab")}
        for name, n in self.stack_counts().items():
            if not n:
                continue
            axes[name] = dict(block, mlp=mlp if name == "dense_layers" else {
                "router": {"kernel": ("layers", "embed", None)},
                "experts": {"gate_proj": {"kernel": wide},
                            "up_proj": {"kernel": wide},
                            "down_proj": {"kernel": (
                                "layers", "experts", "expert_mlp", "embed")}},
                "shared_expert": mlp})
        return axes

    # -- one layer, its kind static ------------------------------------------
    def _rotate(self, q, k, position_ids, kind: str):
        """The kind's rotary table over its first ``rotary dim`` channels;
        the rest of each head passes as it is."""
        inv, scale, dim = self._rope[kind]
        return apply_rope(q, k, position_ids, jnp.asarray(inv),
                          attention_scaling=scale, rotary_dim=dim)

    def _attention_block(self, hidden, p, kind: str, position_ids,
                         segment_ids, attention_mask, cache):
        """``(h, cache_state)``: the gated attention block of a layer of
        kind ``kind``."""
        cfg = self.config
        B, S, _ = hidden.shape
        D, Hk, Hq = cfg.head_dim, cfg.num_key_value_heads, cfg.heads(kind)
        eps = cfg.rms_norm_eps
        proj = self._make_proj(None, 1.0, 0.0, "post", None)
        att = p["self_attn"]
        with jax.named_scope("attn_" + kind):
            u = self._norm(hidden, p["input_layernorm"], eps)
            q = proj(u, att["q_proj"], "self_attn.q_proj").reshape(B, S, Hq, D)
            k = proj(u, att["k_proj"], "self_attn.k_proj").reshape(B, S, Hk, D)
            v = proj(u, att["v_proj"], "self_attn.v_proj").reshape(B, S, Hk, D)
            q = rms_norm(q, att["q_norm"]["weight"], eps)
            k = rms_norm(k, att["k_norm"]["weight"], eps)
            q, k = self._rotate(q, k, position_ids, kind)
            attn, state = self._attention_core(
                q, k, v, segment_ids, attention_mask, cache,
                local_window_size=(int(cfg.sliding_window) if kind == WINDOW
                                   else None))
            attn = checkpoint_name(attn, "attn_core")
            if cfg.gated:
                with jax.named_scope("attn_gate"):
                    g = jax.nn.sigmoid(proj(u, att["attn_gate"],
                                            "self_attn.attn_gate")
                                       .astype(jnp.float32))
                    attn = (attn.astype(jnp.float32)
                            * g[..., None]).astype(attn.dtype)
            out = proj(attn.reshape(B, S, Hq * D), att["o_proj"],
                       "self_attn.o_proj")
            return hidden + out, state

    def _swiglu(self, x, p, name: str):
        proj = self._make_proj(None, 1.0, 0.0, "post", None)
        gate = proj(x, p["gate_proj"], f"{name}.gate_proj")
        up = proj(x, p["up_proj"], f"{name}.up_proj")
        return proj(jax.nn.silu(gate) * up, p["down_proj"],
                    f"{name}.down_proj")

    def _experts_block(self, m32, p, experts, at, valid):
        """The routed block on the post-attention normed stream: the router
        on its float32 result, the experts and the shared expert on its
        rounding.  Decoding (``experts``: the held stacks of ALL this kind's
        layers, ``at`` this layer's place in them; ``valid [B, S]``: the
        forward's real columns): dropless and decode-shaped, returns the
        tokens each held expert got.  Otherwise the grouped dispatch of
        ``ops/moe.expert_ffn``, lossless, and no counts."""
        cfg = self.config
        B, S, H = m32.shape
        T, k, Eh = B * S, cfg.num_experts_per_tok, cfg.n_held_experts
        cd = self.compute_dtype
        m = m32.astype(cd)
        quant = quant_for(self.quant, "mlp.experts")

        def route():
            logits = router_logits(m32.reshape(T, H), p["router"]["kernel"])
            weights, idx, _ = topk_routing(logits, k, norm_topk=True)
            weights = weights * cfg.moe_routed_scaling_factor
            if cfg.held_experts is not None:
                return held_experts_local(weights, idx, *cfg.held_experts)
            return weights, idx

        counts = None
        if experts is not None:
            routed, counts = routed_decode_block(
                m.reshape(T, H), route, valid, experts["gate_proj"]["kernel"],
                experts["up_proj"]["kernel"],
                experts["down_proj"]["kernel"], layer=at, compute_dtype=cd,
                quant=quant)
        else:
            with jax.named_scope("moe_router"):
                weights, idx = route()
            ex = p["experts"]
            with jax.named_scope("moe_experts"):
                M, C = group_and_capacity(T, MOE_GROUP, Eh, k, None)
                xg, pad = group_tokens(m.reshape(T, H), M)
                xg = constrain(xg, ("act_tokens", None, None))
                if pad:
                    weights = jnp.pad(weights, ((0, pad), (0, 0)))
                    idx = jnp.pad(idx, ((0, pad), (0, 0)),
                                  constant_values=Eh)
                routed = expert_ffn(
                    xg, weights.reshape(*xg.shape[:2], k),
                    idx.reshape(*xg.shape[:2], k),
                    ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
                    ex["down_proj"]["kernel"], capacity=C, compute_dtype=cd,
                    quant=quant).reshape(-1, H)[:T]
        with jax.named_scope("moe_shared"):
            shared = self._swiglu(m, p["shared_expert"], "mlp.shared_expert")
        return routed.reshape(B, S, H).astype(cd) + shared, counts

    def _layer(self, h, p, kind: str, dense: bool, experts, at, cache,
               position_ids, segment_ids, attention_mask, valid):
        """One layer: ``(h, cache_state, expert counts or None)``."""
        cfg = self.config
        with jax.named_scope("attn"):
            h, state = self._attention_block(
                h, p, kind, position_ids, segment_ids, attention_mask, cache)
        with jax.named_scope("mlp"):
            if dense:
                with jax.named_scope("dense_mlp"):
                    m = self._norm(h, p["post_attention_layernorm"],
                                   cfg.rms_norm_eps)
                    out, counts = self._swiglu(m, p["mlp"], "mlp"), None
            else:
                m32 = self._norm(h.astype(jnp.float32),
                                 p["post_attention_layernorm"],
                                 cfg.rms_norm_eps)
                out, counts = self._experts_block(m32, p["mlp"], experts,
                                                  at, valid)
            h = constrain(h + out, ("act_batch", "act_seq", "act_embed"))
        return h, state, counts

    # -- the forward ----------------------------------------------------------
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
                "rank-r LoRA bypass is not wired for the laguna stacks by "
                "kind; use peft merge mode")
        B, S = hidden.shape[:2]
        # a forward with a decode cache runs no backward: its experts take
        # the dropless decode-shaped dispatch, and the cache says which
        # columns hold a token (the others stay out of the routing)
        decoding = kv_cache is not None
        if position_ids is None:
            position_ids = default_position_ids(kv_cache, B, S)
        hidden = constrain(hidden.astype(self.compute_dtype),
                           ("act_batch", "act_seq", "act_embed"))
        valid = kv_cache.valid_tokens() if decoding else None
        kd = cfg.dense_layers()
        run = dict(position_ids=position_ids, segment_ids=segment_ids,
                   attention_mask=attention_mask, valid=valid)

        stacks: List[SubStack] = []
        if kd:
            def dense_layer(h, p, _, idx, cache, kind=cfg.kinds()[0]):
                view = (None if cache is None else cache.at_layer(
                    cache.pools, idx, group=(kind, idx)))
                return self._layer(h, p, kind, True, None, None, view, **run)

            stacks.append(SubStack(params["dense_layers"], dense_layer))

        routed = cfg.kinds()[kd:]
        P = cfg.period()
        whole, tail = divmod(len(routed), P)
        present = [k for k in (FULL, WINDOW) if f"layers_{k}" in params]
        experts = {}
        by_kind = {}
        for kind in present:
            stack = params[f"layers_{kind}"]
            if decoding:
                # the expert stacks stay OUT of the scan's xs: the step
                # slices one expert's matrices at (layer, expert) where it
                # multiplies them (``decode_expert_ffn``)
                experts[kind] = stack["mlp"]["experts"]
                stack = dict(stack, mlp={n: v for n, v in stack["mlp"].items()
                                         if n != "experts"})
            by_kind[kind] = stack
        # the dense layers lead their kind's cache group; a kind's stack
        # holds ``per[kind]`` layers a period
        lead = {k: (kd if k == cfg.kinds()[0] else 0) for k in present}
        per = {k: routed[:P].count(k) for k in present}

        def blocks_of(kinds: Tuple[str, ...], n: int, first_block: int):
            """A sub-stack of ``n`` blocks of the layers ``kinds``, from
            block ``first_block`` of the routed stack on."""
            count = {k: kinds.count(k) for k in present if k in kinds}
            rank = [kinds[:j].count(kinds[j]) for j in range(len(kinds))]

            def body(h, p, _, idx, cache):
                state = cache.pools if cache is not None else None
                ys = []
                # the scan counts the dense layers first, then one a block
                block = idx - kd
                for j, kind in enumerate(kinds):
                    pj = jax.tree.map(lambda a: a[rank[j]], p[kind])
                    at = (first_block * per[kind] + (block - first_block)
                          * count[kind] + rank[j])
                    view = (None if cache is None else cache.at_layer(
                        state, kd + block * P + j,
                        group=(kind, at + lead[kind])))
                    h, state, y = self._layer(
                        h, pj, kind, False, experts.get(kind), at, view,
                        **run)
                    ys.append(y)
                return h, state, (jax.tree.map(lambda *a: jnp.stack(a), *ys)
                                  if decoding else None)

            views = {k: jax.tree.map(
                lambda a, c=c, at=first_block * per[k]: a[at:at + n * c]
                .reshape(n, c, *a.shape[1:]), by_kind[k])
                for k, c in count.items()}
            return SubStack(views, body)

        if whole:
            stacks.append(blocks_of(routed[:P], whole, 0))
        if tail:
            stacks.append(blocks_of(routed[whole * P:], 1, whole))
        hidden, cache_state, ys = scan_layers(
            hidden, stacks, kv_cache, remat=self.remat,
            remat_policy=self.remat_policy, scan_unroll=self.scan_unroll)

        out = norm_and_head(
            hidden, params,
            lambda h, p: self._norm(h, p, cfg.rms_norm_eps),
            tied=cfg.tie_word_embeddings, compute_dtype=self.compute_dtype,
            return_hidden=return_hidden)
        if decoding:
            out["kv_cache"] = cache_state
            routed_ys = ys[1:] if kd else ys
            if routed_ys:
                # [blocks, layers a block, held] -> [routed layers, held]
                out["expert_tokens"] = jnp.concatenate(
                    [y.reshape(-1, *y.shape[2:]) for y in routed_ys])
        return out

    def flops_per_token(self) -> float:
        """Training FLOPs a token (3x the forward's matrix products): each
        layer's attention at its kind's head count, the dense MLP or the
        router, the shared expert and the held share of the chosen experts
        (``k * held / num_experts`` a token on average), and the head."""
        cfg = self.config
        H, D, Hk = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
        kd = cfg.dense_layers()
        total = 2 * cfg.vocab_size * H
        for l, kind in enumerate(cfg.kinds()):
            hq = cfg.heads(kind)
            total += 2 * H * (hq + 2 * Hk) * D + 2 * hq * D * H
            if cfg.gated:
                total += 2 * H * hq
            if l < kd:
                total += 6 * H * cfg.intermediate_size
            else:
                total += (2 * H * cfg.num_experts
                          + 6 * H * cfg.shared_expert_intermediate_size
                          + 6 * H * cfg.moe_intermediate_size
                          * cfg.num_experts_per_tok * cfg.n_held_experts
                          / cfg.num_experts)
        return 3.0 * total


def laguna_key_map(config):
    """No published checkpoint layout of this family is known here: loading
    or saving HF safetensors is refused, loudly."""
    raise NotImplementedError(
        "laguna: no HF parameter names are wired for this family; its "
        "weights come from init() or a native checkpoint")
