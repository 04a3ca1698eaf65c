"""Brumby family (HF ``model_type: brumby``, manifestai/Brumby-14B-Base).

The Qwen3 block that ``LlamaForCausalLM`` runs (``qk_norm``, GQA, RoPE,
SwiGLU) with the softmax attention core replaced by POWER RETENTION
(``ops/power_retention.py``: Manifest AI, arXiv:2507.04239): a gated
linear attention whose feature map is the symmetric power embedding of
degree 2, ``phi(q) . phi(k) = (q . k)^2``, normalised by the sum of its
weights.  Every layer is of that kind: the model has no softmax attention
and no key/value cache.  Two deltas from the Llama decoder:

* **the gate** — one logit a key/value head, ``log g = logsigmoid(x W_g +
  b_g)`` in float32 (``self_attn.g_proj``: kernel ``[H, Hk]`` and a bias;
  a zero bias is a bias-free gate);
* **the core** — while decoding, the cache is a per-SEQUENCE state plane
  (``serving/kv_cache.StatePlaneView``; ``generation.DenseKVView`` carries
  the same planes) and the layer calls its ONE method, ``retain``; without
  a cache the chunked form runs over the whole row from an empty state,
  segment-aware (``power_retention.retention_forward``).

``config.json`` does not hold the degree (``power_degree``, 2 in the release
note; nothing else is wired), the gate's projection or the state's type:
``benchmark/configs/brumby-14b.json`` lists them under ``assumed``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops import power_retention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.remat import checkpoint_name


@dataclasses.dataclass
class BrumbyConfig(LlamaConfig):
    power_degree: int = 2
    tie_word_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        self.model_type = "brumby"
        self.qk_norm = True             # the Qwen3 lineage's per-head norms
        if self.power_degree != 2:
            raise NotImplementedError(
                f"power_degree={self.power_degree}: the symmetric embedding "
                "is wired for degree 2 only (ops/power_retention.py)")
        if self.attention_bias:
            raise NotImplementedError(
                "brumby: attention_bias is published false; the gate's is "
                "the only bias in the block")


class BrumbyForCausalLM(LlamaForCausalLM):
    """``model_type: brumby`` — Qwen3's block over power retention."""

    # no per-token cache to split by stage; the pipelined step is untested
    pp_safe = False

    def init(self, key: jax.Array) -> Dict[str, Any]:
        params = super().init(key)
        cfg = self.config
        L, H, Hk = (cfg.num_hidden_layers, cfg.hidden_size,
                    cfg.num_key_value_heads)
        gate = jax.random.normal(jax.random.fold_in(key, 0x6A7E),
                                 (L, H, Hk), jnp.float32) * 0.02
        params["layers"]["self_attn"]["g_proj"] = {
            "kernel": gate.astype(self.param_dtype),
            "bias": jnp.zeros((L, Hk), self.param_dtype)}
        return params

    def param_axes(self) -> Dict[str, Any]:
        axes = super().param_axes()
        # 8 logits a token: replicated, like the norms
        axes["layers"]["self_attn"]["g_proj"] = {
            "kernel": ("layers", "embed", None), "bias": ("layers", None)}
        return axes

    # -- the cache this family keeps ---------------------------------------
    def state_plane_shapes(self) -> Dict[str, Any]:
        cfg = self.config
        return power_retention.state_shapes(
            cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim)

    def paged_cache_planes(self) -> Dict[str, Any]:
        """Per-SEQUENCE planes, not per-token ones: ``("sequence", *shape)``
        a plane (``serving/kv_cache.init_paged_pools`` allocates a row of
        that shape per step-buffer row and layer, float32, and no block
        pool)."""
        return {name: ("sequence", *shape)
                for name, shape in self.state_plane_shapes().items()}

    def init_kv_cache(self, batch: int, max_len: int,
                      dtype: Optional[Any] = None) -> Dict[str, jnp.ndarray]:
        """The state of ``generate()``'s cache: the same planes, a row a
        request; ``max_len`` costs nothing."""
        L = self.config.num_hidden_layers
        return {name: jnp.zeros((L, batch, *shape), jnp.float32)
                for name, shape in self.state_plane_shapes().items()}

    # -- forward -----------------------------------------------------------
    def _decoder_layer(self, hidden, layer_params, position_ids, segment_ids,
                       attention_mask, inv_freq, adapters=None,
                       adapter_scale=1.0, adapter_dropout=0.0,
                       dropout_position="post", dropout_rng=None,
                       kv_cache=None, rope_scale=1.0):
        cfg = self.config
        B, S, H = hidden.shape
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        p = layer_params
        proj = self._make_proj(adapters, adapter_scale, adapter_dropout,
                               dropout_position, dropout_rng)

        # scope names as in the shared layer (llama.py::_decoder_layer),
        # the retention's own inside ``attn``
        with jax.named_scope("attn"):
            resid = hidden
            x = self._norm(hidden, p["input_layernorm"], cfg.rms_norm_eps)
            att = p["self_attn"]
            q = proj(x, att["q_proj"], "self_attn.q_proj").reshape(B, S, Hq, D)
            k = proj(x, att["k_proj"], "self_attn.k_proj").reshape(B, S, Hk, D)
            v = proj(x, att["v_proj"], "self_attn.v_proj").reshape(B, S, Hk, D)
            q = rms_norm(q, att["q_norm"]["weight"], cfg.rms_norm_eps)
            k = rms_norm(k, att["k_norm"]["weight"], cfg.rms_norm_eps)
            q, k = self._apply_rope(q, k, position_ids, inv_freq, rope_scale)
            with jax.named_scope("retention_gate"):
                log_g = jax.nn.log_sigmoid(
                    proj(x, att["g_proj"], "self_attn.g_proj")
                    .astype(jnp.float32))                   # [B, S, Hk]
            new_cache = None
            with jax.named_scope("attn_core"):
                if kv_cache is not None:
                    attn, new_cache = kv_cache.retain(q, k, v, log_g)
                else:
                    attn = power_retention.retention_forward(
                        q, k, v, log_g, segment_ids=segment_ids,
                        attention_mask=attention_mask)
            attn = checkpoint_name(attn, "attn_core")
            with jax.named_scope("retention_out"):
                attn = proj(attn.reshape(B, S, Hq * D), att["o_proj"],
                            "self_attn.o_proj")
            hidden = resid + attn

        with jax.named_scope("mlp"):
            resid = hidden
            x = self._norm(hidden, p["post_attention_layernorm"],
                           cfg.rms_norm_eps)
            down, moe_aux = self._mlp_block(x, p, proj)
            out = constrain(resid + down,
                            ("act_batch", "act_seq", "act_embed"))
        return out, new_cache, moe_aux

    def flops_per_token(self) -> float:
        """Training FLOPs/token: the matmul parameters (gate included) and
        the retention core (a multiply and an add per entry of the state,
        for the update of each kv head and the read-out of each query
        head), fwd + bwd = 3x fwd."""
        cfg = self.config
        d = cfg.head_dim
        core = ((cfg.num_attention_heads + cfg.num_key_value_heads)
                * 2 * (d * (d + 1) // 2) * (d + 1))
        gate = 2 * cfg.hidden_size * cfg.num_key_value_heads
        return super().flops_per_token() + 3.0 * cfg.num_hidden_layers * (
            core + gate)

    def attention_flops_per_token(self, seq_len: int,
                                  causal: bool = True) -> float:
        """0: retention's work does not grow with the row's length."""
        return 0.0
