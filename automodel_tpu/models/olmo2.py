"""OLMo-2 family (HF ``model_type: olmo2``, e.g. allenai/OLMo-2-1124-7B).

The reference trains these through HF transformers
(``nemo_automodel/components/_transformers/auto_model.py:384``); parity
target is ``transformers/models/olmo2/modeling_olmo2.py``.  Two deltas from
the Llama decoder, both norm placement:

* **post-norm residual order** — no input norms; the block norms are
  applied to the attention / MLP OUTPUT before the residual add
  (``h = resid + norm(attn(h))``);
* **full-width q/k RMSNorm** — ``q_norm``/``k_norm`` normalize the whole
  projection output (``[Hq*D]`` / ``[Hk*D]``), not per head
  (Qwen3-style), and run BEFORE the head reshape + rope.

Everything else (projection machinery incl. LoRA/quant, attention core,
SwiGLU MLP, decode cache) is inherited from ``LlamaForCausalLM`` via the
``_make_proj`` / ``_attention_core`` hooks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.remat import checkpoint_name


@dataclasses.dataclass
class Olmo2Config(LlamaConfig):
    def __post_init__(self):
        super().__post_init__()
        self.model_type = "olmo2"
        self.qk_norm = False        # per-head norm off: OLMo-2 is full-width


class Olmo2ForCausalLM(LlamaForCausalLM):
    """``model_type: olmo2`` — post-norm Llama variant."""

    def init(self, key: jax.Array) -> Dict[str, Any]:
        params = super().init(key)
        cfg = self.config
        L, D = cfg.num_hidden_layers, cfg.head_dim
        layers = params["layers"]
        # post-norm layout: input_layernorm -> post_feedforward_layernorm
        layers["post_feedforward_layernorm"] = layers.pop("input_layernorm")
        layers["self_attn"]["q_norm"] = {"weight": jnp.ones(
            (L, cfg.num_attention_heads * D), self.param_dtype)}
        layers["self_attn"]["k_norm"] = {"weight": jnp.ones(
            (L, cfg.num_key_value_heads * D), self.param_dtype)}
        return params

    def param_axes(self) -> Dict[str, Any]:
        axes = super().param_axes()
        layers = axes["layers"]
        layers["post_feedforward_layernorm"] = layers.pop("input_layernorm")
        layers["self_attn"]["q_norm"] = {"weight": ("layers", "heads")}
        layers["self_attn"]["k_norm"] = {"weight": ("layers", "heads")}
        return axes

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

        # Attention on the RAW residual stream; full-width q/k RMSNorm
        # (scope names as in the shared layer: llama.py::_decoder_layer)
        with jax.named_scope("attn"):
            resid = hidden
            q = rms_norm(proj(hidden, p["self_attn"]["q_proj"],
                              "self_attn.q_proj"),
                         p["self_attn"]["q_norm"]["weight"], cfg.rms_norm_eps)
            k = rms_norm(proj(hidden, p["self_attn"]["k_proj"],
                              "self_attn.k_proj"),
                         p["self_attn"]["k_norm"]["weight"], cfg.rms_norm_eps)
            v = proj(hidden, p["self_attn"]["v_proj"], "self_attn.v_proj")
            q = q.reshape(B, S, Hq, D)
            k = k.reshape(B, S, Hk, D)
            v = v.reshape(B, S, Hk, D)
            q, k = self._apply_rope(q, k, position_ids, inv_freq, rope_scale)
            attn, new_cache = self._attention_core(
                q, k, v, segment_ids, attention_mask, kv_cache)
            attn = checkpoint_name(attn, "attn_core")
            attn = proj(attn.reshape(B, S, Hq * D), p["self_attn"]["o_proj"],
                        "self_attn.o_proj")
            hidden = resid + rms_norm(
                attn, p["post_attention_layernorm"]["weight"],
                cfg.rms_norm_eps)

        with jax.named_scope("mlp"):
            resid = hidden
            down, moe_aux = self._mlp_block(hidden, p, proj)
            down = rms_norm(down, p["post_feedforward_layernorm"]["weight"],
                            cfg.rms_norm_eps)
            out = constrain(resid + down,
                            ("act_batch", "act_seq", "act_embed"))
        return out, new_cache, moe_aux
