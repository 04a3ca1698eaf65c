"""Self-contained GPT-2 for YAML-driven pretraining.

TPU re-design of the reference's vanilla-PyTorch GPT-2
(``nemo_automodel/components/models/gpt2.py:64-198``): same architecture
(learned positions, pre-LN blocks, GELU MLP, tied lm_head, GPT-2-style
scaled residual init), expressed as a stacked-layer pytree scanned by
``lax.scan`` like :mod:`automodel_tpu.models.llama`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.layer_scan import SubStack, scan_layers
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import layer_norm


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50304
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    model_type: str = "gpt2"

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "GPT2Config":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


class GPT2LMHeadModel:
    def __init__(self, config: GPT2Config,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.remat = remat

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        L, H = cfg.n_layer, cfg.n_embd
        ks = iter(jax.random.split(key, 8))

        def w(k, shape, std=0.02, layers=True):
            full = (L, *shape) if layers else shape
            return (jax.random.normal(k, full, jnp.float32) * std).astype(self.param_dtype)

        zeros = lambda shape, layers=True: jnp.zeros((L, *shape) if layers else shape, self.param_dtype)
        ones = lambda shape, layers=True: jnp.ones((L, *shape) if layers else shape, self.param_dtype)
        # GPT-2 init: residual-path projections scaled by 1/sqrt(2*n_layer)
        resid_std = 0.02 / (2 * L) ** 0.5
        params = {
            "wte": {"embedding": w(next(ks), (cfg.vocab_size, H), layers=False)},
            "wpe": {"embedding": w(next(ks), (cfg.n_positions, H), 0.01, layers=False)},
            "h": {
                "ln_1": {"weight": ones((H,)), "bias": zeros((H,))},
                "attn": {
                    "c_attn": {"kernel": w(next(ks), (H, 3 * H)), "bias": zeros((3 * H,))},
                    "c_proj": {"kernel": w(next(ks), (H, H), resid_std), "bias": zeros((H,))},
                },
                "ln_2": {"weight": ones((H,)), "bias": zeros((H,))},
                "mlp": {
                    "c_fc": {"kernel": w(next(ks), (H, 4 * H)), "bias": zeros((4 * H,))},
                    "c_proj": {"kernel": w(next(ks), (4 * H, H), resid_std), "bias": zeros((H,))},
                },
            },
            "ln_f": {"weight": ones((H,), layers=False), "bias": zeros((H,), layers=False)},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": w(next(ks), (H, cfg.vocab_size), layers=False)}
        return params

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        """Logical axis names per param (see ``llama.LlamaForCausalLM.param_axes``)."""
        cfg = self.config
        axes: Dict[str, Any] = {
            "wte": {"embedding": ("vocab", "embed")},
            "wpe": {"embedding": ("pos", "embed")},
            "h": {
                "ln_1": {"weight": ("layers", "norm"), "bias": ("layers", "norm")},
                "attn": {
                    "c_attn": {"kernel": ("layers", "embed", "qkv3"),
                               "bias": ("layers", "qkv3")},
                    "c_proj": {"kernel": ("layers", "heads", "embed"),
                               "bias": ("layers", "norm")},
                },
                "ln_2": {"weight": ("layers", "norm"), "bias": ("layers", "norm")},
                "mlp": {
                    "c_fc": {"kernel": ("layers", "embed", "mlp"),
                             "bias": ("layers", "mlp")},
                    "c_proj": {"kernel": ("layers", "mlp", "embed"),
                               "bias": ("layers", "norm")},
                },
            },
            "ln_f": {"weight": ("norm",), "bias": ("norm",)},
        }
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = {"kernel": ("embed", "vocab")}
        return axes

    def _block(self, hidden, p, segment_ids, attention_mask):
        cfg = self.config
        B, S, H = hidden.shape
        nh = cfg.n_head
        cd = self.compute_dtype
        eps = cfg.layer_norm_epsilon

        x = layer_norm(hidden, p["ln_1"]["weight"], p["ln_1"]["bias"], eps)
        qkv = x @ p["attn"]["c_attn"]["kernel"].astype(cd) + p["attn"]["c_attn"]["bias"].astype(cd)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (B, S, nh, H // nh)
        attn = attention(
            q.reshape(shape), k.reshape(shape), v.reshape(shape),
            causal=True, segment_ids=segment_ids, attention_mask=attention_mask,
        ).reshape(B, S, H)
        attn = attn @ p["attn"]["c_proj"]["kernel"].astype(cd) + p["attn"]["c_proj"]["bias"].astype(cd)
        hidden = hidden + attn

        x = layer_norm(hidden, p["ln_2"]["weight"], p["ln_2"]["bias"], eps)
        x = jax.nn.gelu(x @ p["mlp"]["c_fc"]["kernel"].astype(cd) + p["mlp"]["c_fc"]["bias"].astype(cd))
        x = x @ p["mlp"]["c_proj"]["kernel"].astype(cd) + p["mlp"]["c_proj"]["bias"].astype(cd)
        return constrain(hidden + x, ("act_batch", "act_seq", "act_embed"))

    def __call__(self, params, input_ids, position_ids=None, segment_ids=None,
                 attention_mask=None, return_hidden: bool = False):
        cfg = self.config
        B, S = input_ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        hidden = (
            params["wte"]["embedding"][input_ids]
            + params["wpe"]["embedding"][position_ids]
        ).astype(self.compute_dtype)
        hidden = constrain(hidden, ("act_batch", "act_seq", "act_embed"))

        def layer(h, p, _, idx, cache):
            return self._block(h, p, segment_ids, attention_mask), None, None

        hidden, _, _ = scan_layers(hidden, [SubStack(params["h"], layer)],
                                   remat=self.remat, remat_policy=None)
        hidden = layer_norm(hidden, params["ln_f"]["weight"], params["ln_f"]["bias"],
                            cfg.layer_norm_epsilon)
        lm_kernel = (
            params["wte"]["embedding"].T
            if cfg.tie_word_embeddings
            else params["lm_head"]["kernel"]
        )
        if return_hidden:
            return {"hidden_states": hidden, "lm_head_kernel": lm_kernel}
        logits = hidden @ lm_kernel.astype(self.compute_dtype)
        return {"logits": constrain(logits, ("act_batch", "act_seq_nosp", "act_vocab"))}


def build_gpt2_model(**kwargs) -> GPT2LMHeadModel:
    """YAML builder (reference ``models/gpt2.py:198`` ``build_gpt2_model``)."""
    cfg_fields = {f.name for f in dataclasses.fields(GPT2Config)}
    cfg = GPT2Config(**{k: v for k, v in kwargs.items() if k in cfg_fields})
    extra = {k: v for k, v in kwargs.items() if k not in cfg_fields}
    return GPT2LMHeadModel(cfg, **extra)
