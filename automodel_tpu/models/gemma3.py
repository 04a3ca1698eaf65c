"""Gemma-3 text decoder — pure-JAX pytree model (scan over stacked layers).

What the reference loads from HF transformers through
``NeMoAutoModelForCausalLM`` for the Gemma family
(``nemo_automodel/components/_transformers/auto_model.py:169-414``), built
native like :mod:`automodel_tpu.models.llama` with the Gemma-3 specifics:

* embeddings scaled by ``sqrt(hidden_size)``;
* zero-centered RMSNorm applied as ``(1 + w)`` in fp32, with FOUR norms per
  layer (input / post-attention / pre-feedforward / post-feedforward) plus
  per-head q/k norms;
* GeGLU MLP (tanh-approx gelu on the gate);
* attention scale ``query_pre_attn_scalar ** -0.5``;
* alternating sliding-window / full-attention layers: the per-layer rope
  base rides the layer scan as data, and the attention call branches with
  ``lax.cond`` on a per-layer flag so each branch sees a STATIC window —
  sliding layers hit the splash kernel's LocalMask (off-window blocks
  skipped), full layers the plain causal kernel, still one scanned body.

HF round-trip parity is pinned by ``tests/unit_tests/test_gemma3_parity.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.layer_scan import (
    SubStack,
    default_position_ids,
    dense_kv_state,
    norm_and_head,
    scan_layers,
)
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.quant import maybe_qdot
from automodel_tpu.ops.rotary import apply_rope, rope_frequencies

@dataclasses.dataclass
class Gemma3Config:
    """HF ``Gemma3TextConfig`` field names."""

    vocab_size: int = 262144
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 26
    num_attention_heads: int = 8
    num_key_value_heads: int = 4
    head_dim: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    rope_local_base_freq: float = 10_000.0
    rope_scaling: Optional[dict] = None
    query_pre_attn_scalar: float = 256.0
    sliding_window: int = 4096
    layer_types: Optional[List[str]] = None   # "sliding_attention"/"full_attention"
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    model_type: str = "gemma3_text"
    torch_dtype: str = "bfloat16"
    # Gemma-2 deltas (Gemma-3 dropped softcapping and added q/k norms);
    # the shared decoder branches on these so one body serves both.
    qk_norm: bool = True
    attn_logit_softcapping: Optional[float] = None
    final_logit_softcapping: Optional[float] = None

    def __post_init__(self):
        if self.layer_types is None:
            # HF default: every 6th layer is full attention
            self.layer_types = [
                "full_attention" if (i + 1) % 6 == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers)]

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Gemma3Config":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


class Gemma3ForCausalLM:
    """Functional model: ``init`` builds the param pytree, ``__call__`` applies it."""

    def __init__(self, config: Gemma3Config,
                 param_dtype: jnp.dtype = jnp.float32,
                 compute_dtype: jnp.dtype = jnp.bfloat16,
                 remat: bool = True,
                 remat_policy: Optional[str] = "nothing_saveable"):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.remat = remat
        self.remat_policy = remat_policy
        self.quant = None
        # both bases precomputed; each layer selects by its type flag
        self.inv_freq_global = rope_frequencies(
            config.head_dim, config.rope_theta, config.rope_scaling)
        self.inv_freq_local = rope_frequencies(
            config.head_dim, config.rope_local_base_freq, None)

    # -- init --------------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        L, H, I = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        keys = iter(jax.random.split(key, 16))

        def dense(k, shape):
            return (jax.random.normal(k, (L, *shape), jnp.float32)
                    * 0.02).astype(self.param_dtype)

        # zero-centered norm weights: stored w, applied as (1 + w)
        zeros = lambda shape: jnp.zeros(shape, self.param_dtype)
        params: Dict[str, Any] = {
            "embed_tokens": {
                "embedding": (jax.random.normal(
                    next(keys), (cfg.vocab_size, H), jnp.float32)
                    * 0.02).astype(self.param_dtype)},
            "layers": {
                "input_layernorm": {"weight": zeros((L, H))},
                "self_attn": {
                    "q_proj": {"kernel": dense(next(keys), (H, Hq * D))},
                    "k_proj": {"kernel": dense(next(keys), (H, Hk * D))},
                    "v_proj": {"kernel": dense(next(keys), (H, Hk * D))},
                    "o_proj": {"kernel": dense(next(keys), (Hq * D, H))},
                },
                "post_attention_layernorm": {"weight": zeros((L, H))},
                "pre_feedforward_layernorm": {"weight": zeros((L, H))},
                "mlp": {
                    "gate_proj": {"kernel": dense(next(keys), (H, I))},
                    "up_proj": {"kernel": dense(next(keys), (H, I))},
                    "down_proj": {"kernel": dense(next(keys), (I, H))},
                },
                "post_feedforward_layernorm": {"weight": zeros((L, H))},
            },
            "norm": {"weight": zeros((H,))},
        }
        if cfg.qk_norm:
            params["layers"]["self_attn"]["q_norm"] = {
                "weight": zeros((L, D))}
            params["layers"]["self_attn"]["k_norm"] = {
                "weight": zeros((L, D))}
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": (jax.random.normal(
                next(keys), (H, cfg.vocab_size), jnp.float32)
                * 0.02).astype(self.param_dtype)}
        return params

    def abstract_params(self) -> Dict[str, Any]:
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        cfg = self.config
        axes: Dict[str, Any] = {
            "embed_tokens": {"embedding": ("vocab", "embed")},
            "layers": {
                "input_layernorm": {"weight": ("layers", "norm")},
                "self_attn": {
                    "q_proj": {"kernel": ("layers", "embed", "heads")},
                    "k_proj": {"kernel": ("layers", "embed", "heads")},
                    "v_proj": {"kernel": ("layers", "embed", "heads")},
                    "o_proj": {"kernel": ("layers", "heads", "embed")},
                },
                "post_attention_layernorm": {"weight": ("layers", "norm")},
                "pre_feedforward_layernorm": {"weight": ("layers", "norm")},
                "mlp": {
                    "gate_proj": {"kernel": ("layers", "embed", "mlp")},
                    "up_proj": {"kernel": ("layers", "embed", "mlp")},
                    "down_proj": {"kernel": ("layers", "mlp", "embed")},
                },
                "post_feedforward_layernorm": {"weight": ("layers", "norm")},
            },
            "norm": {"weight": ("norm",)},
        }
        if cfg.qk_norm:
            axes["layers"]["self_attn"]["q_norm"] = {
                "weight": ("layers", "head_dim")}
            axes["layers"]["self_attn"]["k_norm"] = {
                "weight": ("layers", "head_dim")}
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = {"kernel": ("embed", "vocab")}
        return axes

    # -- forward -----------------------------------------------------------
    def _layer(self, hidden, p, position_ids, segment_ids, attention_mask,
               inv_freq, is_full, kv_cache=None):
        cfg = self.config
        B, S, H = hidden.shape
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        cd = self.compute_dtype
        eps = cfg.rms_norm_eps

        def proj(x, w, name=""):
            # fp8/int8 quantized compute routes through maybe_qdot when
            # apply_fp8_to_model set self.quant (filter_fqns honored by name)
            return maybe_qdot(x, w["kernel"].astype(cd), self.quant, name)

        resid = hidden
        x = rms_norm(hidden, p["input_layernorm"]["weight"], eps, offset=1.0)
        q = proj(x, p["self_attn"]["q_proj"],
                 "self_attn.q_proj").reshape(B, S, Hq, D)
        k = proj(x, p["self_attn"]["k_proj"],
                 "self_attn.k_proj").reshape(B, S, Hk, D)
        v = proj(x, p["self_attn"]["v_proj"],
                 "self_attn.v_proj").reshape(B, S, Hk, D)
        if cfg.qk_norm:
            q = rms_norm(q, p["self_attn"]["q_norm"]["weight"], eps,
                         offset=1.0)
            k = rms_norm(k, p["self_attn"]["k_norm"]["weight"], eps,
                         offset=1.0)
        q, k = apply_rope(q, k, position_ids, inv_freq)
        scale = float(cfg.query_pre_attn_scalar) ** -0.5
        soft_cap = cfg.attn_logit_softcapping
        sliding = int(cfg.sliding_window)

        def by_window(fn, *operands, **kwargs):
            """``is_full`` is a traced per-layer flag; lax.cond gives each
            branch a STATIC window, so sliding layers hit the splash
            kernel's LocalMask (off-window blocks skipped) instead of a
            traced-window SDPA mask."""
            return lax.cond(
                is_full,
                lambda *ops: fn(*ops, **kwargs),
                lambda *ops: fn(*ops, local_window_size=sliding, **kwargs),
                *operands)

        state = None
        if kv_cache is not None:
            state = kv_cache.write(k, v)
            attn = by_window(kv_cache.attend, q, state, scale=scale,
                             logits_soft_cap=soft_cap)
        else:
            attn = by_window(
                attention, q, k, v, causal=True, scale=scale,
                logits_soft_cap=soft_cap,
                segment_ids=segment_ids, attention_mask=attention_mask)
        attn = proj(attn.reshape(B, S, Hq * D), p["self_attn"]["o_proj"],
                    "self_attn.o_proj")
        attn = rms_norm(attn, p["post_attention_layernorm"]["weight"], eps,
                        offset=1.0)
        hidden = resid + attn

        resid = hidden
        x = rms_norm(hidden, p["pre_feedforward_layernorm"]["weight"], eps,
                     offset=1.0)
        gate = proj(x, p["mlp"]["gate_proj"], "mlp.gate_proj")
        up = proj(x, p["mlp"]["up_proj"], "mlp.up_proj")
        down = proj(jax.nn.gelu(gate, approximate=True) * up,
                    p["mlp"]["down_proj"], "mlp.down_proj")
        down = rms_norm(down, p["post_feedforward_layernorm"]["weight"], eps,
                        offset=1.0)
        out = constrain(resid + down, ("act_batch", "act_seq", "act_embed"))
        return out, state

    def __call__(self, params, input_ids, position_ids=None, segment_ids=None,
                 attention_mask=None, return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        hidden = params["embed_tokens"]["embedding"][input_ids].astype(
            self.compute_dtype)
        # Gemma scales token embeddings by sqrt(H); image features scattered
        # in by the VLM are NOT scaled (HF order: scale, then scatter).
        hidden = hidden * jnp.asarray(
            float(cfg.hidden_size) ** 0.5, self.compute_dtype)
        return self.forward_embeds(
            params, hidden, position_ids=position_ids,
            segment_ids=segment_ids, attention_mask=attention_mask,
            return_hidden=return_hidden, kv_cache=kv_cache)

    def forward_embeds(self, params, hidden, position_ids=None,
                       segment_ids=None, attention_mask=None,
                       return_hidden: bool = False, kv_cache=None
                       ) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        B, S = hidden.shape[:2]
        if return_hidden and cfg.final_logit_softcapping is not None:
            # the fused hidden@lm_head loss path cannot apply the tanh
            # cap — training would silently diverge from HF semantics
            raise NotImplementedError(
                "final_logit_softcapping (Gemma-2) is incompatible with "
                "hidden-state losses (FusedLinearCrossEntropy): the cap "
                "must apply to the full logits; use a logits loss "
                "(e.g. MaskedCrossEntropy) for this family")
        if position_ids is None:
            position_ids = default_position_ids(kv_cache, B, S)
        hidden = constrain(hidden.astype(self.compute_dtype),
                           ("act_batch", "act_seq", "act_embed"))

        is_full = jnp.asarray(
            [t == "full_attention" for t in cfg.layer_types])
        inv_freqs = jnp.where(
            is_full[:, None], jnp.asarray(self.inv_freq_global)[None],
            jnp.asarray(self.inv_freq_local)[None])       # [L, D/2]

        def layer(h, layer_params, xs, idx, cache):
            inv_freq, full_flag = xs
            h, state = self._layer(h, layer_params, position_ids,
                                   segment_ids, attention_mask, inv_freq,
                                   full_flag, kv_cache=cache)
            return h, state, None

        hidden, cache_state, _ = scan_layers(
            hidden, [SubStack(params["layers"], layer, (inv_freqs, is_full))],
            kv_cache, remat=self.remat, remat_policy=self.remat_policy)

        out = norm_and_head(
            hidden, params,
            lambda h, p: rms_norm(h, p["weight"], cfg.rms_norm_eps,
                                  offset=1.0),
            tied=cfg.tie_word_embeddings, compute_dtype=self.compute_dtype,
            return_hidden=return_hidden)
        if not return_hidden and cfg.final_logit_softcapping is not None:
            cap = jnp.asarray(cfg.final_logit_softcapping, jnp.float32)
            logits = out["logits"]
            out["logits"] = (jnp.tanh(logits.astype(jnp.float32) / cap)
                             * cap).astype(logits.dtype)
        if kv_cache is not None:
            out["kv_cache"] = cache_state
        return out

    def init_kv_cache(self, batch: int, max_len: int,
                      dtype: Optional[Any] = None) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        return dense_kv_state(
            cfg.num_hidden_layers, batch, max_len,
            (cfg.num_key_value_heads, cfg.head_dim),
            dtype or self.compute_dtype)

    @property
    def num_params(self) -> int:
        return sum(int(jnp.prod(jnp.array(x.shape)))
                   for x in jax.tree.leaves(self.abstract_params()))

    def flops_per_token(self) -> float:
        return _gemma3_flops_per_token(self.config)


@dataclasses.dataclass
class Gemma3VLConfig:
    """HF multimodal ``Gemma3Config`` (model_type "gemma3")."""

    text_config: Any = None
    vision_config: Any = None
    mm_tokens_per_image: int = 256
    image_token_index: int = 262144
    boi_token_index: int = 255999
    eoi_token_index: int = 256000
    model_type: str = "gemma3"
    tie_word_embeddings: bool = True
    torch_dtype: str = "bfloat16"

    def __post_init__(self):
        from automodel_tpu.models.vision import VisionConfig

        if isinstance(self.text_config, dict):
            self.text_config = Gemma3Config.from_hf_config(self.text_config)
        if isinstance(self.vision_config, dict):
            self.vision_config = VisionConfig.from_hf_config(self.vision_config)
        self.text_config = self.text_config or Gemma3Config()
        self.vision_config = self.vision_config or VisionConfig()

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Gemma3VLConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


class Gemma3ForConditionalGeneration:
    """Gemma-3 multimodal: SigLIP tower -> avg-pool + soft-emb-norm
    projector -> Gemma-3 decoder (HF ``Gemma3ForConditionalGeneration``;
    the BASELINE.md VLM benchmark model family)."""

    def __init__(self, config: Gemma3VLConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True):
        from automodel_tpu.models.vision import VisionTower

        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.language_model = Gemma3ForCausalLM(
            config.text_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat)
        self.vision_tower = VisionTower(
            config.vision_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, Any]:
        kt, kv, kp = jax.random.split(key, 3)
        Hv = self.config.vision_config.hidden_size
        Ht = self.config.text_config.hidden_size
        return {
            "language_model": self.language_model.init(kt),
            "vision_tower": self.vision_tower.init(kv),
            "multi_modal_projector": {
                # HF stores the projection as (Hv, Ht) used as x @ W — our
                # layout exactly, no transpose
                "mm_input_projection_weight": (
                    jax.random.normal(kp, (Hv, Ht), jnp.float32) * 0.02
                ).astype(self.param_dtype),
                "mm_soft_emb_norm": {
                    "weight": jnp.zeros((Hv,), self.param_dtype)},
            },
        }

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        return {
            "language_model": self.language_model.param_axes(),
            "vision_tower": self.vision_tower.param_axes(),
            "multi_modal_projector": {
                "mm_input_projection_weight": ("norm", "embed"),
                "mm_soft_emb_norm": {"weight": ("norm",)},
            },
        }

    # -- forward -----------------------------------------------------------
    def encode_images(self, params, pixel_values: jnp.ndarray) -> jnp.ndarray:
        """[B_img, H, W, C] -> [B_img, mm_tokens_per_image, text_hidden]."""
        cfg = self.config
        cd = self.compute_dtype
        feats = self.vision_tower(params["vision_tower"], pixel_values)
        B, P, Hv = feats.shape
        side = cfg.vision_config.image_size // cfg.vision_config.patch_size
        tokens_side = int(round(cfg.mm_tokens_per_image ** 0.5))
        pool = side // tokens_side
        # avg_pool2d(kernel=stride=pool) as a reshape-mean
        x = feats.reshape(B, tokens_side, pool, tokens_side, pool, Hv)
        x = x.mean(axis=(2, 4)).reshape(B, tokens_side * tokens_side, Hv)
        x = rms_norm(x, params["multi_modal_projector"]
                     ["mm_soft_emb_norm"]["weight"],
                     cfg.text_config.rms_norm_eps, offset=1.0)
        proj = params["multi_modal_projector"][
            "mm_input_projection_weight"].astype(cd)
        return x.astype(cd) @ proj

    def __call__(self, params, input_ids, pixel_values=None,
                 position_ids=None, segment_ids=None, attention_mask=None,
                 return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        lm, lp = self.language_model, params["language_model"]
        B, S = input_ids.shape
        embeds = lp["embed_tokens"]["embedding"][input_ids].astype(
            self.compute_dtype)
        embeds = embeds * jnp.asarray(
            float(cfg.text_config.hidden_size) ** 0.5, self.compute_dtype)

        if pixel_values is not None:
            # HF order: scale token embeds, then overwrite image positions
            # with the (unscaled) projected image features
            from automodel_tpu.models.vlm import merge_image_embeds

            embeds = merge_image_embeds(
                embeds, input_ids, pixel_values,
                lambda pv: self.encode_images(params, pv),
                cfg.image_token_index)

        return lm.forward_embeds(
            lp, embeds, position_ids=position_ids, segment_ids=segment_ids,
            attention_mask=attention_mask, return_hidden=return_hidden,
            kv_cache=kv_cache)

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        return self.language_model.init_kv_cache(batch, max_len, dtype)

    def flops_per_token(self) -> float:
        return self.language_model.flops_per_token()

    def flops_per_image(self) -> float:
        """Vision-tower FLOPs per image (for MFU accounting: step FLOPs =
        text_tokens * flops_per_token + n_images * flops_per_image)."""
        from automodel_tpu.models.vision import vision_flops_per_image

        return vision_flops_per_image(self.config.vision_config)


def _gemma3_flops_per_token(cfg: Gemma3Config) -> float:
    per_layer = (
        2 * cfg.hidden_size * (cfg.num_attention_heads
                               + 2 * cfg.num_key_value_heads) * cfg.head_dim
        + 2 * cfg.num_attention_heads * cfg.head_dim * cfg.hidden_size
        + 6 * cfg.hidden_size * cfg.intermediate_size
    )
    embed = 2 * cfg.vocab_size * cfg.hidden_size
    return 3.0 * (cfg.num_hidden_layers * per_layer + embed)
