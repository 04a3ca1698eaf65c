"""Image-text-to-text model: vision tower + projector + language decoder.

TPU-native equivalent of what the reference loads through
``NeMoAutoModelForImageTextToText`` (``nemo_automodel/components/
_transformers/auto_model.py:415``; llava/Gemma3-VL architecture): SigLIP
vision tower (``automodel_tpu.models.vision``), a 2-layer multimodal
projector, and a Llama-family decoder.  Image features are scattered into
the token stream wherever ``input_ids == image_token_id`` — the HF
"image placeholder expansion" contract the VLM collators produce
(``datasets/vlm/collate_fns.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.models.vision import VisionConfig, VisionTower


def merge_image_embeds(embeds, input_ids, pixel_values, encode, token_id):
    """Scatter image features into placeholder token positions.

    ``pixel_values`` [B, I, H, W, C] (per-row image slots, the collator
    contract): each row's j-th placeholder run receives its OWN j-th image's
    patches — a per-row cumsum, so the batch dim stays dp-shardable and the
    per-host input pipeline needs no cross-host image coordination.  The
    legacy flat [B_img, H, W, C] layout (generation examples, hand-built
    batches) keeps the global row-major scatter; it is only valid unsharded.
    """
    B, S = input_ids.shape
    is_img = input_ids == token_id
    if pixel_values.ndim == 5:
        I = pixel_values.shape[1]
        img = encode(pixel_values.reshape((B * I,) + pixel_values.shape[2:]))
        img_rows = img.reshape(B, I * img.shape[1], -1)    # [B, I*P, Ht]
        idx = jnp.cumsum(is_img, axis=-1) - 1              # per-row
        idx = jnp.clip(idx, 0, img_rows.shape[1] - 1)
        gathered = jnp.take_along_axis(img_rows, idx[..., None], axis=1)
    else:
        img = encode(pixel_values)                         # [Bi, P, Ht]
        img_flat = img.reshape(-1, img.shape[-1])
        idx = jnp.clip(jnp.cumsum(is_img.reshape(-1)) - 1, 0,
                       img_flat.shape[0] - 1)
        gathered = img_flat[idx].reshape(B, S, -1)
    return jnp.where(is_img[..., None], gathered, embeds)


@dataclasses.dataclass
class VLMConfig:
    text_config: LlamaConfig = None
    vision_config: VisionConfig = None
    image_token_id: int = 257152          # Gemma3 <image_soft_token> default
    projector_hidden_act: str = "gelu"
    model_type: str = "llava"
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if isinstance(self.text_config, dict):
            self.text_config = LlamaConfig.from_hf_config(self.text_config)
        if isinstance(self.vision_config, dict):
            self.vision_config = VisionConfig.from_hf_config(self.vision_config)
        self.text_config = self.text_config or LlamaConfig()
        self.vision_config = self.vision_config or VisionConfig()

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "VLMConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in hf.items() if k in known}
        if "image_token_index" in hf:            # llava naming
            kwargs["image_token_id"] = hf["image_token_index"]
        return cls(**kwargs)


class VLMForConditionalGeneration:
    """``model._target_: automodel_tpu.models.vlm.build_vlm_model``"""

    def __init__(self, config: VLMConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.language_model = LlamaForCausalLM(
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
        proj = {
            "fc1": {"kernel": (jax.random.normal(kp, (Hv, Ht), jnp.float32)
                               * 0.02).astype(self.param_dtype),
                    "bias": jnp.zeros((Ht,), self.param_dtype)},
            "fc2": {"kernel": (jax.random.normal(
                jax.random.fold_in(kp, 1), (Ht, Ht), jnp.float32)
                * 0.02).astype(self.param_dtype),
                    "bias": jnp.zeros((Ht,), self.param_dtype)},
        }
        return {
            "language_model": self.language_model.init(kt),
            "vision_tower": self.vision_tower.init(kv),
            "multi_modal_projector": proj,
        }

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        return {
            "language_model": self.language_model.param_axes(),
            "vision_tower": self.vision_tower.param_axes(),
            "multi_modal_projector": {
                "fc1": {"kernel": ("norm", "embed"), "bias": ("norm",)},
                "fc2": {"kernel": ("embed", "norm"), "bias": ("norm",)},
            },
        }

    # -- forward -----------------------------------------------------------
    def encode_images(self, params, pixel_values: jnp.ndarray) -> jnp.ndarray:
        """[B_img, H, W, C] -> [B_img, n_patches, text_hidden]."""
        cd = self.compute_dtype
        feats = self.vision_tower(params["vision_tower"], pixel_values)
        p = params["multi_modal_projector"]
        x = feats @ p["fc1"]["kernel"].astype(cd) + p["fc1"]["bias"].astype(cd)
        x = jax.nn.gelu(x, approximate=True)
        return x @ p["fc2"]["kernel"].astype(cd) + p["fc2"]["bias"].astype(cd)

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        """Decode cache for the language decoder (generation path)."""
        return self.language_model.init_kv_cache(batch, max_len, dtype)

    def __call__(
        self,
        params: Dict[str, Any],
        input_ids: jnp.ndarray,                   # [B, S]
        pixel_values: Optional[jnp.ndarray] = None,   # [B*n_img, H, W, C]
        position_ids: Optional[jnp.ndarray] = None,
        segment_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        return_hidden: bool = False,
        kv_cache: Optional[Any] = None,
    ) -> Dict[str, jnp.ndarray]:
        lm = self.language_model
        lp = params["language_model"]
        B, S = input_ids.shape
        embeds = lp["embed_tokens"]["embedding"][input_ids].astype(
            self.compute_dtype)

        if pixel_values is not None:
            embeds = merge_image_embeds(
                embeds, input_ids, pixel_values,
                lambda pv: self.encode_images(params, pv),
                self.config.image_token_id)

        return lm.forward_embeds(
            lp, embeds,
            position_ids=position_ids, segment_ids=segment_ids,
            attention_mask=attention_mask, return_hidden=return_hidden,
            kv_cache=kv_cache)

    def flops_per_token(self) -> float:
        return self.language_model.flops_per_token()

    def flops_per_image(self) -> float:
        from automodel_tpu.models.vision import vision_flops_per_image

        return vision_flops_per_image(self.config.vision_config)


def build_vlm_model(config: Optional[dict] = None, **kwargs):
    if config is not None:
        if hasattr(config, "to_dict"):
            config = config.to_dict()
        cfg = VLMConfig.from_hf_config(config)
    else:
        cfg = VLMConfig()
    return VLMForConditionalGeneration(cfg, **kwargs)
