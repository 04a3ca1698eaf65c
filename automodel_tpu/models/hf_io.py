"""HF safetensors <-> pytree weight round-trip.

TPU re-design of the reference's parallel HF weight load
(``nemo_automodel/components/checkpoint/checkpointing.py:176-237``) and the
DCP safetensors storage layer (``checkpoint/_backports/hf_storage.py:67-393``):

* **Load**: each param is materialized with ``jax.make_array_from_callback``
  against lazily-opened safetensors files — every host/device reads only the
  byte ranges of its own shards, so 70B checkpoints stream straight into
  sharded device arrays with no host-RAM blowup (the meta-device-init
  equivalent).
* **Save**: the inverse mapping writes standard HF ``model-xxxxx-of-xxxxx
  .safetensors`` shards plus ``model.safetensors.index.json`` — a consolidated
  HF repo a reference user can load back with ``AutoModelForCausalLM``.

Key maps translate between HF names (``model.layers.{i}.self_attn.q_proj
.weight``, torch ``(out, in)`` layout) and our stacked pytree
(``layers/self_attn/q_proj/kernel``, ``(L, in, out)``).
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SAFETENSORS_INDEX = "model.safetensors.index.json"


# ---------------------------------------------------------------------------
# Key maps.  Entry: tree path (tuple of str) -> HfSpec
# ---------------------------------------------------------------------------
class HfSpec:
    """How one pytree param maps onto HF tensors.

    ``template`` contains ``{i}`` when the param is a stack over layers, plus
    ``{e}`` when additionally stacked over experts (``expert_stacked``, MoE:
    our ``[L, E, ...]`` tree leaf maps onto L x E per-expert HF tensors).
    ``transpose``: HF stores torch Linear as (out, in); our kernel is (in, out).
    ``load_transform``/``save_transform``: arbitrary layout changes (e.g. a
    conv patch-embed kernel (out, C, p, p) <-> our patch matmul (p*p*C, out)).
    A transform defeats byte-range slicing, so the full HF tensor is read and
    transformed before the requested slice is taken — only use it for params
    small enough to materialize on every host.
    """

    def __init__(self, template: str, stacked: bool = False,
                 transpose: bool = False,
                 expert_stacked: bool = False,
                 load_transform: Optional[Callable] = None,
                 save_transform: Optional[Callable] = None,
                 column_transform: Optional[Callable] = None,
                 missing_init: Optional[Callable] = None,
                 layer_offset: int = 0):
        self.template = template
        self.stacked = stacked
        self.expert_stacked = expert_stacked
        self.transpose = transpose
        # Stack position 0 maps to HF layer index ``layer_offset`` — for
        # families whose layer stack is split into heterogeneous sub-stacks
        # (DeepSeek first_k_dense_replace: dense layers [0, k), MoE [k, L)).
        self.layer_offset = layer_offset
        self.load_transform = load_transform
        self.save_transform = save_transform
        # Column-local load transform for 2-D torch-Linear tensors: receives
        # OUR layout (in_full, out_slice) — only the out columns of the
        # requested slice are read (full contraction dim), so per-shard reads
        # stay byte-ranged (a plain load_transform re-reads the whole tensor
        # per shard).  The result's rows are then sliced by the request.
        # Use for per-out-channel transforms (streaming int8 quantization).
        self.column_transform = column_transform
        # (shape, dtype) -> np.ndarray used when the checkpoint lacks the
        # tensor: heads a base checkpoint does not carry (e.g. ``score.weight``
        # when fine-tuning a classifier from a causal-LM base — HF
        # random-inits missing heads the same way).
        self.missing_init = missing_init


def llama_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    m: Dict[Tuple[str, ...], HfSpec] = {
        ("embed_tokens", "embedding"): HfSpec("model.embed_tokens.weight"),
        ("norm", "weight"): HfSpec("model.norm.weight"),
        ("layers", "input_layernorm", "weight"): HfSpec(
            "model.layers.{i}.input_layernorm.weight", stacked=True),
        ("layers", "post_attention_layernorm", "weight"): HfSpec(
            "model.layers.{i}.post_attention_layernorm.weight", stacked=True),
    }
    for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
        m[("layers", "self_attn", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.self_attn.{proj}.weight", stacked=True, transpose=True)
    if config.attention_bias:
        for proj in ("q_proj", "k_proj", "v_proj"):
            m[("layers", "self_attn", proj, "bias")] = HfSpec(
                f"model.layers.{{i}}.self_attn.{proj}.bias", stacked=True)
    if config.qk_norm:
        m[("layers", "self_attn", "q_norm", "weight")] = HfSpec(
            "model.layers.{i}.self_attn.q_norm.weight", stacked=True)
        m[("layers", "self_attn", "k_norm", "weight")] = HfSpec(
            "model.layers.{i}.self_attn.k_norm.weight", stacked=True)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        m[("layers", "mlp", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.mlp.{proj}.weight", stacked=True, transpose=True)
    if not config.tie_word_embeddings:
        m[("lm_head", "kernel")] = HfSpec("lm_head.weight", transpose=True)
    return m


def brumby_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Brumby (``model_type: brumby``): the Qwen3 names plus the retention
    gate ``self_attn.g_proj`` (weight and bias, one logit a kv head)."""
    m = llama_key_map(config)
    m[("layers", "self_attn", "g_proj", "kernel")] = HfSpec(
        "model.layers.{i}.self_attn.g_proj.weight", stacked=True,
        transpose=True)
    m[("layers", "self_attn", "g_proj", "bias")] = HfSpec(
        "model.layers.{i}.self_attn.g_proj.bias", stacked=True)
    return m


def mixtral_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Mixtral (HF ``MixtralForCausalLM`` naming): Llama attention plus
    ``block_sparse_moe.gate`` and per-expert ``experts.{e}.w1/w2/w3``."""
    m = llama_key_map(config)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        del m[("layers", "mlp", proj, "kernel")]
    m[("layers", "block_sparse_moe", "gate", "kernel")] = HfSpec(
        "model.layers.{i}.block_sparse_moe.gate.weight", stacked=True,
        transpose=True)
    for w in ("w1", "w2", "w3"):
        m[("layers", "block_sparse_moe", "experts", w, "kernel")] = HfSpec(
            f"model.layers.{{i}}.block_sparse_moe.experts.{{e}}.{w}.weight",
            stacked=True, expert_stacked=True, transpose=True)
    return m


def qwen3_moe_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Qwen3-MoE (HF ``Qwen3MoeForCausalLM`` naming): Qwen3 attention
    (q/k norms via the llama map) plus ``mlp.gate`` router and per-expert
    ``mlp.experts.{e}.gate_proj/up_proj/down_proj``."""
    m = llama_key_map(config)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        del m[("layers", "mlp", proj, "kernel")]
    m[("layers", "mlp", "gate", "kernel")] = HfSpec(
        "model.layers.{i}.mlp.gate.weight", stacked=True, transpose=True)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        m[("layers", "mlp", "experts", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.mlp.experts.{{e}}.{proj}.weight",
            stacked=True, expert_stacked=True, transpose=True)
    return m


def smallthinker_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """SmallThinker (``model_type: smallthinker``): Llama attention names
    plus ``block_sparse_moe.primary_router`` and per-expert
    ``block_sparse_moe.experts.{e}.gate/up/down``.  The names follow the
    published ``modeling_smallthinker.py`` AS REMEMBERED (no checkpoint
    could be read where this was written): check them against a
    checkpoint's index before the first load."""
    m = llama_key_map(config)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        del m[("layers", "mlp", proj, "kernel")]
    moe = "model.layers.{i}.block_sparse_moe."
    m[("layers", "block_sparse_moe", "primary_router", "kernel")] = HfSpec(
        moe + "primary_router.weight", stacked=True, transpose=True)
    for w in ("gate", "up", "down"):
        m[("layers", "block_sparse_moe", "experts", w, "kernel")] = HfSpec(
            moe + f"experts.{{e}}.{w}.weight", stacked=True,
            expert_stacked=True, transpose=True)
    return m


def deepseek_v2_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """DeepSeek-V2: the V3 map without the correction-bias tensor (the V2
    softmax gate has none)."""
    m = deepseek_v3_key_map(config)
    m.pop(("layers", "mlp", "gate", "e_score_correction_bias"), None)
    return m


def olmo2_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """OLMo-2 (HF ``Olmo2ForCausalLM``): llama projections, post-norm
    layout (post_attention + post_feedforward norms), full-width q/k
    norms."""
    m = llama_key_map(config)
    del m[("layers", "input_layernorm", "weight")]
    m[("layers", "post_feedforward_layernorm", "weight")] = HfSpec(
        "model.layers.{i}.post_feedforward_layernorm.weight", stacked=True)
    for norm in ("q_norm", "k_norm"):
        m[("layers", "self_attn", norm, "weight")] = HfSpec(
            f"model.layers.{{i}}.self_attn.{norm}.weight", stacked=True)
    return m


def starcoder2_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """StarCoder-2 (HF ``Starcoder2ForCausalLM``): llama attention with
    biases everywhere, LayerNorm (+bias) blocks, c_fc/c_proj GELU MLP."""
    m = llama_key_map(config)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        del m[("layers", "mlp", proj, "kernel")]
    for proj in ("c_fc", "c_proj"):
        m[("layers", "mlp", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.mlp.{proj}.weight", stacked=True,
            transpose=True)
        if config.use_bias:
            m[("layers", "mlp", proj, "bias")] = HfSpec(
                f"model.layers.{{i}}.mlp.{proj}.bias", stacked=True)
    if config.use_bias:
        m[("layers", "self_attn", "o_proj", "bias")] = HfSpec(
            "model.layers.{i}.self_attn.o_proj.bias", stacked=True)
    for norm in ("input_layernorm", "post_attention_layernorm"):
        m[("layers", norm, "bias")] = HfSpec(
            f"model.layers.{{i}}.{norm}.bias", stacked=True)
    m[("norm", "bias")] = HfSpec("model.norm.bias")
    return m


def deepseek_v3_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """DeepSeek-V2/V3 (HF ``DeepseekV3ForCausalLM`` naming): MLA attention
    projections plus the split dense/MoE layer stacks.  HF layer ``i`` maps
    to ``dense_layers[i]`` for ``i < first_k_dense_replace`` and to
    ``layers[i - first_k_dense_replace]`` after (``layer_offset``)."""
    kd = config.first_k_dense_replace
    n_moe = config.num_hidden_layers - kd
    m: Dict[Tuple[str, ...], HfSpec] = {
        ("embed_tokens", "embedding"): HfSpec("model.embed_tokens.weight"),
        ("norm", "weight"): HfSpec("model.norm.weight"),
    }
    if not config.tie_word_embeddings:
        m[("lm_head", "kernel")] = HfSpec("lm_head.weight", transpose=True)

    def attn_and_norms(stack: str, off: int):
        for norm in ("input_layernorm", "post_attention_layernorm"):
            m[(stack, norm, "weight")] = HfSpec(
                f"model.layers.{{i}}.{norm}.weight", stacked=True,
                layer_offset=off)
        projs = (("q_proj",) if config.q_lora_rank is None
                 else ("q_a_proj", "q_b_proj"))
        for proj in projs + ("kv_a_proj_with_mqa", "kv_b_proj", "o_proj"):
            m[(stack, "self_attn", proj, "kernel")] = HfSpec(
                f"model.layers.{{i}}.self_attn.{proj}.weight", stacked=True,
                transpose=True, layer_offset=off)
        norms = (("kv_a_layernorm",) if config.q_lora_rank is None
                 else ("q_a_layernorm", "kv_a_layernorm"))
        for norm in norms:
            m[(stack, "self_attn", norm, "weight")] = HfSpec(
                f"model.layers.{{i}}.self_attn.{norm}.weight", stacked=True,
                layer_offset=off)

    if kd:
        attn_and_norms("dense_layers", 0)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            m[("dense_layers", "mlp", proj, "kernel")] = HfSpec(
                f"model.layers.{{i}}.mlp.{proj}.weight", stacked=True,
                transpose=True)
    if n_moe:
        attn_and_norms("layers", kd)
        m[("layers", "mlp", "gate", "kernel")] = HfSpec(
            "model.layers.{i}.mlp.gate.weight", stacked=True, transpose=True,
            layer_offset=kd)
        m[("layers", "mlp", "gate", "e_score_correction_bias")] = HfSpec(
            "model.layers.{i}.mlp.gate.e_score_correction_bias", stacked=True,
            layer_offset=kd,
            missing_init=lambda shape, dtype: np.zeros(shape, dtype))
        for proj in ("gate_proj", "up_proj", "down_proj"):
            m[("layers", "mlp", "experts", proj, "kernel")] = HfSpec(
                f"model.layers.{{i}}.mlp.experts.{{e}}.{proj}.weight",
                stacked=True, expert_stacked=True, transpose=True,
                layer_offset=kd)
            m[("layers", "mlp", "shared_experts", proj, "kernel")] = HfSpec(
                f"model.layers.{{i}}.mlp.shared_experts.{proj}.weight",
                stacked=True, transpose=True, layer_offset=kd)
    return m


def gemma3_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Gemma-3 text (HF ``Gemma3ForCausalLM`` naming — llama-like plus q/k
    norms and pre/post feedforward norms)."""
    m: Dict[Tuple[str, ...], HfSpec] = {
        ("embed_tokens", "embedding"): HfSpec("model.embed_tokens.weight"),
        ("norm", "weight"): HfSpec("model.norm.weight"),
    }
    for norm in ("input_layernorm", "post_attention_layernorm",
                 "pre_feedforward_layernorm", "post_feedforward_layernorm"):
        m[("layers", norm, "weight")] = HfSpec(
            f"model.layers.{{i}}.{norm}.weight", stacked=True)
    for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
        m[("layers", "self_attn", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.self_attn.{proj}.weight", stacked=True,
            transpose=True)
    if getattr(config, "qk_norm", True):   # Gemma-2 has no q/k norms
        for norm in ("q_norm", "k_norm"):
            m[("layers", "self_attn", norm, "weight")] = HfSpec(
                f"model.layers.{{i}}.self_attn.{norm}.weight", stacked=True)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        m[("layers", "mlp", proj, "kernel")] = HfSpec(
            f"model.layers.{{i}}.mlp.{proj}.weight", stacked=True,
            transpose=True)
    if not config.tie_word_embeddings:
        m[("lm_head", "kernel")] = HfSpec("lm_head.weight", transpose=True)
    return m


def gemma3n_text_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Gemma-3n text (HF ``Gemma3nForCausalLM`` naming): the Gemma-3 layer
    set (shared via :func:`gemma3_key_map`) plus AltUp / Laurel /
    per-layer-embedding tensors."""
    m = gemma3_key_map(config)
    m.pop(("lm_head", "kernel"), None)    # gemma3n is always tied
    m.update({
        ("embed_tokens_per_layer", "embedding"): HfSpec(
            "model.embed_tokens_per_layer.weight"),
        ("per_layer_model_projection", "kernel"): HfSpec(
            "model.per_layer_model_projection.weight", transpose=True),
        ("per_layer_projection_norm", "weight"): HfSpec(
            "model.per_layer_projection_norm.weight"),
        ("altup_projections", "kernel"): HfSpec(
            "model.altup_projections.{i}.weight", stacked=True,
            transpose=True),
        ("altup_unembed_projections", "kernel"): HfSpec(
            "model.altup_unembed_projections.{i}.weight", stacked=True,
            transpose=True),
    })
    m[("layers", "altup", "correct_output_scale")] = HfSpec(
        "model.layers.{i}.altup.correct_output_scale", stacked=True)
    for lin in ("correction_coefs", "prediction_coefs", "modality_router"):
        m[("layers", "altup", lin, "kernel")] = HfSpec(
            f"model.layers.{{i}}.altup.{lin}.weight", stacked=True,
            transpose=True)
    m[("layers", "altup", "router_norm", "weight")] = HfSpec(
        "model.layers.{i}.altup.router_norm.weight", stacked=True)
    for lin in ("linear_left", "linear_right"):
        m[("layers", "laurel", lin, "kernel")] = HfSpec(
            f"model.layers.{{i}}.laurel.{lin}.weight", stacked=True,
            transpose=True)
    m[("layers", "laurel", "post_laurel_norm", "weight")] = HfSpec(
        "model.layers.{i}.laurel.post_laurel_norm.weight", stacked=True)
    for lin in ("per_layer_input_gate", "per_layer_projection"):
        m[("layers", lin, "kernel")] = HfSpec(
            f"model.layers.{{i}}.{lin}.weight", stacked=True, transpose=True)
    m[("layers", "post_per_layer_input_norm", "weight")] = HfSpec(
        "model.layers.{i}.post_per_layer_input_norm.weight", stacked=True)
    return m


def gemma3n_vlm_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Gemma-3n multimodal (HF ``Gemma3nForConditionalGeneration`` naming):
    text under ``model.language_model.``, the multimodal embedder under
    ``model.embed_vision.``; the NATIVE vision tower has no timm
    counterpart, so its weights live under ``model.vision_tower.native.*``
    (HF loaders warn + random-init their timm tower — Phi-4-MM precedent)."""
    text = {
        ("language_model",) + path: HfSpec(
            spec.template.replace("model.", "model.language_model.", 1),
            stacked=spec.stacked, transpose=spec.transpose)
        for path, spec in gemma3n_text_key_map(config.text_config).items()
    }
    ev = "model.embed_vision."
    m: Dict[Tuple[str, ...], HfSpec] = dict(text)
    m[("embed_vision", "embedding", "embedding")] = HfSpec(
        ev + "embedding.weight")
    m[("embed_vision", "hard_embedding_norm", "weight")] = HfSpec(
        ev + "hard_embedding_norm.weight")
    m[("embed_vision", "soft_embedding_norm", "weight")] = HfSpec(
        ev + "soft_embedding_norm.weight")
    m[("embed_vision", "embedding_projection", "kernel")] = HfSpec(
        ev + "embedding_projection.weight", transpose=True)
    vt = "model.vision_tower.native."
    m[("vision_tower", "stem", "kernel")] = HfSpec(vt + "stem.kernel")
    for name in ("expand", "depthwise", "project"):
        m[("vision_tower", "blocks", name, "kernel")] = HfSpec(
            vt + f"blocks.{name}.kernel")
    m[("vision_tower", "blocks", "norm", "weight")] = HfSpec(
        vt + "blocks.norm.weight")
    m[("vision_tower", "head", "kernel")] = HfSpec(vt + "head.kernel")
    return m


def gpt2_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    # HF GPT-2 uses Conv1D: weights already (in, out) — no transpose.
    m: Dict[Tuple[str, ...], HfSpec] = {
        ("wte", "embedding"): HfSpec("wte.weight"),
        ("wpe", "embedding"): HfSpec("wpe.weight"),
        ("ln_f", "weight"): HfSpec("ln_f.weight"),
        ("ln_f", "bias"): HfSpec("ln_f.bias"),
    }
    if not config.tie_word_embeddings:
        m[("lm_head", "kernel")] = HfSpec("lm_head.weight", transpose=True)
    for ln in ("ln_1", "ln_2"):
        for wb in ("weight", "bias"):
            m[("h", ln, wb)] = HfSpec(f"h.{{i}}.{ln}.{wb}", stacked=True)
    for mod, sub in (("attn", "c_attn"), ("attn", "c_proj"),
                     ("mlp", "c_fc"), ("mlp", "c_proj")):
        m[("h", mod, sub, "kernel")] = HfSpec(f"h.{{i}}.{mod}.{sub}.weight", stacked=True)
        m[("h", mod, sub, "bias")] = HfSpec(f"h.{{i}}.{mod}.{sub}.bias", stacked=True)
    return m


def vision_key_map(config, prefix: str = "vision_tower.vision_model."
                   ) -> Dict[Tuple[str, ...], HfSpec]:
    """SigLIP-family vision tower (HF ``SiglipVisionModel`` naming, the tower
    Gemma3/PaliGemma VLMs carry; reference loads it through
    ``NeMoAutoModelForImageTextToText``, ``_transformers/auto_model.py:415``)."""
    p, C, H = config.patch_size, config.num_channels, config.hidden_size

    def conv_to_matmul(w: np.ndarray) -> np.ndarray:
        # (H_out, C, p, p) conv kernel -> (p*p*C, H_out) patch matmul, patch
        # vector laid out (row, col, channel) to match VisionTower.patchify.
        return np.ascontiguousarray(
            w.transpose(2, 3, 1, 0).reshape(p * p * C, w.shape[0]))

    def matmul_to_conv(w: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(
            w.reshape(p, p, C, w.shape[-1]).transpose(3, 2, 0, 1))

    m: Dict[Tuple[str, ...], HfSpec] = {
        ("patch_embed", "kernel"): HfSpec(
            prefix + "embeddings.patch_embedding.weight",
            load_transform=conv_to_matmul, save_transform=matmul_to_conv),
        ("patch_embed", "bias"): HfSpec(
            prefix + "embeddings.patch_embedding.bias"),
        ("pos_embed", "embedding"): HfSpec(
            prefix + "embeddings.position_embedding.weight"),
        ("post_ln", "weight"): HfSpec(prefix + "post_layernorm.weight"),
        ("post_ln", "bias"): HfSpec(prefix + "post_layernorm.bias"),
    }
    layer = prefix + "encoder.layers.{i}."
    for ours, hf in (("ln_1", "layer_norm1"), ("ln_2", "layer_norm2")):
        for wb in ("weight", "bias"):
            m[("layers", ours, wb)] = HfSpec(
                layer + f"{hf}.{wb}", stacked=True)
    for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
        m[("layers", "attn", proj, "kernel")] = HfSpec(
            layer + f"self_attn.{proj}.weight", stacked=True, transpose=True)
        m[("layers", "attn", proj, "bias")] = HfSpec(
            layer + f"self_attn.{proj}.bias", stacked=True)
    for fc in ("fc1", "fc2"):
        m[("layers", "mlp", fc, "kernel")] = HfSpec(
            layer + f"mlp.{fc}.weight", stacked=True, transpose=True)
        m[("layers", "mlp", fc, "bias")] = HfSpec(
            layer + f"mlp.{fc}.bias", stacked=True)
    return m


def vlm_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Image-text-to-text model (llava-style HF naming: ``language_model.*``,
    ``vision_tower.vision_model.*``, ``multi_modal_projector.linear_{1,2}``)."""
    m: Dict[Tuple[str, ...], HfSpec] = {}
    for path, spec in llama_key_map(config.text_config).items():
        m[("language_model",) + path] = HfSpec(
            "language_model." + spec.template, stacked=spec.stacked,
            transpose=spec.transpose)
    for path, spec in vision_key_map(config.vision_config).items():
        m[("vision_tower",) + path] = spec
    for ours, hf in (("fc1", "linear_1"), ("fc2", "linear_2")):
        m[("multi_modal_projector", ours, "kernel")] = HfSpec(
            f"multi_modal_projector.{hf}.weight", transpose=True)
        m[("multi_modal_projector", ours, "bias")] = HfSpec(
            f"multi_modal_projector.{hf}.bias")
    return m


def gemma3_vlm_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Gemma-3 multimodal (HF ``Gemma3ForConditionalGeneration`` naming:
    ``model.language_model.*``, ``model.vision_tower.vision_model.*``,
    ``model.multi_modal_projector.mm_*``)."""
    m: Dict[Tuple[str, ...], HfSpec] = {}
    for path, spec in gemma3_key_map(config.text_config).items():
        # text templates are "model.layers..." / "model.norm..." etc.
        tpl = spec.template.replace("model.", "model.language_model.", 1)
        m[("language_model",) + path] = HfSpec(
            tpl, stacked=spec.stacked, transpose=spec.transpose)
    for path, spec in vision_key_map(
            config.vision_config,
            prefix="model.vision_tower.vision_model.").items():
        m[("vision_tower",) + path] = spec
    m[("multi_modal_projector", "mm_input_projection_weight")] = HfSpec(
        "model.multi_modal_projector.mm_input_projection_weight")
    m[("multi_modal_projector", "mm_soft_emb_norm", "weight")] = HfSpec(
        "model.multi_modal_projector.mm_soft_emb_norm.weight")
    return m


def qwen2_5_vl_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Qwen2.5-VL (HF ``Qwen2_5_VLForConditionalGeneration``): text under
    ``model.language_model.``, windowed ViT under ``model.visual.``; the
    conv3d patch embed (out, C, tps, ps, ps) flattens to our patch matmul
    (C*tps*ps*ps, out)."""
    m: Dict[Tuple[str, ...], HfSpec] = {}
    for path, spec in llama_key_map(config.text_config).items():
        t = spec.template
        if t.startswith("model."):
            t = "model.language_model." + t[len("model."):]
        m[("language_model",) + path] = HfSpec(
            t, stacked=spec.stacked, transpose=spec.transpose)

    def conv_to_matmul(w: np.ndarray) -> np.ndarray:
        return w.reshape(w.shape[0], -1).T          # (out, pdim) -> (pdim, out)

    def matmul_to_conv(w: np.ndarray) -> np.ndarray:
        vc = config.vision_config
        return w.T.reshape(-1, vc.in_channels, vc.temporal_patch_size,
                           vc.patch_size, vc.patch_size)

    m[("visual", "patch_embed", "kernel")] = HfSpec(
        "model.visual.patch_embed.proj.weight",
        load_transform=conv_to_matmul, save_transform=matmul_to_conv)
    pre = "model.visual.blocks.{i}."
    m[("visual", "blocks", "norm1", "weight")] = HfSpec(
        pre + "norm1.weight", stacked=True)
    m[("visual", "blocks", "norm2", "weight")] = HfSpec(
        pre + "norm2.weight", stacked=True)
    for mod, name in (("qkv", "attn.qkv"), ("proj", "attn.proj")):
        m[("visual", "blocks", "attn", mod, "kernel")] = HfSpec(
            pre + name + ".weight", stacked=True, transpose=True)
        m[("visual", "blocks", "attn", mod, "bias")] = HfSpec(
            pre + name + ".bias", stacked=True)
    for proj in ("gate_proj", "up_proj", "down_proj"):
        m[("visual", "blocks", "mlp", proj, "kernel")] = HfSpec(
            pre + f"mlp.{proj}.weight", stacked=True, transpose=True)
        m[("visual", "blocks", "mlp", proj, "bias")] = HfSpec(
            pre + f"mlp.{proj}.bias", stacked=True)
    m[("visual", "merger", "ln_q", "weight")] = HfSpec(
        "model.visual.merger.ln_q.weight")
    for ours, theirs in (("fc1", "mlp.0"), ("fc2", "mlp.2")):
        m[("visual", "merger", ours, "kernel")] = HfSpec(
            f"model.visual.merger.{theirs}.weight", transpose=True)
        m[("visual", "merger", ours, "bias")] = HfSpec(
            f"model.visual.merger.{theirs}.bias")
    return m


def phi3_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Phi-3 / Phi-4 text (HF ``Phi3ForCausalLM`` naming): the fused
    qkv_proj / gate_up_proj Phi decoder as a standalone family."""
    m: Dict[Tuple[str, ...], HfSpec] = {
        ("embed_tokens", "embedding"): HfSpec("model.embed_tokens.weight"),
        ("norm", "weight"): HfSpec("model.norm.weight"),
        ("layers", "input_layernorm", "weight"): HfSpec(
            "model.layers.{i}.input_layernorm.weight", stacked=True),
        ("layers", "post_attention_layernorm", "weight"): HfSpec(
            "model.layers.{i}.post_attention_layernorm.weight", stacked=True),
        ("layers", "self_attn", "qkv_proj", "kernel"): HfSpec(
            "model.layers.{i}.self_attn.qkv_proj.weight", stacked=True,
            transpose=True),
        ("layers", "self_attn", "o_proj", "kernel"): HfSpec(
            "model.layers.{i}.self_attn.o_proj.weight", stacked=True,
            transpose=True),
        ("layers", "mlp", "gate_up_proj", "kernel"): HfSpec(
            "model.layers.{i}.mlp.gate_up_proj.weight", stacked=True,
            transpose=True),
        ("layers", "mlp", "down_proj", "kernel"): HfSpec(
            "model.layers.{i}.mlp.down_proj.weight", stacked=True,
            transpose=True),
    }
    if not config.tie_word_embeddings:
        m[("lm_head", "kernel")] = HfSpec("lm_head.weight", transpose=True)
    return m


def phi4_mm_key_map(config) -> Dict[Tuple[str, ...], HfSpec]:
    """Phi-4-multimodal, audio + text scope (no vision tower — see
    ``models/phi4_mm.py``): Phi decoder with FUSED qkv/gate_up under
    ``model.layers.`` (shared with :func:`phi3_key_map`), conformer audio
    encoder under ``model.embed_tokens_extend.audio_embed.``."""
    m = phi3_key_map(config.text_config)
    text = {("language_model",) + path: spec for path, spec in m.items()}

    conv1d_load = lambda w: np.asarray(w)[:, :, 0].T     # (O, I, 1) -> (I, O)
    conv1d_save = lambda w: np.asarray(w).T[:, :, None]
    dw_load = lambda w: np.asarray(w)[:, 0, :]           # (C, 1, k) -> (C, k)
    dw_save = lambda w: np.asarray(w)[:, None, :]
    squeeze_b = lambda w: np.asarray(w).reshape(-1)      # (1, E, 1) -> (E,)
    unsqueeze_b = lambda w: np.asarray(w)[None, :, None]

    ae = "model.embed_tokens_extend.audio_embed."
    enc = ae + "encoder."
    blk = enc + "encoders.{i}."
    a: Dict[Tuple[str, ...], HfSpec] = {}
    p = ("audio_embed", "encoder")
    a[p + ("encoder_embedding", "global_mean")] = HfSpec(
        enc + "encoder_embedding.global_mean")
    a[p + ("encoder_embedding", "global_invstd")] = HfSpec(
        enc + "encoder_embedding.global_invstd")
    a[p + ("relative_attention_bias", "weight")] = HfSpec(
        enc + "relative_attention_bias_layer.bias_values.weight")
    # nemo subsampling Sequential: conv0 at 0, then (dw, pw, act) triples
    import math as _math

    n_stages = int(_math.log2(config.audio_config.time_reduction))
    conv_idx = {"conv0": 0}
    for s in range(1, n_stages):
        conv_idx[f"dw{s}"] = 3 * s - 1
        conv_idx[f"pw{s}"] = 3 * s
    for ours, idx in conv_idx.items():
        a[p + ("embed", ours, "kernel")] = HfSpec(
            enc + f"embed.conv.{idx}.weight")
        a[p + ("embed", ours, "bias")] = HfSpec(
            enc + f"embed.conv.{idx}.bias")
    a[p + ("embed", "out", "kernel")] = HfSpec(
        enc + "embed.out.weight", transpose=True)
    a[p + ("embed", "out", "bias")] = HfSpec(enc + "embed.out.bias")

    def lin(path, name, bias=True, conv=False):
        if conv:
            a[p + ("encoders",) + path + ("kernel",)] = HfSpec(
                blk + name + ".weight", stacked=True,
                load_transform=conv1d_load, save_transform=conv1d_save)
        else:
            a[p + ("encoders",) + path + ("kernel",)] = HfSpec(
                blk + name + ".weight", stacked=True, transpose=True)
        if bias:
            a[p + ("encoders",) + path + ("bias",)] = HfSpec(
                blk + name + ".bias", stacked=True)

    def ln(path, name):
        a[p + ("encoders",) + path + ("weight",)] = HfSpec(
            blk + name + ".weight", stacked=True)
        a[p + ("encoders",) + path + ("bias",)] = HfSpec(
            blk + name + ".bias", stacked=True)

    for mod in ("feed_forward_in", "feed_forward_out"):
        ln((mod, "layer_norm"), mod + ".layer_norm")
        lin((mod, "gate_up_proj"), mod + ".gate_up_proj")
        lin((mod, "down_proj"), mod + ".down_proj")
    ln(("layer_norm_att",), "layer_norm_att")
    ln(("layer_norm",), "layer_norm")
    for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
        lin(("self_attn", proj), "self_attn." + proj)
    ln(("conv", "layer_norm"), "conv.layer_norm")
    lin(("conv", "glu"), "conv.glu.ext_pw_conv_1d", conv=True)
    for b in ("b1", "b2"):
        a[p + ("encoders", "conv", f"glu_{b}")] = HfSpec(
            blk + f"conv.glu.{b}", stacked=True,
            load_transform=squeeze_b, save_transform=unsqueeze_b)
    a[p + ("encoders", "conv", "dw_conv", "kernel")] = HfSpec(
        blk + "conv.dw_sep_conv_1d.dw_conv.weight", stacked=True,
        load_transform=dw_load, save_transform=dw_save)
    a[p + ("encoders", "conv", "dw_conv", "bias")] = HfSpec(
        blk + "conv.dw_sep_conv_1d.dw_conv.bias", stacked=True)
    lin(("conv", "pw_conv"), "conv.dw_sep_conv_1d.pw_conv", conv=True)
    lin(("conv", "ext_pw_conv"), "conv.ext_pw_conv_1d", conv=True)

    for proj in ("up_proj_for_speech", "down_proj_for_speech",
                 "up_proj_for_vision_speech", "down_proj_for_vision_speech"):
        a[("audio_embed", proj, "kernel")] = HfSpec(
            ae + proj + ".weight", transpose=True)
        a[("audio_embed", proj, "bias")] = HfSpec(ae + proj + ".bias")
    return {**text, **a}


def _key_map_for(model) -> Dict[Tuple[str, ...], HfSpec]:
    from automodel_tpu.models.registry import get_family

    if hasattr(model, "hf_key_map"):
        # wrapper models (e.g. sequence classification re-rooting a backbone)
        # own their mapping; the registry is keyed by model_type, which a
        # wrapper shares with its base family
        return model.hf_key_map()
    return get_family(model.config.model_type).key_map_fn(model.config)


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------
# HF multimodal serialization drift: post-refactor transformers nests
# everything under ``model.`` (``model.language_model.layers...``) while
# published hub checkpoints (e.g. google/gemma-3-*-it) still carry the legacy
# flat naming (``language_model.model.layers...``).  Key maps emit the new
# convention; the checkpoint reader falls back through these renames (the
# _checkpoint_conversion_mapping role in transformers).
_LEGACY_KEY_RENAMES = (
    ("model.language_model.", "language_model.model."),
    ("model.language_model.", "model."),      # qwen2.5-vl legacy flat naming
    ("model.vision_tower.", "vision_tower."),
    ("model.multi_modal_projector.", "multi_modal_projector."),
    ("model.audio_tower.", "audio_tower."),
    ("model.visual.", "visual."),
)


class _LazyCheckpoint:
    """Lazily-opened safetensors shard set with per-slice reads."""

    def __init__(self, ckpt_dir: str):
        from safetensors import safe_open

        self._safe_open = safe_open
        self.ckpt_dir = ckpt_dir
        index_path = os.path.join(ckpt_dir, SAFETENSORS_INDEX)
        if os.path.exists(index_path):
            with open(index_path) as f:
                self.weight_map: Dict[str, str] = json.load(f)["weight_map"]
        else:
            single = os.path.join(ckpt_dir, "model.safetensors")
            if not os.path.exists(single):
                raise FileNotFoundError(
                    f"No model.safetensors[.index.json] under {ckpt_dir}")
            with safe_open(single, framework="numpy") as f:
                self.weight_map = {k: "model.safetensors" for k in f.keys()}
        self._handles: Dict[str, Any] = {}

    def _file(self, fname: str):
        if fname not in self._handles:
            self._handles[fname] = self._safe_open(
                os.path.join(self.ckpt_dir, fname), framework="numpy")
        return self._handles[fname]

    def resolve(self, key: str) -> str:
        """Checkpoint name for ``key``, trying legacy<->new renames when the
        mapped name is absent (loads real hub snapshots, not just our own
        exports)."""
        if key in self.weight_map:
            return key
        for a, b in _LEGACY_KEY_RENAMES:
            for pre, alt_pre in ((a, b), (b, a)):
                if key.startswith(pre):
                    alt = alt_pre + key[len(pre):]
                    if alt in self.weight_map:
                        return alt
        raise KeyError(
            f"{key!r} not in checkpoint under {self.ckpt_dir} "
            "(legacy-name aliases tried too)")

    def __contains__(self, key: str) -> bool:
        try:
            self.resolve(key)
            return True
        except KeyError:
            return False

    def get_slice(self, key: str, idx: Tuple[slice, ...]) -> np.ndarray:
        key = self.resolve(key)
        sl = self._file(self.weight_map[key]).get_slice(key)
        return sl[idx]

    def get(self, key: str) -> np.ndarray:
        key = self.resolve(key)
        return self._file(self.weight_map[key]).get_tensor(key)


def _hf_slice(spec: HfSpec, layer: Optional[int], idx: Tuple[slice, ...],
              ckpt: _LazyCheckpoint, dtype,
              expert: Optional[int] = None,
              sub_shape: Optional[Tuple[int, ...]] = None) -> np.ndarray:
    key = (spec.template.format(
        i=None if layer is None else layer + spec.layer_offset, e=expert)
        if spec.stacked else spec.template)
    if (spec.missing_init is not None and sub_shape is not None
            and key not in ckpt):
        # per-layer fallback for stacked specs (e.g. a DeepSeek checkpoint
        # without e_score_correction_bias tensors)
        return np.asarray(spec.missing_init(sub_shape, dtype))[idx]
    if spec.column_transform is not None:
        in_sl, out_sl = idx[-2], idx[-1]
        # HF stores (out, in): reading (out_slice, :) is a contiguous
        # byte-range; transpose to ours and transform per out column
        raw = ckpt.get_slice(key, (out_sl, slice(None)))
        arr = spec.column_transform(raw.T)[in_sl, :]
    elif spec.load_transform is not None:
        arr = spec.load_transform(ckpt.get(key))[idx]
    elif spec.transpose:
        # requested (in, out) slice -> read (out, in) then transpose
        hf_idx = (idx[1], idx[0]) if len(idx) == 2 else idx[::-1]
        arr = ckpt.get_slice(key, hf_idx).T
    else:
        arr = ckpt.get_slice(key, idx)
    return arr.astype(dtype)


def load_hf_weights(
    model,
    ckpt_dir: str,
    shardings: Optional[Any] = None,
    abstract: Optional[Any] = None,
) -> Dict[str, Any]:
    """Stream an HF checkpoint directory into a (sharded) param pytree.

    ``shardings``: pytree of ``jax.sharding.Sharding`` matching the param tree
    (None -> fully replicated / single device).  Each addressable shard pulls
    only its own byte ranges via safetensors slicing.
    """
    ckpt = _LazyCheckpoint(ckpt_dir)
    key_map = _key_map_for(model)
    abstract = abstract if abstract is not None else model.abstract_params()
    flat_abs = _flatten(abstract)
    flat_shard = _flatten(shardings) if shardings is not None else {
        p: None for p in flat_abs}

    out_flat: Dict[Tuple[str, ...], jax.Array] = {}
    for path, aval in flat_abs.items():
        spec = key_map.get(path)
        if spec is None:
            raise KeyError(f"No HF mapping for param {'/'.join(path)}")
        shape, dtype = aval.shape, aval.dtype
        sharding = flat_shard.get(path)
        if sharding is None:
            sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        def cb(idx: Tuple[slice, ...], spec=spec, shape=shape, dtype=dtype):
            if (spec.missing_init is not None and not spec.stacked
                    and spec.template not in ckpt):
                return np.asarray(spec.missing_init(shape, dtype))[idx]
            if spec.expert_stacked:
                l0, l1, _ = idx[0].indices(shape[0])
                e0, e1, _ = idx[1].indices(shape[1])
                return np.stack([
                    np.stack([
                        _hf_slice(spec, i, idx[2:], ckpt, dtype, expert=e,
                                  sub_shape=shape[2:])
                        for e in range(e0, e1)
                    ], axis=0)
                    for i in range(l0, l1)
                ], axis=0)
            if spec.stacked:
                lsl = idx[0]
                start, stop, _ = lsl.indices(shape[0])
                parts = [
                    _hf_slice(spec, i, idx[1:], ckpt, dtype,
                              sub_shape=shape[1:])
                    for i in range(start, stop)
                ]
                return np.stack(parts, axis=0)
            return _hf_slice(spec, None, idx, ckpt, dtype)

        out_flat[path] = jax.make_array_from_callback(shape, sharding, cb)
    return _unflatten(out_flat)


# ---------------------------------------------------------------------------
# Writing (consolidated HF repo)
# ---------------------------------------------------------------------------
def save_hf_weights(
    model,
    params: Dict[str, Any],
    out_dir: str,
    max_shard_bytes: int = 5 * 1024**3,
    save_dtype: Optional[Any] = None,
    distribute_writes: bool = True,
    barrier_fn=None,
) -> None:
    """Write params as a consolidated HF safetensors repo (+ index + config.json).

    Multi-host: the shard plan is deterministic from shapes alone, so every
    process computes it identically and **each shard file is written by a
    different process** (round-robin) — write bandwidth scales with hosts
    instead of funnelling the whole model through host 0 (the reference's
    per-rank writer idea, ``checkpoint/_backports/hf_storage.py:67``, applied
    to the consolidated layout).  Gathers remain collective; process 0 writes
    the index.  ``distribute_writes=False`` restores the host-0-only writer
    (e.g. when only host 0 sees the output filesystem).

    ``barrier_fn``: replaces the internal ``sync_global_devices`` sync
    points (async-checkpoint committer threads must not issue device
    collectives; they pass their namespace's KV-store barrier).  Callers in
    that mode hand in HOST-materialized params (numpy leaves), so the
    collective-gather branch of ``materialize`` is never reached there.
    """
    from safetensors.numpy import save_file

    key_map = _key_map_for(model)
    flat = _flatten(params)
    save_dtype = np.dtype(save_dtype) if save_dtype is not None else None

    def materialize(v) -> np.ndarray:
        # Cross-host-sharded leaves need a collective gather that EVERY
        # process participates in; fully-addressable ones are a local copy.
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            from jax.experimental import multihost_utils

            arr = np.asarray(multihost_utils.process_allgather(v, tiled=True))
        else:
            arr = np.asarray(jax.device_get(v))
        return arr.astype(save_dtype) if save_dtype is not None else arr

    # Expand stacked params to per-layer HF tensors, lazily, with byte sizes
    # known up-front from shapes — so shard assignment (and the
    # model-xxxxx-of-xxxxx total) is planned before anything materializes.
    entries: List[Tuple[str, int, Callable[[], np.ndarray]]] = []
    for path, value in flat.items():
        spec = key_map.get(path)
        if spec is None:
            raise KeyError(f"No HF mapping for param {'/'.join(path)}")
        itemsize = (save_dtype or np.dtype(str(value.dtype))).itemsize

        def to_hf(arr: np.ndarray, spec: HfSpec) -> np.ndarray:
            if spec.save_transform is not None:
                arr = spec.save_transform(arr)
            elif spec.transpose:
                arr = arr.T
            # safetensors serializes the raw buffer, ignoring strides: a
            # transposed *view* would save the untransposed data.
            return np.ascontiguousarray(arr)

        if spec.expert_stacked:
            per_expert = int(np.prod(value.shape[2:])) * itemsize
            for i in range(value.shape[0]):
                for e in range(value.shape[1]):
                    def expert_fn(v=value, i=i, e=e, spec=spec):
                        return to_hf(materialize(v[i][e]), spec)
                    entries.append(
                        (spec.template.format(i=i + spec.layer_offset, e=e),
                         per_expert, expert_fn))
        elif spec.stacked:
            per_layer = int(np.prod(value.shape[1:])) * itemsize
            for i in range(value.shape[0]):
                def layer_fn(v=value, i=i, spec=spec):
                    return to_hf(materialize(v[i]), spec)
                entries.append((spec.template.format(i=i + spec.layer_offset),
                                per_layer, layer_fn))
        else:
            def full_fn(v=value, spec=spec):
                return to_hf(materialize(v), spec)
            entries.append(
                (spec.template, int(np.prod(value.shape)) * itemsize, full_fn))

    # Greedy shard plan by byte budget.
    shard_plan: List[List[Tuple[str, Callable[[], np.ndarray]]]] = [[]]
    cur_bytes = 0
    for name, nbytes, fn in entries:
        if shard_plan[-1] and cur_bytes + nbytes > max_shard_bytes:
            shard_plan.append([])
            cur_bytes = 0
        shard_plan[-1].append((name, fn))
        cur_bytes += nbytes

    proc, nproc = jax.process_index(), jax.process_count()
    # every writing process creates the dir on ITS filesystem (the output
    # path need not be shared; the index then only covers host-0 files, so
    # non-shared setups should pass distribute_writes=False)
    if barrier_fn is None:
        def barrier_fn(tag):
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(tag)
    if proc == 0 or distribute_writes:
        os.makedirs(out_dir, exist_ok=True)
    if nproc > 1:
        barrier_fn("hf_save_dir_ready")

    # Materialize and write one shard at a time: peak host RAM is one shard,
    # not the whole model.  All processes run the loop (the gathers are
    # collective); shard i is kept + written by process i % nproc.
    n = len(shard_plan)
    weight_map: Dict[str, str] = {}
    total = 0
    for i, shard_entries in enumerate(shard_plan):
        fname = (
            "model.safetensors" if n == 1
            else f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        )
        writes_this = (i % nproc == proc) if distribute_writes else (proc == 0)
        shard: Dict[str, np.ndarray] = {}
        for name, fn in shard_entries:
            arr = fn()
            # the index is deterministic from the plan — track it everywhere
            weight_map[name] = fname
            total += arr.nbytes
            if writes_this:
                shard[name] = arr
        if writes_this:
            save_file(shard, os.path.join(out_dir, fname),
                      metadata={"format": "pt"})
        del shard
    if nproc > 1:
        barrier_fn("hf_save_shards_done")
    if proc != 0:
        return
    # On a non-shared filesystem, distributed writers leave this host with an
    # index that names shards it never received — verify the plan landed
    # before publishing the index (otherwise the corruption is only found at
    # load time as an opaque safetensors open error).
    missing = sorted(
        f for f in set(weight_map.values())
        if not os.path.exists(os.path.join(out_dir, f)))
    if missing:
        raise RuntimeError(
            f"consolidated HF save incomplete: {len(missing)} planned shard "
            f"file(s) missing from {out_dir} (e.g. {missing[0]}); if the "
            "output directory is not on a filesystem shared by all hosts, "
            "pass distribute_writes=False so process 0 writes every shard")
    with open(os.path.join(out_dir, SAFETENSORS_INDEX), "w") as f:
        json.dump(
            {"metadata": {"total_size": total}, "weight_map": weight_map},
            f, indent=2)
    save_hf_config(model, out_dir)


# Tokenizer / generation-config sidecar files a complete HF repo carries
# (reference copies them into consolidated exports, ``checkpointing.py:240``).
HF_AUX_FILES = (
    "tokenizer.json", "tokenizer_config.json", "special_tokens_map.json",
    "tokenizer.model", "vocab.json", "merges.txt", "generation_config.json",
    "preprocessor_config.json", "processor_config.json", "chat_template.json",
)


def copy_hf_aux_files(src_dir: Optional[str], out_dir: str) -> List[str]:
    """Copy tokenizer/processor/generation files from the source checkpoint
    into an exported repo so it is loadable end-to-end (AutoTokenizer +
    AutoModel) without the original.  Process 0 only; missing files skip."""
    import shutil

    if src_dir is None or jax.process_index() != 0:
        return []
    copied = []
    for name in HF_AUX_FILES:
        src = os.path.join(src_dir, name)
        if os.path.isfile(src):
            shutil.copy2(src, os.path.join(out_dir, name))
            copied.append(name)
    return copied


def save_hf_config(model, out_dir: str) -> None:
    import dataclasses

    from automodel_tpu.models.registry import get_family

    cfg = model.config
    d = dataclasses.asdict(cfg)
    # HF configs use field ABSENCE for optional ints (e.g. Phi-3's
    # original_max_position_embeddings defaults to max_position_embeddings);
    # an explicit null would override that default with None.
    if d.get("original_max_position_embeddings") is None:
        d.pop("original_max_position_embeddings", None)
    d["architectures"] = (getattr(model, "hf_architectures", None)
                          or get_family(cfg.model_type).hf_architectures)
    for k, v in getattr(model, "hf_config_extra", lambda: {})().items():
        d[k] = v
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(d, f, indent=2, default=str)


# path-keyed pytree flatten helpers (shared)
from automodel_tpu.utils.pytree import (  # noqa: E402
    flatten_path_dict as _flatten,
    unflatten_path_dict as _unflatten,
)
