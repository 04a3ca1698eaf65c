"""Single registry of model families.

One entry per ``model_type`` holds everything the framework needs to know
about a family: config class, model class, HF key map builder, and the HF
``architectures`` string for exported ``config.json``.  New families register
here once (vs. the reference's per-model dicts scattered across
``_transformers/auto_model.py`` and ``distributed/optimized_tp_plans.py:235``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    model_type: str
    config_cls: type
    model_cls: type
    key_map_fn: Callable          # config -> {tree path: HfSpec}
    hf_architectures: List[str]


_REGISTRY: Dict[str, ModelFamily] = {}


def register_model(family: ModelFamily) -> None:
    _REGISTRY[family.model_type] = family


def get_family(model_type: str) -> ModelFamily:
    _ensure_builtin()
    if model_type not in _REGISTRY:
        raise KeyError(
            f"Unknown model_type {model_type!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[model_type]


def known_model_types() -> List[str]:
    _ensure_builtin()
    return sorted(_REGISTRY)


_BUILTIN_DONE = False


def _ensure_builtin() -> None:
    """Lazy registration avoids import cycles (model modules import nothing
    from here; this module imports them only on first lookup)."""
    global _BUILTIN_DONE
    if _BUILTIN_DONE:
        return
    _BUILTIN_DONE = True
    from automodel_tpu.models import hf_io
    from automodel_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    for mt, arch in (
        ("llama", "LlamaForCausalLM"),
        ("mistral", "MistralForCausalLM"),
        ("qwen2", "Qwen2ForCausalLM"),
        ("qwen3", "Qwen3ForCausalLM"),
    ):
        register_model(ModelFamily(mt, LlamaConfig, LlamaForCausalLM,
                                   hf_io.llama_key_map, [arch]))
    register_model(ModelFamily("gpt2", GPT2Config, GPT2LMHeadModel,
                               hf_io.gpt2_key_map, ["GPT2LMHeadModel"]))
    from automodel_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    register_model(ModelFamily("mixtral", MixtralConfig, MixtralForCausalLM,
                               hf_io.mixtral_key_map, ["MixtralForCausalLM"]))
    from automodel_tpu.models.gemma3 import (
        Gemma3Config,
        Gemma3ForCausalLM,
        Gemma3ForConditionalGeneration,
        Gemma3VLConfig,
    )

    register_model(ModelFamily("gemma3_text", Gemma3Config, Gemma3ForCausalLM,
                               hf_io.gemma3_key_map, ["Gemma3ForCausalLM"]))
    # HF model_type "gemma3" is the MULTIMODAL config (nested text/vision)
    register_model(ModelFamily("gemma3", Gemma3VLConfig,
                               Gemma3ForConditionalGeneration,
                               hf_io.gemma3_vlm_key_map,
                               ["Gemma3ForConditionalGeneration"]))
    from automodel_tpu.models.vlm import VLMConfig, VLMForConditionalGeneration

    register_model(ModelFamily("llava", VLMConfig, VLMForConditionalGeneration,
                               hf_io.vlm_key_map,
                               ["LlavaForConditionalGeneration"]))
    from automodel_tpu.models.qwen2_5_vl import (
        Qwen25VLConfig,
        Qwen25VLForConditionalGeneration,
    )

    register_model(ModelFamily("qwen2_5_vl", Qwen25VLConfig,
                               Qwen25VLForConditionalGeneration,
                               hf_io.qwen2_5_vl_key_map,
                               ["Qwen2_5_VLForConditionalGeneration"]))
    from automodel_tpu.models.qwen2_5_vl import (
        Qwen25VLTextConfig,
        Qwen25VLTextModel,
    )

    register_model(ModelFamily("qwen2_5_vl_text", Qwen25VLTextConfig,
                               Qwen25VLTextModel, hf_io.llama_key_map,
                               ["Qwen2_5_VLTextModel"]))
    from automodel_tpu.models.phi4_mm import Phi4MMConfig, Phi4MMForCausalLM

    register_model(ModelFamily("phi4_multimodal", Phi4MMConfig,
                               Phi4MMForCausalLM, hf_io.phi4_mm_key_map,
                               ["Phi4MultimodalForCausalLM"]))
    from automodel_tpu.models.phi3 import Phi3Config, Phi3ForCausalLM

    register_model(ModelFamily("phi3", Phi3Config, Phi3ForCausalLM,
                               hf_io.phi3_key_map, ["Phi3ForCausalLM"]))
    from automodel_tpu.models.gemma2 import Gemma2Config, Gemma2ForCausalLM

    register_model(ModelFamily("gemma2", Gemma2Config, Gemma2ForCausalLM,
                               hf_io.gemma3_key_map, ["Gemma2ForCausalLM"]))
    from automodel_tpu.models.qwen3_moe import (
        Qwen3MoeConfig,
        Qwen3MoeForCausalLM,
    )

    register_model(ModelFamily("qwen3_moe", Qwen3MoeConfig,
                               Qwen3MoeForCausalLM, hf_io.qwen3_moe_key_map,
                               ["Qwen3MoeForCausalLM"]))
    from automodel_tpu.models.gemma3n import (
        Gemma3nForCausalLM,
        Gemma3nTextConfig,
    )

    register_model(ModelFamily("gemma3n_text", Gemma3nTextConfig,
                               Gemma3nForCausalLM,
                               hf_io.gemma3n_text_key_map,
                               ["Gemma3nForCausalLM"]))
    from automodel_tpu.models.gemma3n import (
        Gemma3nForConditionalGeneration,
        Gemma3nVLConfig,
    )

    register_model(ModelFamily("gemma3n", Gemma3nVLConfig,
                               Gemma3nForConditionalGeneration,
                               hf_io.gemma3n_vlm_key_map,
                               ["Gemma3nForConditionalGeneration"]))
    from automodel_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        DeepseekV3ForCausalLM,
    )

    register_model(ModelFamily("deepseek_v3", DeepseekV3Config,
                               DeepseekV3ForCausalLM,
                               hf_io.deepseek_v3_key_map,
                               ["DeepseekV3ForCausalLM"]))
    # Kimi-K2's published config.json says ``model_type: kimi_k2``; its
    # modeling code is DeepSeek-V3's (MLA, sigmoid noaux_tc router, one
    # shared expert) at other sizes.
    register_model(ModelFamily("kimi_k2", DeepseekV3Config,
                               DeepseekV3ForCausalLM,
                               hf_io.deepseek_v3_key_map,
                               ["DeepseekV3ForCausalLM"]))
    from automodel_tpu.models.deepseek_v2 import (
        DeepseekV2Config,
        DeepseekV2ForCausalLM,
    )

    register_model(ModelFamily("deepseek_v2", DeepseekV2Config,
                               DeepseekV2ForCausalLM,
                               hf_io.deepseek_v2_key_map,
                               ["DeepseekV2ForCausalLM"]))
    from automodel_tpu.models.olmo2 import Olmo2Config, Olmo2ForCausalLM

    register_model(ModelFamily("olmo2", Olmo2Config, Olmo2ForCausalLM,
                               hf_io.olmo2_key_map, ["Olmo2ForCausalLM"]))
    from automodel_tpu.models.starcoder2 import (
        Starcoder2Config,
        Starcoder2ForCausalLM,
    )

    register_model(ModelFamily("starcoder2", Starcoder2Config,
                               Starcoder2ForCausalLM,
                               hf_io.starcoder2_key_map,
                               ["Starcoder2ForCausalLM"]))
    from automodel_tpu.models.granite import GraniteConfig, GraniteForCausalLM

    # llama key map verbatim: Granite's deltas are scalars, not tensors
    register_model(ModelFamily("granite", GraniteConfig, GraniteForCausalLM,
                               hf_io.llama_key_map, ["GraniteForCausalLM"]))
    from automodel_tpu.models.smallthinker import (
        SmallThinkerConfig,
        SmallThinkerForCausalLM,
    )

    # window and full (NoPE) layers in one stack, routed ReGLU experts
    register_model(ModelFamily("smallthinker", SmallThinkerConfig,
                               SmallThinkerForCausalLM,
                               hf_io.smallthinker_key_map,
                               ["SmallThinkerForCausalLM"]))
    from automodel_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM

    # Qwen3's block over power retention: no softmax attention, no KV cache
    register_model(ModelFamily("brumby", BrumbyConfig, BrumbyForCausalLM,
                               hf_io.brumby_key_map, ["BrumbyForCausalLM"]))
