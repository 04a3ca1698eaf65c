from automodel_tpu.generation.dense_kv import DenseKVView
from automodel_tpu.generation.generate import GenerationConfig, generate

__all__ = ["DenseKVView", "GenerationConfig", "generate"]
