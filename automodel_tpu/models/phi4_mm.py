"""Phi-4-multimodal (audio + text scope): conformer speech encoder + Phi
decoder.

Reference: the collator ``phi4_mm_collate_fn``
(``nemo_automodel/components/datasets/vlm/collate_fns.py:77-117``) pairs with
a transformers-loaded Phi-4-MM; parity target is
``transformers/models/phi4_multimodal/modeling_phi4_multimodal.py``.  This
family finally CONSUMES the audio keys that collator emits
(``input_audio_embeds`` / ``audio_embed_sizes`` / ``audio_attention_mask``)
— previously the train step failed loudly on them by design.

Scope: the speech path (audio encoder + speech projector + decoder).  The
vision tower is not built — Phi-4-MM's vision side duplicates what the
SigLIP/llava and Gemma-3 families already cover, while the conformer audio
stack is the one modality the framework lacked.  Exports therefore carry no
``image_embed`` weights (HF ``from_pretrained`` random-inits them with a
warning; audio+text logits are unaffected).

TPU shape:
* the conformer blocks are scan-stacked like every decoder here (one
  compiled body for all ``num_blocks``); the depthwise/causal convolutions
  ride the scan as ``[depth, ...]`` kernels via ``lax.conv_general_dilated``;
* the audio->token scatter is static-shape: a stable argsort over the
  per-frame validity mask replaces HF's data-dependent concat + index_put;
* the deterministic (eval) streaming-mask path is implemented; HF's
  train-time random chunk-alignment jitter (a regularizer) and the >500
  frame unfold path are not — both asserted against, not silently skipped.

The decoder is the Phi architecture: FUSED qkv and gate_up projections
(bias-free), partial-rotary support, same pre-norm residual order as Llama.
It keeps its own layer body because the fused param layout must round-trip
HF checkpoints 1:1 (splitting the tensors would break consolidated save).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import layer_norm, rms_norm


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Phi4MMAudioConfig:
    """HF ``Phi4MultimodalAudioConfig`` field names (speech-relevant set)."""

    hidden_size: int = 1024
    intermediate_size: int = 1536
    num_blocks: int = 24
    num_attention_heads: int = 16
    chunk_size: int = -1
    left_chunk: int = 18
    ext_pw_out_channel: int = 1024
    depthwise_separable_out_channel: int = 1024
    depthwise_multiplier: int = 1
    kernel_size: int = 3
    input_size: int = 80
    time_reduction: int = 8
    bias_max_distance: int = 1000
    bias_symmetric: bool = False
    nemo_conv_channels: int = 1024
    downsample_rate: int = 1
    audio_token_id: int = 200011

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Phi4MMAudioConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def nemo_final_size(self) -> int:
        length = self.input_size
        for _ in range(int(math.log2(self.time_reduction))):
            length = math.floor((length - 1) / 2 + 1)
        return length

    @property
    def num_buckets(self) -> int:
        return (self.bias_max_distance if self.bias_symmetric
                else 2 * self.bias_max_distance)


@dataclasses.dataclass
class Phi4MMTextConfig(LlamaConfig):
    """Phi decoder: fused qkv/gate_up, optional partial rotary."""

    partial_rotary_factor: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        self.model_type = "phi4_multimodal_text"

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Phi4MMTextConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


@dataclasses.dataclass
class Phi4MMConfig:
    """HF ``Phi4MultimodalConfig`` (text fields live at the top level)."""

    text_config: Any = None
    audio_config: Any = None
    model_type: str = "phi4_multimodal"

    def __post_init__(self):
        if isinstance(self.text_config, dict):
            self.text_config = Phi4MMTextConfig.from_hf_config(
                self.text_config)
        if isinstance(self.audio_config, dict):
            self.audio_config = Phi4MMAudioConfig.from_hf_config(
                self.audio_config)
        self.text_config = self.text_config or Phi4MMTextConfig()
        self.audio_config = self.audio_config or Phi4MMAudioConfig()

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Phi4MMConfig":
        # HF nests audio_config but keeps text fields top-level
        return cls(text_config={k: v for k, v in hf.items()
                                if k not in ("audio_config", "vision_config")},
                   audio_config=hf.get("audio_config") or {})


# ---------------------------------------------------------------------------
# Audio encoder (conformer)
# ---------------------------------------------------------------------------
def _layer_norm(x, p, eps=1e-5):
    return layer_norm(x, p["weight"], p["bias"], eps)


def _lin(x, p, dtype):
    y = x @ p["kernel"].astype(dtype)
    return y + p["bias"].astype(dtype) if "bias" in p else y


def _audio_mlp(x, p, cd):
    """Half-GLU MLP — NOTE: HF's audio MLP chunks (up, gate), the DECODER
    mlp chunks (gate, up); the order is load-bearing for parity."""
    y = _layer_norm(x, p["layer_norm"])
    uu = _lin(y, p["gate_up_proj"], cd)
    up, gate = jnp.split(uu, 2, axis=-1)
    return _lin(up * jax.nn.silu(gate), p["down_proj"], cd)


def _conv_module(x, p, cfg: Phi4MMAudioConfig, cd):
    """GLU pointwise -> causal depthwise-separable -> act -> pointwise."""
    y = _layer_norm(x, p["layer_norm"])
    # GLU pointwise (1x1 conv == matmul), with the b1/b2 channel biases
    h = _lin(y, p["glu"], cd)                        # [B, T, 2*E]
    e = cfg.ext_pw_out_channel
    h = ((h[..., :e] + p["glu_b1"].astype(cd))
         * jax.nn.silu(h[..., e:] + p["glu_b2"].astype(cd)))
    # causal depthwise conv over time (torch pad=k-1 both sides, trim right)
    k = cfg.kernel_size
    hp = jnp.pad(h, ((0, 0), (k - 1, 0), (0, 0)))
    dw = p["dw_conv"]["kernel"].astype(cd)           # [C, k]
    h = lax.conv_general_dilated(
        hp.swapaxes(1, 2)[:, :, :],                  # NCW
        dw[:, None, :],                              # (C, 1, k), groups=C
        window_strides=(1,), padding="VALID",
        feature_group_count=h.shape[-1],
        dimension_numbers=("NCH", "OIH", "NCH"),
    ).swapaxes(1, 2) + p["dw_conv"]["bias"].astype(cd)
    h = _lin(h, p["pw_conv"], cd)                    # pointwise of dw-sep
    h = jax.nn.silu(h)
    return _lin(h, p["ext_pw_conv"], cd)


class Phi4MMAudioEncoder:
    """Mean-var norm -> nemo conv subsampling -> scan-stacked conformer."""

    def __init__(self, config: Phi4MMAudioConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.remat = remat

    @property
    def _n_stages(self) -> int:
        return int(math.log2(self.config.time_reduction))

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        D, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.ext_pw_out_channel
        C = cfg.nemo_conv_channels
        L = cfg.num_blocks
        keys = iter(jax.random.split(key, 32))

        def dense(k, shape, stacked=True):
            full = (L, *shape) if stacked else shape
            return (jax.random.normal(k, full, jnp.float32) * 0.02).astype(
                self.param_dtype)

        def zeros(shape):
            return jnp.zeros(shape, self.param_dtype)

        def lin(k, i, o, stacked=True):
            b = (L, o) if stacked else (o,)
            return {"kernel": dense(k, (i, o), stacked),
                    "bias": zeros(b)}

        def ln(stacked=True):
            s = (L, D) if stacked else (D,)
            return {"weight": jnp.ones(s, self.param_dtype),
                    "bias": zeros(s)}

        subsample = {"conv0": {"kernel": dense(next(keys), (C, 1, 3, 3),
                                               stacked=False),
                               "bias": zeros((C,))}}
        for s in range(1, self._n_stages):
            subsample[f"dw{s}"] = {"kernel": dense(next(keys), (C, 1, 3, 3),
                                                   stacked=False),
                                   "bias": zeros((C,))}
            subsample[f"pw{s}"] = {"kernel": dense(next(keys), (C, C, 1, 1),
                                                   stacked=False),
                                   "bias": zeros((C,))}
        subsample["out"] = lin(next(keys), C * cfg.nemo_final_size, D,
                               stacked=False)

        block = {
            "feed_forward_in": {
                "layer_norm": ln(), "gate_up_proj": lin(next(keys), D, 2 * I),
                "down_proj": lin(next(keys), I, D)},
            "layer_norm_att": ln(),
            "self_attn": {
                "q_proj": lin(next(keys), D, D),
                "k_proj": lin(next(keys), D, D),
                "v_proj": lin(next(keys), D, D),
                "o_proj": lin(next(keys), D, D)},
            "conv": {
                "layer_norm": ln(),
                "glu": lin(next(keys), D, 2 * E),
                "glu_b1": zeros((L, E)), "glu_b2": zeros((L, E)),
                "dw_conv": {"kernel": dense(
                    next(keys), (cfg.depthwise_separable_out_channel,
                                 cfg.kernel_size)),
                    "bias": zeros((L, cfg.depthwise_separable_out_channel))},
                "pw_conv": lin(next(keys),
                               cfg.depthwise_separable_out_channel, D),
                "ext_pw_conv": lin(next(keys), D, E)},
            "feed_forward_out": {
                "layer_norm": ln(), "gate_up_proj": lin(next(keys), D, 2 * I),
                "down_proj": lin(next(keys), I, D)},
            "layer_norm": ln(),
        }
        return {
            "encoder_embedding": {
                "global_mean": zeros((cfg.input_size,)),
                "global_invstd": jnp.ones((cfg.input_size,),
                                          self.param_dtype)},
            "embed": subsample,
            "relative_attention_bias": {
                "weight": dense(next(keys),
                                (cfg.num_buckets, cfg.num_attention_heads),
                                stacked=False)},
            "encoders": block,
        }

    def param_axes(self) -> Dict[str, Any]:
        def rep(tree):
            return jax.tree.map(
                lambda leaf: tuple([None] * len(leaf.shape)),
                tree)

        abs_tree = jax.eval_shape(self.init, jax.random.key(0))
        axes = rep(abs_tree)
        # the big per-layer matmuls shard like decoder FFNs
        enc = axes["encoders"]
        for mod in ("feed_forward_in", "feed_forward_out"):
            enc[mod]["gate_up_proj"]["kernel"] = ("layers", "embed", "mlp")
            enc[mod]["down_proj"]["kernel"] = ("layers", "mlp", "embed")
        for proj in ("q_proj", "k_proj", "v_proj"):
            enc["self_attn"][proj]["kernel"] = ("layers", "embed", "heads")
        enc["self_attn"]["o_proj"]["kernel"] = ("layers", "heads", "embed")
        return axes

    def _subsample(self, x, params):
        """[B, T, input_size] -> [B, ceil-ish T/time_reduction, hidden]."""
        cd = self.compute_dtype
        h = x.astype(cd)[:, None, :, :]              # NCHW (C=1)
        p = params["embed"]

        def conv(h, node, groups=1):
            return lax.conv_general_dilated(
                h, node["kernel"].astype(cd), window_strides=(2, 2),
                padding=((1, 1), (1, 1)), feature_group_count=groups,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            ) + node["bias"].astype(cd)[None, :, None, None]

        h = jax.nn.relu(conv(h, p["conv0"]))
        for s in range(1, self._n_stages):
            h = conv(h, p[f"dw{s}"], groups=h.shape[1])
            h = lax.conv_general_dilated(
                h, p[f"pw{s}"]["kernel"].astype(cd), window_strides=(1, 1),
                padding="VALID",
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            ) + p[f"pw{s}"]["bias"].astype(cd)[None, :, None, None]
            h = jax.nn.relu(h)
        b, c, t, f = h.shape
        h = h.transpose(0, 2, 1, 3).reshape(b, t, c * f)
        return _lin(h, p["out"], cd)

    def _rel_bias(self, params, t: int) -> jnp.ndarray:
        cfg = self.config
        rel = np.arange(t)[None, :] - np.arange(t)[:, None]
        rel = np.clip(rel, -cfg.bias_max_distance, cfg.bias_max_distance - 1)
        idx = np.abs(rel) if cfg.bias_symmetric else rel + cfg.num_buckets // 2
        table = params["relative_attention_bias"]["weight"]
        bias = table[jnp.asarray(idx)]               # [T, T, heads]
        return bias.transpose(2, 0, 1)[None]         # [1, H, T, T]

    def __call__(self, params, features: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """[B, T, input_size] (+ optional [B, T] frame mask) ->
        [B, T', hidden]."""
        cfg = self.config
        cd = self.compute_dtype
        emb = params["encoder_embedding"]
        x = ((features.astype(jnp.float32)
              - emb["global_mean"].astype(jnp.float32))
             * emb["global_invstd"].astype(jnp.float32))
        x = self._subsample(x, params)
        B, T, D = x.shape
        assert T <= 500, (
            f"audio sequence {T} frames post-subsampling exceeds the "
            "absolute-position window (500); the HF unfold path is not "
            "implemented — chunk the audio at the collator")
        if cfg.chunk_size > 0:
            raise NotImplementedError(
                "streaming chunk masks: only the full-attention default "
                "(chunk_size=-1) is implemented")
        if mask is not None:
            lens = jnp.sum(mask.astype(jnp.int32), axis=1)
            sub_lens = jnp.ceil(lens / cfg.time_reduction).astype(jnp.int32)
            pad_mask = jnp.arange(T)[None, :] < sub_lens[:, None]  # [B, T]
        else:
            pad_mask = jnp.ones((B, T), bool)
        # HF quirk reproduced exactly: the (bool) availability mask is ADDED
        # to the logits (+1 for visible frames), not -inf masked
        add_mask = (pad_mask[:, None, None, :].astype(jnp.float32)
                    + self._rel_bias(params, T).astype(jnp.float32))

        Hh, Dh = cfg.num_attention_heads, cfg.head_dim
        scale = Dh ** -0.5

        def block(x, p):
            r = x + 0.5 * _audio_mlp(x, p["feed_forward_in"], cd)
            y = _layer_norm(r, p["layer_norm_att"])
            q = _lin(y, p["self_attn"]["q_proj"], cd).reshape(B, T, Hh, Dh)
            k = _lin(y, p["self_attn"]["k_proj"], cd).reshape(B, T, Hh, Dh)
            v = _lin(y, p["self_attn"]["v_proj"], cd).reshape(B, T, Hh, Dh)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            logits = logits * scale + add_mask
            w = jax.nn.softmax(logits, axis=-1).astype(cd)
            o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, T, Hh * Dh)
            x = r + _lin(o, p["self_attn"]["o_proj"], cd)
            x = x + _conv_module(x, p["conv"], cfg, cd)
            x = x + 0.5 * _audio_mlp(x, p["feed_forward_out"], cd)
            return _layer_norm(x, p["layer_norm"]), None

        body = block
        if self.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, params["encoders"])
        return x


# ---------------------------------------------------------------------------
# Decoder (Phi architecture: fused qkv / gate_up, partial rotary)
# ---------------------------------------------------------------------------
class Phi4MMTextModel(LlamaForCausalLM):
    def __init__(self, config: Phi4MMTextConfig, **kwargs):
        super().__init__(config, **kwargs)
        rotary_dim = int(config.head_dim
                         * getattr(config, "partial_rotary_factor", 1.0))
        # Re-derive the rope tables at the (possibly partial) rotary dim;
        # handles longrope (Phi-3-mini-128k / long Phi-4) via the base
        # class's short/long table pair.
        self._init_rope(rotary_dim)
        self._rotary_dim = rotary_dim

    def _init_ffn(self, keys, dense):
        cfg = self.config
        H, I = cfg.hidden_size, cfg.intermediate_size
        return {"mlp": {
            "gate_up_proj": {"kernel": dense(next(keys), (H, 2 * I))},
            "down_proj": {"kernel": dense(next(keys), (I, H))}}}

    def _ffn_axes(self):
        return {"mlp": {
            "gate_up_proj": {"kernel": ("layers", "embed", "mlp")},
            "down_proj": {"kernel": ("layers", "mlp", "embed")}}}

    def init(self, key: jax.Array) -> Dict[str, Any]:
        params = super().init(key)
        cfg = self.config
        L, H = cfg.num_hidden_layers, cfg.hidden_size
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        k = jax.random.fold_in(key, 99)
        attn = {"qkv_proj": {"kernel": (jax.random.normal(
            k, (L, H, (Hq + 2 * Hk) * D), jnp.float32) * 0.02).astype(
                self.param_dtype)},
            "o_proj": params["layers"]["self_attn"]["o_proj"]}
        params["layers"]["self_attn"] = attn
        return params

    def param_axes(self) -> Dict[str, Any]:
        axes = super().param_axes()
        axes["layers"]["self_attn"] = {
            "qkv_proj": {"kernel": ("layers", "embed", "qkv3")},
            "o_proj": {"kernel": ("layers", "heads", "embed")}}
        return axes

    def _apply_rope(self, q, k, position_ids, inv_freq, rope_scale=1.0):
        from automodel_tpu.ops.rotary import apply_rope

        # Partial rotary: HF scales only the rotated channels (the pass-
        # through tail is concatenated unscaled).
        return apply_rope(q, k, position_ids, inv_freq,
                          attention_scaling=rope_scale,
                          rotary_dim=self._rotary_dim)

    def _decoder_layer(self, hidden, layer_params, position_ids, segment_ids,
                       attention_mask, inv_freq, adapters=None,
                       adapter_scale=1.0, adapter_dropout=0.0,
                       dropout_position="post", dropout_rng=None,
                       kv_cache=None, rope_scale=1.0):
        cfg = self.config
        B, S, H = hidden.shape
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        p = layer_params
        cd = self.compute_dtype
        if adapters is not None:
            # the fused-projection layout has no bypass wiring yet; fail
            # instead of training adapters whose grads would be zero
            # (PEFT's merge path still works — it rewrites kernels directly)
            raise NotImplementedError(
                "rank-r LoRA bypass is not wired for the fused Phi "
                "projections; use peft merge mode (dropout=0)")
        from automodel_tpu.ops.quant import maybe_qdot

        resid = hidden
        x = rms_norm(hidden, p["input_layernorm"]["weight"], cfg.rms_norm_eps)
        # Fused projections route through maybe_qdot like the per-module
        # Llama path: quantization is per-matmul, so the fused qkv/gate_up
        # kernels are each ONE quantized GEMM (filter_fqns match the fused
        # module names).
        qkv = maybe_qdot(x, p["self_attn"]["qkv_proj"]["kernel"].astype(cd),
                         self.quant, "self_attn.qkv_proj")
        q = qkv[..., :Hq * D].reshape(B, S, Hq, D)
        k = qkv[..., Hq * D:(Hq + Hk) * D].reshape(B, S, Hk, D)
        v = qkv[..., (Hq + Hk) * D:].reshape(B, S, Hk, D)
        q, k = self._apply_rope(q, k, position_ids, inv_freq, rope_scale)
        attn, new_cache = self._attention_core(
            q, k, v, segment_ids, attention_mask, kv_cache,
            local_window_size=self._sliding_window)
        attn = maybe_qdot(attn.reshape(B, S, Hq * D),
                          p["self_attn"]["o_proj"]["kernel"].astype(cd),
                          self.quant, "self_attn.o_proj")
        hidden = resid + attn

        resid = hidden
        x = rms_norm(hidden, p["post_attention_layernorm"]["weight"],
                     cfg.rms_norm_eps)
        gu = maybe_qdot(x, p["mlp"]["gate_up_proj"]["kernel"].astype(cd),
                        self.quant, "mlp.gate_up_proj")
        gate, up = jnp.split(gu, 2, axis=-1)     # decoder order: gate first
        down = maybe_qdot(up * jax.nn.silu(gate),
                          p["mlp"]["down_proj"]["kernel"].astype(cd),
                          self.quant, "mlp.down_proj")
        from automodel_tpu.distributed.shardings import constrain

        out = constrain(resid + down, ("act_batch", "act_seq", "act_embed"))
        return out, new_cache, None


# ---------------------------------------------------------------------------
# Wrapper
# ---------------------------------------------------------------------------
class Phi4MMForCausalLM:
    """``model._target_: automodel_tpu.models.phi4_mm.build_phi4_mm``"""

    extra_batch_keys = ("input_audio_embeds", "audio_embed_sizes",
                        "audio_attention_mask")

    def __init__(self, config: Phi4MMConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True, **kwargs):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.language_model = Phi4MMTextModel(
            config.text_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat, **kwargs)
        self.audio_encoder = Phi4MMAudioEncoder(
            config.audio_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        kt, ka, kp = jax.random.split(key, 3)
        D = self.config.audio_config.hidden_size
        H = self.config.text_config.hidden_size
        dsr = self.config.audio_config.downsample_rate

        def lin(k, i, o):
            return {"kernel": (jax.random.normal(k, (i, o), jnp.float32)
                               * 0.02).astype(self.param_dtype),
                    "bias": jnp.zeros((o,), self.param_dtype)}

        ks = jax.random.split(kp, 4)
        return {
            "language_model": self.language_model.init(kt),
            "audio_embed": {
                "encoder": self.audio_encoder.init(ka),
                "up_proj_for_speech": lin(ks[0], D * dsr, H),
                "down_proj_for_speech": lin(ks[1], H, H),
                "up_proj_for_vision_speech": lin(ks[2], D * dsr, H),
                "down_proj_for_vision_speech": lin(ks[3], H, H),
            },
        }

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        rep2 = {"kernel": (None, "embed"), "bias": ("norm",)}
        return {
            "language_model": self.language_model.param_axes(),
            "audio_embed": {
                "encoder": self.audio_encoder.param_axes(),
                "up_proj_for_speech": rep2,
                "down_proj_for_speech": rep2,
                "up_proj_for_vision_speech": rep2,
                "down_proj_for_vision_speech": rep2,
            },
        }

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        return self.language_model.init_kv_cache(batch, max_len, dtype)

    def encode_audio(self, params, features, audio_attention_mask=None,
                     mode: str = "speech") -> jnp.ndarray:
        cd = self.compute_dtype
        ae = params["audio_embed"]
        h = self.audio_encoder(ae["encoder"], features, audio_attention_mask)
        up = ae[f"up_proj_for_{mode}"]
        down = ae[f"down_proj_for_{mode}"]
        h = jax.nn.gelu(_lin(h, up, cd), approximate=False)
        return _lin(h, down, cd)

    def __call__(self, params, input_ids, input_audio_embeds=None,
                 audio_embed_sizes=None, audio_attention_mask=None,
                 position_ids=None, segment_ids=None, attention_mask=None,
                 return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        lm = self.language_model
        lp = params["language_model"]
        B, S = input_ids.shape
        embeds = lp["embed_tokens"]["embedding"][input_ids].astype(
            self.compute_dtype)
        if input_audio_embeds is not None:
            feats = self.encode_audio(params, input_audio_embeds,
                                      audio_attention_mask)  # [Na, T, H]
            Na, T, H = feats.shape
            if audio_embed_sizes is None:
                audio_embed_sizes = jnp.full((Na,), T, jnp.int32)
            # static-shape merge: HF concatenates the first sizes[i] frames
            # of each sample then index_puts at audio-token positions; here a
            # stable argsort over frame validity produces the same row-major
            # merged order without data-dependent shapes
            valid = (jnp.arange(T)[None, :]
                     < audio_embed_sizes[:, None]).reshape(-1)
            order = jnp.argsort(~valid, stable=True)
            merged = feats.reshape(Na * T, H)[order]
            is_audio = (input_ids
                        == self.config.audio_config.audio_token_id).reshape(-1)
            idx = jnp.clip(jnp.cumsum(is_audio) - 1, 0, merged.shape[0] - 1)
            gathered = merged[idx].reshape(B, S, -1)
            embeds = jnp.where(is_audio.reshape(B, S)[..., None],
                               gathered.astype(embeds.dtype), embeds)
        return lm.forward_embeds(
            lp, embeds, position_ids=position_ids, segment_ids=segment_ids,
            attention_mask=attention_mask, return_hidden=return_hidden,
            kv_cache=kv_cache)

    @property
    def checkpoint_dir(self):
        return getattr(self, "_checkpoint_dir", None)

    @checkpoint_dir.setter
    def checkpoint_dir(self, v):
        self._checkpoint_dir = v

    def flops_per_token(self) -> float:
        return self.language_model.flops_per_token()


def build_phi4_mm(config: Optional[dict] = None, **kwargs):
    """YAML-friendly builder (``model._target_``)."""
    if config is not None:
        if hasattr(config, "to_dict"):
            config = config.to_dict()
        cfg = Phi4MMConfig.from_hf_config(config)
    else:
        cfg = Phi4MMConfig()
    return Phi4MMForCausalLM(cfg, **kwargs)
