"""Llama-family decoder (Llama 2/3/3.x, Mistral, Qwen2, Qwen3) — pure-JAX pytree model.

TPU-first re-design of what the reference gets from HF transformers via
``NeMoAutoModelForCausalLM`` (``nemo_automodel/components/_transformers/
auto_model.py:169-414``): parameters are a nested-dict pytree; all decoder
layers are *stacked* along a leading axis and the forward runs one
``lax.scan`` over them — one compiled layer body regardless of depth (fast
XLA compile at 70B scale), with ``jax.checkpoint`` rematerialization applied
to the scan body to trade FLOPs for HBM.

Weights live in param dtype (default fp32), compute runs in ``compute_dtype``
(default bf16, the MXU-native type).  HF safetensors round-trip is defined by
:func:`hf_key_map` in ``automodel_tpu/models/hf_io.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

import zlib

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
from automodel_tpu.ops.remat import checkpoint_name
from automodel_tpu.ops.rotary import apply_rope, rope_parameters


def _stable_hash(name: str) -> int:
    """Process-independent int for rng folds (``hash()`` is salted per
    process — different fold constants per host would desync the traced
    programs on a multi-host mesh)."""
    return zlib.crc32(name.encode())


@dataclasses.dataclass
class LlamaConfig:
    """Superset config covering Llama / Mistral / Qwen2 / Qwen3 (HF field names)."""

    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 16
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 131072
    # HF Phi-3 keeps this top-level (longrope short/long switch point);
    # llama3/yarn carry it inside rope_scaling instead.
    original_max_position_embeddings: Optional[int] = None
    tie_word_embeddings: bool = True
    attention_bias: bool = False       # Qwen2: True
    qk_norm: bool = False              # Qwen3: True (per-head RMSNorm on q/k)
    # Sliding-window attention: Mistral v0.1 applies it globally whenever
    # sliding_window is set; Qwen2 gates it behind use_sliding_window
    # (HF default False) + max_window_layers.
    sliding_window: Optional[int] = None
    use_sliding_window: bool = True
    max_window_layers: Optional[int] = None
    attention_dropout: float = 0.0     # accepted, unused (SFT default 0)
    model_type: str = "llama"
    torch_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "LlamaConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in hf.items() if k in known}
        if hf.get("model_type") == "qwen2":
            kwargs.setdefault("attention_bias", True)
        if str(hf.get("model_type", "")).startswith(("qwen2", "qwen3")):
            # HF Qwen*Config defaults use_sliding_window to False (the
            # serialized config may omit it)
            kwargs.setdefault("use_sliding_window", False)
        if hf.get("model_type") == "qwen3":
            kwargs["qk_norm"] = True
        return cls(**kwargs)


def llama3_2_1b_config() -> "LlamaConfig":
    """The Llama-3.2-1B shape — the BASELINE.md north-star config, which
    ``__graft_entry__.py`` runs."""
    return LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
        head_dim=64, rope_theta=500000.0, tie_word_embeddings=True,
        rope_scaling={
            "rope_type": "llama3", "factor": 32.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
        })


class LlamaForCausalLM:
    """Functional model: ``init`` builds the param pytree, ``__call__`` applies it."""

    # Pipeline-parallel stage splitting is valid for this family: the
    # forward is embed -> uniform layer scan -> norm/head, so the pipelined
    # step (``training/pipeline.py``) can replay it split at layer-slab
    # boundaries.  Families whose forward consumes the stream differently
    # (sequence classification's last-token pooling, VLM feature merges,
    # Gemma/DeepSeek/GPT-2's own loops) MUST NOT inherit True — the gate
    # also rejects any subclass that overrides ``forward_embeds``, and MoE
    # aux losses are rejected at trace time.
    pp_safe = True

    def __init__(
        self,
        config: LlamaConfig,
        param_dtype: jnp.dtype = jnp.float32,
        compute_dtype: jnp.dtype = jnp.bfloat16,
        remat: bool = True,
        remat_policy: Optional[str] = "nothing_saveable",
        weight_only_quant: Optional[str] = None,   # "int8": QLoRA-style base
        scan_unroll: int = 1,
        scan_block: int = 1,
    ):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.remat = remat
        self.remat_policy = remat_policy
        # lax.scan unroll factor for the layer loop: >1 trades compile time
        # for removing while-loop iteration overhead (and at unroll == L,
        # the loop entirely).  Measured NEGATIVE at Llama-1B bench shapes
        # (round 5: unroll 4 was ~7% slower, 16 OOMed) — kept as a knob.
        self.scan_unroll = scan_unroll
        # Layers per checkpointed scan body: block 2 halves the stacked
        # [L, B, S, H] carried-residual memory (the backward recomputes a
        # 2-layer window instead of 1), buying HBM for cheaper-to-save
        # tensors like the splash attention residuals (see
        # ``ops/splash_attention.py`` residual_checkpoint_name).
        self.scan_block = scan_block
        self.quant = None  # set by quantization.fp8.apply_fp8_to_model
        # Weight-only quantized layer kernels (int8 + per-out-channel scale,
        # dequantized on the fly in proj) — the bitsandbytes-QLoRA role
        # (reference ``_peft/lora.py:32,308-314``), TPU-shaped: frozen base
        # weights cost 1 byte/param in HBM, adapters stay bf16/fp32.
        self.weight_only_quant = weight_only_quant
        # Scalar family hooks (Granite-style multipliers); 1.0/None are
        # constant-folded by XLA so the shared decoder pays nothing.
        self._embedding_scale = 1.0     # embeds *= this after lookup
        self._residual_scale = 1.0      # resid + this * block_out
        self._attn_softmax_scale = None  # None -> head_dim ** -0.5
        self._logits_divisor = 1.0      # logits /= this
        # Resolved sliding window for the shared attention core (uniform
        # across layers; per-layer window/full mixes are the Gemma families'
        # own forward).
        sw = getattr(config, "sliding_window", None)
        self._sliding_window = None
        if sw and getattr(config, "use_sliding_window", True):
            # HF semantics: layer i slides only when i >= max_window_layers
            # — so mwl >= L means NO layer slides (the published Qwen2
            # field combo), mwl in (0, L) is a mixed stack this shared
            # decoder cannot express, and mwl None/0 slides everywhere
            # (Mistral v0.1, StarCoder-2).
            mwl = getattr(config, "max_window_layers", None)
            if mwl is None or mwl == 0:
                self._sliding_window = int(sw)
            elif mwl >= config.num_hidden_layers:
                self._sliding_window = None
            else:
                raise NotImplementedError(
                    f"max_window_layers={mwl} inside (0, num_hidden_layers="
                    f"{config.num_hidden_layers}): mixed sliding/full layer "
                    "stacks are not wired for this family")
        self._init_rope(config.head_dim)

    def _init_rope(self, rotary_dim: int) -> None:
        """Short- and (longrope) long-context rope tables + amplitude scale.

        ``longrope`` checkpoints (Phi-3-mini-128k, long Phi-4) carry two
        per-dim rescale lists; HF switches to ``long_factor`` once the
        sequence exceeds ``original_max_position_embeddings``.  S is static
        under jit, so :meth:`_rope_for_len` makes the same choice at trace
        time."""
        cfg = self.config
        max_pos = getattr(cfg, "max_position_embeddings", None)
        # HF longrope threshold: the CONFIG-LEVEL original_max_position_
        # embeddings if present, else max_position_embeddings (the
        # rope_scaling dict's own key is not consulted — see
        # transformers _compute_longrope_parameters).
        orig = getattr(cfg, "original_max_position_embeddings", None)
        self.inv_freq, self.rope_attention_scaling = rope_parameters(
            rotary_dim, cfg.rope_theta, cfg.rope_scaling,
            max_position_embeddings=max_pos,
            original_max_position_embeddings=orig, seq_len=1)
        self._rope_original_max = orig or max_pos
        self._rope_long = None
        rope_type = (cfg.rope_scaling or {}).get(
            "rope_type", (cfg.rope_scaling or {}).get("type", "default"))
        if rope_type == "longrope" and self._rope_original_max:
            self._rope_long = rope_parameters(
                rotary_dim, cfg.rope_theta, cfg.rope_scaling,
                max_position_embeddings=max_pos,
                original_max_position_embeddings=orig,
                seq_len=self._rope_original_max + 1)

    def _rope_tables(self, position_ids):
        """(inv_freq [D/2] possibly traced, attention_scaling float).

        HF's longrope switches tables when ``max(position_ids) + 1``
        exceeds the original context length (``dynamic_rope_update``);
        positions are runtime values here, so the same predicate selects
        between the two static tables with a jnp.where — the attention
        factor is identical in both regimes and stays a python float."""
        if self._rope_long is None:
            return jnp.asarray(self.inv_freq), self.rope_attention_scaling
        long_inv, _ = self._rope_long
        use_long = jnp.max(position_ids) + 1 > self._rope_original_max
        inv = jnp.where(use_long, jnp.asarray(long_inv),
                        jnp.asarray(self.inv_freq))
        return inv, self.rope_attention_scaling

    # -- init --------------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        L, H, I = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        keys = iter(jax.random.split(key, 16))

        def dense(k, shape, layers=True):
            full = (L, *shape) if layers else shape
            return (jax.random.normal(k, full, jnp.float32) * 0.02).astype(self.param_dtype)

        ones = lambda shape: jnp.ones(shape, self.param_dtype)
        attn = {
            "q_proj": {"kernel": dense(next(keys), (H, Hq * D))},
            "k_proj": {"kernel": dense(next(keys), (H, Hk * D))},
            "v_proj": {"kernel": dense(next(keys), (H, Hk * D))},
            "o_proj": {"kernel": dense(next(keys), (Hq * D, H))},
        }
        if cfg.attention_bias:
            attn["q_proj"]["bias"] = jnp.zeros((L, Hq * D), self.param_dtype)
            attn["k_proj"]["bias"] = jnp.zeros((L, Hk * D), self.param_dtype)
            attn["v_proj"]["bias"] = jnp.zeros((L, Hk * D), self.param_dtype)
        if cfg.qk_norm:
            attn["q_norm"] = {"weight": ones((L, D))}
            attn["k_norm"] = {"weight": ones((L, D))}
        params: Dict[str, Any] = {
            "embed_tokens": {
                "embedding": (
                    jax.random.normal(next(keys), (cfg.vocab_size, H), jnp.float32) * 0.02
                ).astype(self.param_dtype)
            },
            "layers": {
                "input_layernorm": {"weight": ones((L, H))},
                "self_attn": attn,
                "post_attention_layernorm": {"weight": ones((L, H))},
                **self._init_ffn(keys, dense),
            },
            "norm": {"weight": ones((H,))},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": dense(next(keys), (H, cfg.vocab_size), layers=False)}
        if self.weight_only_quant == "int8":
            from automodel_tpu.quantization.weight_only import (
                quantize_base_params,
            )

            params = quantize_base_params(params)
        return params

    def _init_ffn(self, keys, dense) -> Dict[str, Any]:
        """Per-layer feed-forward param subtree; MoE families override (so
        the dense MLP stack is never materialized for routed models)."""
        cfg = self.config
        H, I = cfg.hidden_size, cfg.intermediate_size
        return {
            "mlp": {
                "gate_proj": {"kernel": dense(next(keys), (H, I))},
                "up_proj": {"kernel": dense(next(keys), (H, I))},
                "down_proj": {"kernel": dense(next(keys), (I, H))},
            },
        }

    def _ffn_axes(self) -> Dict[str, Any]:
        return {
            "mlp": {
                "gate_proj": {"kernel": ("layers", "embed", "mlp")},
                "up_proj": {"kernel": ("layers", "embed", "mlp")},
                "down_proj": {"kernel": ("layers", "mlp", "embed")},
            },
        }

    def abstract_params(self) -> Dict[str, Any]:
        return jax.eval_shape(self.init, jax.random.key(0))

    def hf_key_map(self):
        """Family key map; int8 weight-only bases swap the quantized-module
        kernels for streaming (int8, scale) spec pairs so HF bf16 checkpoints
        quantize in the read callback (``quantization/weight_only.py``)."""
        from automodel_tpu.models.registry import get_family

        m = get_family(self.config.model_type).key_map_fn(self.config)
        if self.weight_only_quant == "int8":
            from automodel_tpu.quantization.weight_only import (
                quantized_key_map,
            )

            m = quantized_key_map(m)
        return m

    def param_axes(self) -> Dict[str, Any]:
        """Logical axis names per param (consumed by
        ``automodel_tpu.distributed.shardings``) — the TP/FSDP plan as data,
        replacing the reference's per-model DTensor plan registry
        (``distributed/optimized_tp_plans.py:235-243``)."""
        cfg = self.config
        attn: Dict[str, Any] = {
            "q_proj": {"kernel": ("layers", "embed", "heads")},
            "k_proj": {"kernel": ("layers", "embed", "heads")},
            "v_proj": {"kernel": ("layers", "embed", "heads")},
            "o_proj": {"kernel": ("layers", "heads", "embed")},
        }
        if cfg.attention_bias:
            for proj in ("q_proj", "k_proj", "v_proj"):
                attn[proj]["bias"] = ("layers", "heads")
        if cfg.qk_norm:
            attn["q_norm"] = {"weight": ("layers", "head_dim")}
            attn["k_norm"] = {"weight": ("layers", "head_dim")}
        axes: Dict[str, Any] = {
            "embed_tokens": {"embedding": ("vocab", "embed")},
            "layers": {
                "input_layernorm": {"weight": ("layers", "norm")},
                "self_attn": attn,
                "post_attention_layernorm": {"weight": ("layers", "norm")},
                **self._ffn_axes(),
            },
            "norm": {"weight": ("norm",)},
        }
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = {"kernel": ("embed", "vocab")}
        if self.weight_only_quant == "int8":
            # per-out-channel scales: [L, 1, out] shards like the kernel's
            # output axis, contraction axis replicated
            from automodel_tpu.quantization.weight_only import (
                QUANTIZED_MODULES,
            )

            for mod, proj in QUANTIZED_MODULES:
                kaxes = axes["layers"][mod][proj]["kernel"]
                axes["layers"][mod][proj]["scale"] = (
                    kaxes[0], None, kaxes[2])
        return axes

    # -- forward -----------------------------------------------------------
    def _apply_rope(self, q, k, position_ids, inv_freq, rope_scale=1.0):
        """RoPE hook: Qwen2.5-VL overrides with multimodal 3-section rope
        (position_ids [B, S, 3])."""
        return apply_rope(q, k, position_ids, inv_freq,
                          attention_scaling=rope_scale)

    def _norm(self, x, p, eps):
        """Block-norm hook: RMSNorm here; LayerNorm families (StarCoder-2)
        override."""
        return rms_norm(x, p["weight"], eps)

    def _make_proj(self, adapters, adapter_scale, adapter_dropout,
                   dropout_position, dropout_rng, adapter_ids=None):
        """Projection closure shared by every decoder-layer variant:
        int8 weight-only dequant, quantized-compute routing, rank-r LoRA
        bypass (single-adapter or grouped multi-tenant slabs), optional
        bias."""
        cd = self.compute_dtype

        def proj(x, w, name):
            kern = w["kernel"]
            if kern.dtype == jnp.int8:
                # weight-only dequant: XLA fuses the scale-multiply into the
                # matmul's operand read
                kern = kern.astype(cd) * w["scale"].astype(cd)
            else:
                kern = kern.astype(cd)
            y = maybe_qdot(x, kern, self.quant, name)
            if adapters is not None and name in adapters \
                    and adapters[name]["A"].ndim == 3:
                # Multi-tenant serving: per-layer slabs A [E, in, r] /
                # B [E, r, out] with each batch row routed to its own
                # adapter slot by ``adapter_ids`` (slot 0 = base = zeros).
                # Grouped rank-r GEMM through the gmm substrate — see
                # ``ops/lora_gmm.py``.
                from automodel_tpu.ops.lora_gmm import multi_lora_delta

                ab = adapters[name]
                delta = multi_lora_delta(
                    x, ab["A"].astype(cd), ab["B"].astype(cd), adapter_ids)
                y = y + jnp.asarray(adapter_scale, cd) * delta
            elif adapters is not None and name in adapters:
                # Rank-r LoRA bypass: y += s * (x@A)@B — never materializes
                # the merged [in, out] kernel (reference Triton path intent,
                # ``_peft/lora.py:67-214``, done the XLA way).
                ab = adapters[name]
                xa = x
                if adapter_dropout > 0.0 and dropout_rng is not None \
                        and dropout_position == "pre":
                    keep = 1.0 - adapter_dropout
                    m = jax.random.bernoulli(
                        jax.random.fold_in(dropout_rng, _stable_hash(name)),
                        keep, x.shape)
                    xa = jnp.where(m, x / keep, 0.0).astype(x.dtype)
                delta = (xa @ ab["A"].astype(cd)) @ ab["B"].astype(cd)
                if adapter_dropout > 0.0 and dropout_rng is not None \
                        and dropout_position == "post":
                    keep = 1.0 - adapter_dropout
                    m = jax.random.bernoulli(
                        jax.random.fold_in(dropout_rng, _stable_hash(name)),
                        keep, delta.shape)
                    delta = jnp.where(m, delta / keep, 0.0).astype(delta.dtype)
                y = y + jnp.asarray(adapter_scale, cd) * delta
            if "bias" in w:
                y = y + w["bias"].astype(cd)
            return y

        return proj

    def _attention_core(self, q, k, v, segment_ids, attention_mask,
                        kv_cache, local_window_size=None):
        """Train/prefill/decode attention + cache update on rotated q/k."""
        scale = self._attn_softmax_scale
        if kv_cache is not None:
            # Decoding: the cache (``models/layer_scan.py`` has the
            # protocol) stands at this layer of its stacked state.  Write
            # this step's k/v, then attend what the cache holds; the state
            # that comes back is what the layer scan carries on.
            with jax.named_scope("kv_write"):
                state = kv_cache.write(k, v)
            with jax.named_scope("attn_core"):
                attn = kv_cache.attend(
                    q, state, scale=scale,
                    local_window_size=local_window_size)
            return attn, state
        with jax.named_scope("attn_core"):
            attn = attention(
                q, k, v,
                causal=True,
                segment_ids=segment_ids,
                attention_mask=attention_mask,
                scale=scale,
                local_window_size=local_window_size,
            )
        return attn, None

    def _decoder_layer(self, hidden, layer_params, position_ids, segment_ids,
                       attention_mask, inv_freq, adapters=None,
                       adapter_scale=1.0, adapter_dropout=0.0,
                       dropout_position="post", dropout_rng=None,
                       kv_cache=None, rope_scale=1.0, adapter_ids=None):
        cfg = self.config
        B, S, H = hidden.shape
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        p = layer_params
        proj = self._make_proj(adapters, adapter_scale, adapter_dropout,
                               dropout_position, dropout_rng,
                               adapter_ids=adapter_ids)

        # Attention block.  ``attn`` / ``mlp`` (and ``kv_write`` /
        # ``attn_core`` inside) are the names a trace shows for a layer's
        # device time; a family that overrides this method repeats them.
        with jax.named_scope("attn"):
            resid = hidden
            x = self._norm(hidden, p["input_layernorm"], cfg.rms_norm_eps)
            q = proj(x, p["self_attn"]["q_proj"], "self_attn.q_proj").reshape(B, S, Hq, D)
            k = proj(x, p["self_attn"]["k_proj"], "self_attn.k_proj").reshape(B, S, Hk, D)
            v = proj(x, p["self_attn"]["v_proj"], "self_attn.v_proj").reshape(B, S, Hk, D)
            if cfg.qk_norm:
                q = rms_norm(q, p["self_attn"]["q_norm"]["weight"], cfg.rms_norm_eps)
                k = rms_norm(k, p["self_attn"]["k_norm"]["weight"], cfg.rms_norm_eps)
            q, k = self._apply_rope(q, k, position_ids, inv_freq, rope_scale)
            attn, new_cache = self._attention_core(
                q, k, v, segment_ids, attention_mask, kv_cache,
                local_window_size=self._sliding_window)
            attn = checkpoint_name(attn, "attn_core")
            attn = proj(attn.reshape(B, S, Hq * D), p["self_attn"]["o_proj"],
                        "self_attn.o_proj")
            if self._residual_scale != 1.0:
                attn = attn * self._residual_scale
            hidden = resid + attn

        # MLP block (dense SwiGLU here; MoE families override ``_mlp_block``)
        with jax.named_scope("mlp"):
            resid = hidden
            x = self._norm(hidden, p["post_attention_layernorm"],
                           cfg.rms_norm_eps)
            down, moe_aux = self._mlp_block(x, p, proj)
            if self._residual_scale != 1.0:
                down = down * self._residual_scale
            # SP/CP activation layout between blocks (no-op without a
            # sharding ctx)
            out = constrain(resid + down,
                            ("act_batch", "act_seq", "act_embed"))
        return out, new_cache, moe_aux

    def _combine_aux(self, aux_losses):
        """Fold per-layer aux ys (stacked over L by the scan) into the
        scalar ``aux_loss`` output; MoE families override."""
        return jnp.mean(aux_losses)

    def _mlp_block(self, x, p, proj):
        """Post-norm feed-forward of one layer -> ``(out, aux|None)``.
        The seam MoE families replace (routed experts return per-layer
        routing stats for the load-balancing aux loss; dense returns None)."""
        gate = proj(x, p["mlp"]["gate_proj"], "mlp.gate_proj")
        up = proj(x, p["mlp"]["up_proj"], "mlp.up_proj")
        act = checkpoint_name(jax.nn.silu(gate) * up, "mlp_silu")
        down = proj(act, p["mlp"]["down_proj"], "mlp.down_proj")
        return down, None

    def __call__(
        self,
        params: Dict[str, Any],
        input_ids: jnp.ndarray,                 # [B, S] int32
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
        adapter_ids: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Forward pass. Returns ``{"logits": ...}`` or, with ``return_hidden``,
        ``{"hidden_states": ..., "lm_head_kernel": ...}`` for fused linear CE
        (the reference's logits_to_keep path, ``recipes/llm/train_ft.py:436-460``).

        ``adapters``: rank-r LoRA bypass weights, keyed by in-layer module
        path (``"self_attn.q_proj"``) with layer-stacked ``{"A": [L, in, r],
        "B": [L, r, out]}`` values — they ride the layer scan next to the
        base params (see ``automodel_tpu/peft/lora.py``).  Multi-tenant
        serving instead stacks slot slabs ``{"A": [L, E, in, r], "B":
        [L, E, r, out]}`` and routes each batch row via ``adapter_ids``
        (``[B]`` int32, 0 = base model) — see ``serving/adapters.py``.

        ``kv_cache``: a decode cache view (``generation.DenseKVView``, the
        serving engine's ``PagedKVView``; the protocol is in
        ``models/layer_scan.py``) — the result carries the cache's new
        state under ``"kv_cache"``."""
        with jax.named_scope("embed"):
            hidden = params["embed_tokens"]["embedding"][input_ids].astype(
                self.compute_dtype)
            if self._embedding_scale != 1.0:
                hidden = hidden * jnp.asarray(self._embedding_scale,
                                              self.compute_dtype)
        # adapter_ids only reaches forward_embeds when armed — subclasses
        # that override it (deepseek_v3) don't take the kwarg.
        extra = {} if adapter_ids is None else {"adapter_ids": adapter_ids}
        return self.forward_embeds(
            params, hidden, position_ids=position_ids,
            segment_ids=segment_ids, attention_mask=attention_mask,
            return_hidden=return_hidden, adapters=adapters,
            adapter_scale=adapter_scale, adapter_dropout=adapter_dropout,
            adapter_dropout_position=adapter_dropout_position,
            dropout_rng=dropout_rng, kv_cache=kv_cache, **extra)

    def paged_cache_planes(self) -> Dict[str, Any]:
        """What the serving engine's paged pools hold per token and layer,
        plane by plane (``serving/kv_cache.init_paged_pools``): per-head
        keys and values here; a latent-attention family says otherwise."""
        cfg = self.config
        per_head = (cfg.num_key_value_heads, cfg.head_dim)
        return {"k": per_head, "v": per_head}

    def init_kv_cache(self, batch: int, max_len: int,
                      dtype: Optional[Any] = None) -> Dict[str, jnp.ndarray]:
        """The state of ``generate()``'s dense cache:
        ``{"k"|"v": [L, B, max_len, Hk, D]}``."""
        cfg = self.config
        return dense_kv_state(
            cfg.num_hidden_layers, batch, max_len,
            (cfg.num_key_value_heads, cfg.head_dim),
            dtype or self.compute_dtype)

    def forward_embeds(
        self,
        params: Dict[str, Any],
        hidden: jnp.ndarray,                    # [B, S, H] input embeddings
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
        adapter_ids: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Forward from input embeddings — the VLM path (image features
        already merged into the token stream)."""
        cfg = self.config
        B, S = hidden.shape[:2]
        if position_ids is None:
            position_ids = default_position_ids(kv_cache, B, S)
        hidden = constrain(hidden.astype(self.compute_dtype),
                           ("act_batch", "act_seq", "act_embed"))
        inv_freq, rope_scale = self._rope_tables(position_ids)

        # LoRA adapters are stacked [L, ...] like the base layer params:
        # strip the "layers." prefix and scan them alongside.
        layer_adapters = None
        if adapters:
            layer_adapters = {
                k[len("layers."):]: v for k, v in adapters.items()
                if k.startswith("layers.")}
        # Grouped multi-LoRA routing only exists on models whose
        # _decoder_layer takes adapter_ids; subclasses that override it
        # (olmo2, phi4_mm) never see the kwarg unless it's armed.
        extra = {} if adapter_ids is None else {"adapter_ids": adapter_ids}

        def layer(h, layer_params, ad, idx, cache):
            rng = (jax.random.fold_in(dropout_rng, idx)
                   if dropout_rng is not None else None)
            return self._decoder_layer(
                h, layer_params, position_ids, segment_ids, attention_mask,
                inv_freq, adapters=ad, adapter_scale=adapter_scale,
                adapter_dropout=adapter_dropout,
                dropout_position=adapter_dropout_position, dropout_rng=rng,
                kv_cache=cache, rope_scale=rope_scale, **extra)

        hidden, cache_state, (aux_losses,) = scan_layers(
            hidden, [SubStack(params["layers"], layer, layer_adapters)],
            kv_cache, remat=self.remat, remat_policy=self.remat_policy,
            scan_block=self.scan_block, scan_unroll=self.scan_unroll)

        out = norm_and_head(
            hidden, params,
            lambda h, p: self._norm(h, p, cfg.rms_norm_eps),
            tied=cfg.tie_word_embeddings, compute_dtype=self.compute_dtype,
            return_hidden=return_hidden,
            logits_divisor=self._logits_divisor)
        if aux_losses is not None:
            out["aux_loss"] = self._combine_aux(aux_losses)
        if kv_cache is not None:
            out["kv_cache"] = cache_state
        return out

    @property
    def num_params(self) -> int:
        return sum(
            int(jnp.prod(jnp.array(x.shape)))
            for x in jax.tree.leaves(self.abstract_params())
        )

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd = 6N for matmul params)."""
        cfg = self.config
        per_layer = (
            2 * cfg.hidden_size * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * cfg.head_dim
            + 2 * cfg.num_attention_heads * cfg.head_dim * cfg.hidden_size
            + 6 * cfg.hidden_size * cfg.intermediate_size
        )
        embed = 2 * cfg.vocab_size * cfg.hidden_size
        return 3.0 * (cfg.num_hidden_layers * per_layer + embed)

    def attention_flops_per_token(self, seq_len: int,
                                  causal: bool = True) -> float:
        """Training FLOPs/token of the attention score/value matmuls at a
        given row length — the sequence-length-dependent term the 6N
        convention omits.  Causal rows average S/2 attended keys per query;
        QK^T and P@V each cost ``2 * D * Hq * S_avg`` fwd, and training
        counts fwd+bwd as 3x fwd (same convention as
        :meth:`flops_per_token`; the remat re-forward is not credited).
        At 16k this term is ~40% on top of the matmul FLOPs — a tok/s
        without it is not an MFU (VERDICT r4 weak #2)."""
        cfg = self.config
        s_avg = seq_len / 2 if causal else seq_len
        fwd = 2 * 2 * cfg.num_attention_heads * cfg.head_dim * s_avg
        return 3.0 * cfg.num_hidden_layers * fwd
