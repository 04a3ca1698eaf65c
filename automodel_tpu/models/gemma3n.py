"""Gemma-3n family (HF ``model_type: gemma3n`` — e2b/e4b).

The reference fine-tunes Gemma-3n through HF transformers
(``nemo_automodel/components/_transformers/auto_model.py:415``; examples
``examples/vlm_finetune/gemma3n/gemma3n_vl_4b_medpix*.yaml``).  Parity
target for the TEXT decoder is
``transformers/models/gemma3n/modeling_gemma3n.py``, pinned by
``tests/unit_tests/test_gemma3n.py``.

Architecture (what Gemma-3n adds over Gemma-3):

* **AltUp** (alternating updates): ``altup_num_inputs`` parallel hidden
  streams; each layer predicts all streams from the active one via a
  router-modulated coefficient matrix, runs the transformer body on the
  active prediction, then corrects every stream with the innovation.
* **Laurel** (learned augmented residual): a low-rank ``left @ right``
  bypass around attention, rms-normed, averaged with the attention
  residual by ``1/sqrt(2)``.
* **Per-layer embeddings (PLE)**: a second embedding table
  ``[vocab_per_layer, L * H_pl]`` whose per-layer slice gates the
  corrected streams through ``per_layer_input_gate``/``projection``.
* **MatFormer** per-layer ``intermediate_size`` (list form); the scan
  body requires a uniform width, so heterogeneous lists fail loudly.
* **Activation sparsity**: per-layer gaussian top-k relu on the gate
  activations (``activation_sparsity_pattern``), std multiplier from the
  normal ppf, precomputed host-side.
* attention with q/k/v rms-norms (v without scale), **scaling 1.0** (no
  1/sqrt(d)), sliding/full layer types with dual rope bases (Gemma-3
  machinery), final logit softcapping, always-tied lm_head.

KV sharing note: HF shares the last ``num_kv_shared_layers`` layers' k/v
ONLY when a cache object is present — its uncached forward computes every
layer's k/v from that layer's own projections, and the two paths disagree
numerically (measured 0.4 max-abs on a tiny config).  Training is the
uncached path, so this implementation uses per-layer k/v everywhere;
decode therefore matches HF's ``use_cache=False`` greedy argmax, not
``generate()``'s cached variant.

TPU shape: one scanned layer body (stacked ``[L, ...]`` params; per-layer
inputs, sparsity thresholds and layer-type flags ride the scan as data;
sliding vs full branches by ``lax.cond`` so each side sees a static
window, same as Gemma-3).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.models.layer_scan import SubStack, scan_layers
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.rotary import apply_rope, rope_frequencies


def _rms_norm(x, weight=None, eps=1e-6):
    """Gemma-3n RMSNorm: plain ``norm(x) * w`` in fp32 (NOT the zero-
    centered (1+w) form of Gemma-2/3), eps inside the sqrt."""
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


@dataclasses.dataclass
class Gemma3nTextConfig:
    """HF ``Gemma3nTextConfig`` field names (speech fields omitted)."""

    vocab_size: int = 262400
    vocab_size_per_layer_input: int = 262144
    hidden_size: int = 2048
    hidden_size_per_layer_input: int = 256
    intermediate_size: Union[int, List[int]] = 16384
    num_hidden_layers: int = 35
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1_000_000.0
    rope_scaling: Optional[dict] = None
    rope_local_base_freq: float = 10_000.0
    sliding_window: int = 512
    layer_types: Optional[List[str]] = None
    max_position_embeddings: int = 32768
    final_logit_softcapping: Optional[float] = 30.0
    altup_active_idx: int = 0
    altup_coef_clip: Optional[float] = 120.0
    altup_correct_scale: bool = True
    altup_num_inputs: int = 4
    num_kv_shared_layers: int = 15
    laurel_rank: int = 64
    activation_sparsity_pattern: Optional[List[float]] = None
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    model_type: str = "gemma3n_text"
    torch_dtype: str = "bfloat16"

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:
            # HF default: every 5th layer full attention
            self.layer_types = [
                "full_attention" if (i + 1) % 5 == 0 else "sliding_attention"
                for i in range(L)]
        if isinstance(self.intermediate_size, (list, tuple)):
            widths = set(int(x) for x in self.intermediate_size)
            if len(widths) != 1:
                raise NotImplementedError(
                    "gemma3n: heterogeneous per-layer intermediate_size "
                    f"(MatFormer widths {sorted(widths)}) cannot ride one "
                    "scanned layer body; released e2b/e4b configs are "
                    "uniform")
            self.intermediate_size = widths.pop()
        if self.activation_sparsity_pattern is None:
            self.activation_sparsity_pattern = [0.0] * L
        self.activation_sparsity_pattern = [
            float(x) for x in self.activation_sparsity_pattern]

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Gemma3nTextConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


def _ppf(p: float) -> float:
    """Standard-normal inverse CDF (host-side, for the sparsity cutoff)."""
    if p <= 0.0:
        return -math.inf
    return float(math.sqrt(2.0) * float(_erfinv(2.0 * p - 1.0)))


def _erfinv(x: float) -> float:
    # Winitzki's approximation refined by two Newton steps — plenty for the
    # one constant per layer this feeds (HF uses torch's erfinv).
    a = 0.147
    ln1mx2 = math.log(max(1.0 - x * x, 1e-300))
    t = 2.0 / (math.pi * a) + ln1mx2 / 2.0
    y = math.copysign(math.sqrt(math.sqrt(t * t - ln1mx2 / a) - t), x)
    for _ in range(2):
        err = math.erf(y) - x
        y -= err / (2.0 / math.sqrt(math.pi) * math.exp(-y * y))
    return y


class Gemma3nForCausalLM:
    """``model_type: gemma3n_text`` — functional pytree model."""

    def __init__(self, config: Gemma3nTextConfig,
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
        self.inv_freq_global = rope_frequencies(
            config.head_dim, config.rope_theta, config.rope_scaling)
        self.inv_freq_local = rope_frequencies(
            config.head_dim, config.rope_local_base_freq, None)
        # per-layer sparsity cutoff multipliers (normal ppf), host-side
        self._std_mult = np.asarray(
            [_ppf(p) if p > 0.0 else 0.0
             for p in config.activation_sparsity_pattern], np.float32)
        self._sparse_flag = np.asarray(
            [p > 0.0 for p in config.activation_sparsity_pattern])

    # -- init --------------------------------------------------------------
    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        L, H, I = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
        A, R, Hpl = cfg.altup_num_inputs, cfg.laurel_rank, cfg.hidden_size_per_layer_input
        keys = iter(jax.random.split(key, 24))

        def dense(k, shape, stacked=True):
            full = (L, *shape) if stacked else shape
            return (jax.random.normal(k, full, jnp.float32) * 0.02).astype(
                self.param_dtype)

        ones = lambda shape: jnp.ones(shape, self.param_dtype)
        zeros = lambda shape: jnp.zeros(shape, self.param_dtype)
        params: Dict[str, Any] = {
            "embed_tokens": {"embedding": dense(
                next(keys), (cfg.vocab_size, H), stacked=False)},
            "embed_tokens_per_layer": {"embedding": dense(
                next(keys), (cfg.vocab_size_per_layer_input, L * Hpl),
                stacked=False)},
            "per_layer_model_projection": {"kernel": dense(
                next(keys), (H, L * Hpl), stacked=False)},
            "per_layer_projection_norm": {"weight": ones((Hpl,))},
            "altup_projections": {"kernel": dense(
                next(keys), (A - 1, H, H), stacked=False)},
            "altup_unembed_projections": {"kernel": dense(
                next(keys), (A - 1, H, H), stacked=False)},
            "layers": {
                "input_layernorm": {"weight": ones((L, H))},
                "self_attn": {
                    "q_proj": {"kernel": dense(next(keys), (H, Hq * D))},
                    "k_proj": {"kernel": dense(next(keys), (H, Hk * D))},
                    "v_proj": {"kernel": dense(next(keys), (H, Hk * D))},
                    "o_proj": {"kernel": dense(next(keys), (Hq * D, H))},
                    "q_norm": {"weight": ones((L, D))},
                    "k_norm": {"weight": ones((L, D))},
                },
                "post_attention_layernorm": {"weight": ones((L, H))},
                "pre_feedforward_layernorm": {"weight": ones((L, H))},
                "mlp": {
                    "gate_proj": {"kernel": dense(next(keys), (H, I))},
                    "up_proj": {"kernel": dense(next(keys), (H, I))},
                    "down_proj": {"kernel": dense(next(keys), (I, H))},
                },
                "post_feedforward_layernorm": {"weight": ones((L, H))},
                "altup": {
                    "correct_output_scale": zeros((L, H)),
                    "correction_coefs": {"kernel": dense(
                        next(keys), (A, A))},
                    "prediction_coefs": {"kernel": dense(
                        next(keys), (A, A * A))},
                    "modality_router": {"kernel": dense(
                        next(keys), (H, A))},
                    "router_norm": {"weight": ones((L, H))},
                },
                "laurel": {
                    "linear_left": {"kernel": dense(next(keys), (H, R))},
                    "linear_right": {"kernel": dense(next(keys), (R, H))},
                    "post_laurel_norm": {"weight": ones((L, H))},
                },
                "per_layer_input_gate": {"kernel": dense(
                    next(keys), (H, Hpl))},
                "per_layer_projection": {"kernel": dense(
                    next(keys), (Hpl, H))},
                "post_per_layer_input_norm": {"weight": ones((L, H))},
            },
            "norm": {"weight": ones((H,))},
        }
        return params

    def abstract_params(self) -> Dict[str, Any]:
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        lin = lambda a, b: {"kernel": ("layers", a, b)}
        return {
            "embed_tokens": {"embedding": ("vocab", "embed")},
            "embed_tokens_per_layer": {"embedding": ("vocab", None)},
            "per_layer_model_projection": {"kernel": ("embed", None)},
            "per_layer_projection_norm": {"weight": (None,)},
            "altup_projections": {"kernel": (None, "embed", None)},
            "altup_unembed_projections": {"kernel": (None, "embed", None)},
            "layers": {
                "input_layernorm": {"weight": ("layers", "norm")},
                "self_attn": {
                    "q_proj": lin("embed", "heads"),
                    "k_proj": lin("embed", "heads"),
                    "v_proj": lin("embed", "heads"),
                    "o_proj": lin("heads", "embed"),
                    "q_norm": {"weight": ("layers", "head_dim")},
                    "k_norm": {"weight": ("layers", "head_dim")},
                },
                "post_attention_layernorm": {"weight": ("layers", "norm")},
                "pre_feedforward_layernorm": {"weight": ("layers", "norm")},
                "mlp": {
                    "gate_proj": lin("embed", "mlp"),
                    "up_proj": lin("embed", "mlp"),
                    "down_proj": lin("mlp", "embed"),
                },
                "post_feedforward_layernorm": {"weight": ("layers", "norm")},
                "altup": {
                    "correct_output_scale": ("layers", "norm"),
                    "correction_coefs": {"kernel": ("layers", None, None)},
                    "prediction_coefs": {"kernel": ("layers", None, None)},
                    "modality_router": {"kernel": ("layers", "embed", None)},
                    "router_norm": {"weight": ("layers", "norm")},
                },
                "laurel": {
                    "linear_left": lin("embed", None),
                    "linear_right": lin(None, "embed"),
                    "post_laurel_norm": {"weight": ("layers", "norm")},
                },
                "per_layer_input_gate": lin("embed", None),
                "per_layer_projection": lin(None, "embed"),
                "post_per_layer_input_norm": {"weight": ("layers", "norm")},
            },
            "norm": {"weight": ("norm",)},
        }

    # -- altup -------------------------------------------------------------
    def _router_modalities(self, x, p_altup, eps):
        cfg = self.config
        r = _rms_norm(x, p_altup["router_norm"]["weight"], eps)
        r = r * jnp.asarray(1.0 / cfg.hidden_size, r.dtype)
        routed = r @ p_altup["modality_router"]["kernel"].astype(r.dtype)
        return jnp.tanh(routed.astype(jnp.float32)).astype(x.dtype)

    def _altup_predict(self, h, p_altup, eps):
        """h: [A, B, S, H] -> predictions [A, B, S, H]."""
        cfg = self.config
        A = cfg.altup_num_inputs
        mods = self._router_modalities(h[cfg.altup_active_idx], p_altup, eps)
        pc = mods @ p_altup["prediction_coefs"]["kernel"].astype(mods.dtype)
        pcr = pc.reshape(*mods.shape[:-1], A, A)          # [B, S, j, a]
        pred = jnp.einsum("bsja,absh->jbsh", pcr.astype(jnp.float32),
                          h.astype(jnp.float32))
        return (pred.astype(h.dtype) + h), mods

    def _altup_correct(self, predictions, activated, p_altup, eps):
        cfg = self.config
        mods = self._router_modalities(activated, p_altup, eps)
        innovation = activated - predictions[cfg.altup_active_idx]
        coefs = (mods @ p_altup["correction_coefs"]["kernel"].astype(
            mods.dtype)) + 1.0                             # [B, S, A]
        coefs = jnp.moveaxis(coefs, -1, 0)[..., None]      # [A, B, S, 1]
        return predictions + coefs * innovation[None]

    # -- layer body --------------------------------------------------------
    def _layer(self, h, xs, position_ids, segment_ids, attention_mask):
        cfg = self.config
        p, per_layer_in, inv_freq, is_full, std_mult, is_sparse = xs
        eps = cfg.rms_norm_eps
        cd = self.compute_dtype
        A, B, S, H = h.shape
        D, Hq, Hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads

        predictions, _ = self._altup_predict(h, p["altup"], eps)
        active = predictions[cfg.altup_active_idx]
        active_normed = _rms_norm(active, p["input_layernorm"]["weight"], eps)

        # laurel low-rank bypass
        lo = active_normed @ p["laurel"]["linear_left"]["kernel"].astype(cd)
        lo = lo @ p["laurel"]["linear_right"]["kernel"].astype(cd)
        laurel_out = active_normed + _rms_norm(
            lo, p["laurel"]["post_laurel_norm"]["weight"], eps)

        # attention: q/k/v norms, scaling 1.0, sliding/full by lax.cond
        q = (active_normed @ p["self_attn"]["q_proj"]["kernel"].astype(cd)
             ).reshape(B, S, Hq, D)
        k = (active_normed @ p["self_attn"]["k_proj"]["kernel"].astype(cd)
             ).reshape(B, S, Hk, D)
        v = (active_normed @ p["self_attn"]["v_proj"]["kernel"].astype(cd)
             ).reshape(B, S, Hk, D)
        q = _rms_norm(q, p["self_attn"]["q_norm"]["weight"], eps)
        k = _rms_norm(k, p["self_attn"]["k_norm"]["weight"], eps)
        v = _rms_norm(v, None, eps)
        q, k = apply_rope(q, k, position_ids, inv_freq)
        sliding = int(cfg.sliding_window)

        def full_attn(q, k, v):
            return attention(q, k, v, causal=True, scale=1.0,
                             segment_ids=segment_ids,
                             attention_mask=attention_mask)

        def window_attn(q, k, v):
            return attention(q, k, v, causal=True, scale=1.0,
                             segment_ids=segment_ids,
                             attention_mask=attention_mask,
                             local_window_size=sliding)

        attn = lax.cond(is_full, full_attn, window_attn, q, k, v)
        attn = (attn.reshape(B, S, Hq * D)
                @ p["self_attn"]["o_proj"]["kernel"].astype(cd))
        attn = _rms_norm(attn, p["post_attention_layernorm"]["weight"], eps)

        attn_gated = active + attn
        attn_laurel = ((attn_gated + laurel_out)
                       * jnp.asarray(1.0 / math.sqrt(2.0), cd))

        x = _rms_norm(attn_laurel, p["pre_feedforward_layernorm"]["weight"],
                      eps)
        gate = x @ p["mlp"]["gate_proj"]["kernel"].astype(cd)

        def sparse_gate(g):
            g32 = g.astype(jnp.float32)
            mean = jnp.mean(g32, axis=-1, keepdims=True)
            std = jnp.std(g32, axis=-1, keepdims=True)
            cutoff = mean + std * std_mult
            return jax.nn.relu(g32 - cutoff).astype(g.dtype)

        gate = lax.cond(is_sparse, sparse_gate, lambda g: g, gate)
        up = x @ p["mlp"]["up_proj"]["kernel"].astype(cd)
        down = (jax.nn.gelu(gate, approximate=True) * up
                ) @ p["mlp"]["down_proj"]["kernel"].astype(cd)
        ffw = _rms_norm(down, p["post_feedforward_layernorm"]["weight"], eps)
        activated = attn_laurel + ffw

        corrected = self._altup_correct(predictions, activated, p["altup"],
                                        eps)
        first = corrected[cfg.altup_active_idx]
        if cfg.altup_correct_scale:
            first = first * p["altup"]["correct_output_scale"].astype(
                first.dtype)
        g = jax.nn.gelu(
            first @ p["per_layer_input_gate"]["kernel"].astype(cd),
            approximate=True)
        g = g * per_layer_in
        g = g @ p["per_layer_projection"]["kernel"].astype(cd)
        g = _rms_norm(g, p["post_per_layer_input_norm"]["weight"], eps)
        corrected = corrected.at[1:].add(g[None].astype(corrected.dtype))
        return constrain(corrected, (None, "act_batch", "act_seq",
                                     "act_embed"))

    # -- forward -----------------------------------------------------------
    def _per_layer_inputs(self, params, input_ids, embeds):
        cfg = self.config
        cd = self.compute_dtype
        B, S = input_ids.shape
        L, Hpl = cfg.num_hidden_layers, cfg.hidden_size_per_layer_input
        # PLE token embeddings (own scale), 0 outside the per-layer vocab
        in_range = input_ids < cfg.vocab_size_per_layer_input
        safe_ids = jnp.where(in_range, input_ids, 0)
        ple = params["embed_tokens_per_layer"]["embedding"][safe_ids].astype(
            cd) * jnp.asarray(float(Hpl) ** 0.5, cd)
        ple = jnp.where(in_range[..., None], ple, 0.0).reshape(B, S, L, Hpl)
        proj = (embeds @ params["per_layer_model_projection"][
            "kernel"].astype(cd)) * jnp.asarray(
                float(cfg.hidden_size) ** -0.5, cd)
        proj = proj.reshape(B, S, L, Hpl)
        proj = _rms_norm(proj, params["per_layer_projection_norm"]["weight"],
                         cfg.rms_norm_eps)
        return (proj + ple) * jnp.asarray(1.0 / math.sqrt(2.0), cd)

    def _expand_streams(self, h0, kernels):
        """[B, S, H] -> [A, B, S, H]: magnitude-matched projections."""
        cfg = self.config
        target = jnp.sqrt(jnp.mean(
            h0.astype(jnp.float32) ** 2, axis=-1, keepdims=True))
        streams = [h0]
        for i in range(cfg.altup_num_inputs - 1):
            proj = (h0 @ kernels[i].astype(h0.dtype)).astype(jnp.float32)
            mag = jnp.sqrt(jnp.maximum(
                jnp.mean(proj ** 2, axis=-1, keepdims=True), 1e-5))
            streams.append((proj * target / mag).astype(h0.dtype))
        return jnp.stack(streams, axis=0)

    def _merge_streams(self, h, kernels):
        cfg = self.config
        target = jnp.sqrt(jnp.mean(
            h[0].astype(jnp.float32) ** 2, axis=-1, keepdims=True))
        streams = [h[0]]
        for i in range(cfg.altup_num_inputs - 1):
            proj = (h[i + 1] @ kernels[i].astype(h.dtype)).astype(
                jnp.float32)
            mag = jnp.sqrt(jnp.maximum(
                jnp.mean(proj ** 2, axis=-1, keepdims=True), 1e-5))
            streams.append((proj * target / mag).astype(h.dtype))
        return jnp.mean(jnp.stack(streams, axis=0), axis=0)

    def __call__(self, params, input_ids, position_ids=None, segment_ids=None,
                 attention_mask=None, return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        cd = self.compute_dtype
        B, S = input_ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S))
        if kv_cache is not None:
            raise NotImplementedError(
                "gemma3n decode uses the cacheless forward (see the KV "
                "sharing note in the module docstring); generation runs "
                "full-prefix forwards")

        embeds = params["embed_tokens"]["embedding"][input_ids].astype(cd)
        embeds = embeds * jnp.asarray(float(cfg.hidden_size) ** 0.5, cd)
        return self.forward_tokens_and_embeds(
            params, input_ids, embeds, position_ids=position_ids,
            segment_ids=segment_ids, attention_mask=attention_mask,
            return_hidden=return_hidden)

    def forward_tokens_and_embeds(self, params, input_ids, embeds,
                                  position_ids=None, segment_ids=None,
                                  attention_mask=None,
                                  return_hidden: bool = False
                                  ) -> Dict[str, jnp.ndarray]:
        """Forward from PRE-BUILT (already scattered) embeddings while the
        per-layer-embedding table is still keyed by ``input_ids`` — the
        entry the VLM wrapper uses (``_per_layer_inputs`` zeroes ids
        outside the per-layer vocab, which covers multimodal placeholder
        ids)."""
        cfg = self.config
        cd = self.compute_dtype
        B, S = input_ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S))
        per_layer = self._per_layer_inputs(params, input_ids,
                                           embeds.astype(cd))
        h = self._expand_streams(embeds.astype(cd),
                                 params["altup_projections"]["kernel"])
        is_full = jnp.asarray(
            [t == "full_attention" for t in cfg.layer_types])
        inv_freqs = jnp.where(
            is_full[:, None], jnp.asarray(self.inv_freq_global)[None],
            jnp.asarray(self.inv_freq_local)[None])
        per_layer_l = jnp.moveaxis(per_layer, 2, 0)
        std_mult = jnp.asarray(self._std_mult)
        sparse = jnp.asarray(self._sparse_flag)

        def layer(h, p, xs, idx, cache):
            return self._layer(h, (p, *xs), position_ids, segment_ids,
                               attention_mask), None, None

        h, _, _ = scan_layers(
            h, [SubStack(params["layers"], layer,
                         (per_layer_l, inv_freqs, is_full, std_mult,
                          sparse))],
            remat=self.remat, remat_policy=self.remat_policy)
        hidden = self._merge_streams(
            h, params["altup_unembed_projections"]["kernel"])
        hidden = _rms_norm(hidden, params["norm"]["weight"],
                           cfg.rms_norm_eps)
        lm_kernel = params["embed_tokens"]["embedding"].T
        if return_hidden:
            if cfg.final_logit_softcapping is not None:
                # see gemma3.py: the fused hidden@lm_head loss path cannot
                # apply the tanh cap
                raise NotImplementedError(
                    "final_logit_softcapping is incompatible with hidden-"
                    "state losses (FusedLinearCrossEntropy): use a logits "
                    "loss (e.g. MaskedCrossEntropy) for gemma3n")
            return {"hidden_states": hidden, "lm_head_kernel": lm_kernel}
        logits = hidden @ lm_kernel.astype(cd)
        if cfg.final_logit_softcapping is not None:
            cap = jnp.asarray(cfg.final_logit_softcapping, jnp.float32)
            logits = (jnp.tanh(logits.astype(jnp.float32) / cap)
                      * cap).astype(logits.dtype)
        return {"logits": constrain(
            logits, ("act_batch", "act_seq_nosp", "act_vocab"))}

    @property
    def num_params(self) -> int:
        return sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(self.abstract_params()))

    def flops_per_token(self) -> float:
        cfg = self.config
        H, D = cfg.hidden_size, cfg.head_dim
        Hpl = cfg.hidden_size_per_layer_input
        A, R = cfg.altup_num_inputs, cfg.laurel_rank
        attn = (2 * H * (cfg.num_attention_heads
                         + 2 * cfg.num_key_value_heads) * D
                + 2 * cfg.num_attention_heads * D * H)
        ffn = 6 * H * cfg.intermediate_size
        extras = (2 * H * R * 2            # laurel
                  + 2 * H * A * (1 + A)    # altup router + coefs
                  + 2 * H * Hpl * 2)       # per-layer gate + projection
        embed = 2 * cfg.vocab_size * H
        return 3.0 * (cfg.num_hidden_layers * (attn + ffn + extras) + embed)


# ---------------------------------------------------------------------------
# Multimodal (vision) wrapper
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Gemma3nVisionConfig:
    """HF ``Gemma3nVisionConfig`` interface fields plus native-tower knobs.

    HF's tower is a timm MobileNetV5 (``architecture:
    mobilenetv5_300m_enc``) — timm is not a dependency here, so the tower
    is a NATIVE MobileNet-style conv encoder (stem + scanned
    inverted-residual blocks + 1x1 head, average-pooled to the soft-token
    grid).  The language-side contract (soft tokens ``[N,
    vision_soft_tokens_per_image, hidden_size]`` through the multimodal
    embedder) is HF's; the tower weights are ours alone, so exports carry
    them under ``model.vision_tower.native.*`` (HF loaders warn and
    random-init their timm tower, same as the Phi-4-MM vision precedent).
    """

    hidden_size: int = 2048
    vocab_size: int = 128
    vocab_offset: int = 262144
    rms_norm_eps: float = 1e-6
    # native tower knobs (not HF fields)
    in_channels: int = 3
    stem_channels: int = 64
    depth: int = 4
    expand_ratio: int = 2
    model_type: str = "gemma3n_vision"

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Gemma3nVisionConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


@dataclasses.dataclass
class Gemma3nVLConfig:
    """HF multimodal ``Gemma3nConfig`` (model_type "gemma3n")."""

    text_config: Any = None
    vision_config: Any = None
    image_token_id: int = 262145
    vision_soft_tokens_per_image: int = 256
    model_type: str = "gemma3n"
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if isinstance(self.text_config, dict):
            self.text_config = Gemma3nTextConfig.from_hf_config(
                self.text_config)
        if isinstance(self.vision_config, dict):
            self.vision_config = Gemma3nVisionConfig.from_hf_config(
                self.vision_config)
        self.text_config = self.text_config or Gemma3nTextConfig()
        self.vision_config = self.vision_config or Gemma3nVisionConfig()
        g = int(math.isqrt(self.vision_soft_tokens_per_image))
        if g * g != self.vision_soft_tokens_per_image:
            raise ValueError(
                "vision_soft_tokens_per_image must be a square grid; got "
                f"{self.vision_soft_tokens_per_image}")

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Gemma3nVLConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})


class Gemma3nVisionTower:
    """Native MobileNet-style encoder: NHWC images -> soft tokens
    ``[N, soft_tokens, vision_hidden]`` (see Gemma3nVisionConfig)."""

    def __init__(self, config: Gemma3nVisionConfig, soft_tokens: int,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16):
        self.config = config
        self.soft_tokens = int(soft_tokens)
        self.grid = int(math.isqrt(self.soft_tokens))
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        C, E = cfg.stem_channels, cfg.expand_ratio
        keys = iter(jax.random.split(key, 8))

        def conv(k, shape):
            fan_in = float(np.prod(shape[:-1]))
            return (jax.random.normal(k, shape, jnp.float32)
                    * (2.0 / fan_in) ** 0.5).astype(self.param_dtype)

        D = cfg.depth
        return {
            "stem": {"kernel": conv(next(keys),
                                    (3, 3, cfg.in_channels, C))},
            "blocks": {
                "expand": {"kernel": (jax.random.normal(
                    next(keys), (D, 1, 1, C, E * C), jnp.float32)
                    * 0.05).astype(self.param_dtype)},
                "depthwise": {"kernel": (jax.random.normal(
                    next(keys), (D, 3, 3, 1, E * C), jnp.float32)
                    * 0.1).astype(self.param_dtype)},
                "project": {"kernel": (jax.random.normal(
                    next(keys), (D, 1, 1, E * C, C), jnp.float32)
                    * 0.05).astype(self.param_dtype)},
                "norm": {"weight": jnp.ones((D, C), self.param_dtype)},
            },
            "head": {"kernel": conv(next(keys),
                                    (1, 1, C, cfg.hidden_size))},
        }

    def param_axes(self) -> Dict[str, Any]:
        return {
            "stem": {"kernel": (None, None, None, None)},
            "blocks": {
                "expand": {"kernel": ("layers", None, None, None, None)},
                "depthwise": {"kernel": ("layers", None, None, None, None)},
                "project": {"kernel": ("layers", None, None, None, None)},
                "norm": {"weight": ("layers", None)},
            },
            "head": {"kernel": (None, None, None, "embed")},
        }

    def __call__(self, params, images: jnp.ndarray) -> jnp.ndarray:
        """``images`` [N, H, W, C] float -> [N, soft_tokens, hidden]."""
        cfg = self.config
        cd = self.compute_dtype
        dn = ("NHWC", "HWIO", "NHWC")
        x = lax.conv_general_dilated(
            images.astype(cd), params["stem"]["kernel"].astype(cd),
            window_strides=(2, 2), padding="SAME", dimension_numbers=dn)
        x = jax.nn.gelu(x, approximate=True)

        def block(x, p):
            y = lax.conv_general_dilated(
                x, p["expand"]["kernel"].astype(cd), (1, 1), "SAME",
                dimension_numbers=dn)
            y = jax.nn.gelu(y, approximate=True)
            y = lax.conv_general_dilated(
                y, p["depthwise"]["kernel"].astype(cd), (1, 1), "SAME",
                dimension_numbers=dn,
                feature_group_count=y.shape[-1])
            y = jax.nn.gelu(y, approximate=True)
            y = lax.conv_general_dilated(
                y, p["project"]["kernel"].astype(cd), (1, 1), "SAME",
                dimension_numbers=dn)
            y = _rms_norm(y, p["norm"]["weight"], cfg.rms_norm_eps)
            return x + y, None

        x, _ = lax.scan(block, x, params["blocks"])
        x = lax.conv_general_dilated(
            x, params["head"]["kernel"].astype(cd), (1, 1), "SAME",
            dimension_numbers=dn)
        # adaptive average pool to the soft-token grid
        N, H, W, D = x.shape
        g = self.grid
        if H % g or W % g:
            raise ValueError(
                f"vision input {H}x{W} must be divisible by the soft-token "
                f"grid {g}x{g} after the stride-2 stem")
        x = x.reshape(N, g, H // g, g, W // g, D).mean(axis=(2, 4))
        return x.reshape(N, g * g, D)


class Gemma3nForConditionalGeneration:
    """``model._target_: automodel_tpu.models.gemma3n.build_gemma3n_vl``

    HF semantics for the language side: multimodal placeholder ids (>=
    ``embed_vision.vocab_offset``) embed through the embedder's HARD path;
    image features (native tower soft tokens, scaled by
    ``sqrt(vision_hidden)``) run the SOFT path and scatter onto
    ``image_token_id`` positions; per-layer embeddings for placeholder ids
    are zero (outside the per-layer vocab).  Audio is out of scope — audio
    batch keys fail loudly at the train step (no ``extra_batch_keys``)."""

    def __init__(self, config: Gemma3nVLConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True, **kwargs):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.language_model = Gemma3nForCausalLM(
            config.text_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat, **kwargs)
        self.vision_tower = Gemma3nVisionTower(
            config.vision_config, config.vision_soft_tokens_per_image,
            param_dtype=param_dtype, compute_dtype=compute_dtype)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        kt, kv, ke = jax.random.split(key, 3)
        vc = self.config.vision_config
        tc = self.config.text_config
        k1, k2 = jax.random.split(ke)
        embed_vision = {
            "embedding": {"embedding": (jax.random.normal(
                k1, (vc.vocab_size, vc.hidden_size), jnp.float32)
                * 0.02).astype(self.param_dtype)},
            "hard_embedding_norm": {"weight": jnp.ones(
                (vc.hidden_size,), self.param_dtype)},
            "soft_embedding_norm": {"weight": jnp.ones(
                (vc.hidden_size,), self.param_dtype)},
            "embedding_projection": {"kernel": (jax.random.normal(
                k2, (vc.hidden_size, tc.hidden_size), jnp.float32)
                * 0.02).astype(self.param_dtype)},
        }
        return {"language_model": self.language_model.init(kt),
                "vision_tower": self.vision_tower.init(kv),
                "embed_vision": embed_vision}

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        return {
            "language_model": self.language_model.param_axes(),
            "vision_tower": self.vision_tower.param_axes(),
            "embed_vision": {
                "embedding": {"embedding": ("vocab", None)},
                "hard_embedding_norm": {"weight": (None,)},
                "soft_embedding_norm": {"weight": (None,)},
                "embedding_projection": {"kernel": (None, "embed")},
            },
        }

    def _embed_soft(self, p_emb, soft: jnp.ndarray) -> jnp.ndarray:
        vc = self.config.vision_config
        y = _rms_norm(soft, p_emb["soft_embedding_norm"]["weight"],
                      vc.rms_norm_eps)
        y = y @ p_emb["embedding_projection"]["kernel"].astype(y.dtype)
        return _rms_norm(y, None, vc.rms_norm_eps)

    def _embed_hard(self, p_emb, ids: jnp.ndarray) -> jnp.ndarray:
        vc = self.config.vision_config
        local = jnp.clip(ids - vc.vocab_offset, 0, vc.vocab_size - 1)
        y = p_emb["embedding"]["embedding"][local].astype(self.compute_dtype)
        y = _rms_norm(y, p_emb["hard_embedding_norm"]["weight"],
                      vc.rms_norm_eps)
        y = y @ p_emb["embedding_projection"]["kernel"].astype(y.dtype)
        return _rms_norm(y, None, vc.rms_norm_eps)

    def encode_images(self, params, pixel_values: jnp.ndarray) -> jnp.ndarray:
        """[N, H, W, C] images -> flat soft-token embeds
        [N * soft_tokens, text_hidden] in language-model space."""
        vc = self.config.vision_config
        soft = self.vision_tower(params["vision_tower"], pixel_values)
        soft = soft * jnp.asarray(float(vc.hidden_size) ** 0.5, soft.dtype)
        emb = self._embed_soft(params["embed_vision"], soft)
        return emb.reshape(-1, emb.shape[-1])

    def __call__(self, params, input_ids, pixel_values=None,
                 position_ids=None, segment_ids=None, attention_mask=None,
                 return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        tc = cfg.text_config
        cd = self.compute_dtype
        lp = params["language_model"]
        B, S = input_ids.shape
        if kv_cache is not None:
            raise NotImplementedError(
                "gemma3n decode uses the cacheless forward (see the KV "
                "sharing note in the module docstring); generation runs "
                "full-prefix forwards")
        # text embeddings (scaled); multimodal placeholder ids embed via the
        # embedder's hard path (HF: ids >= vocab_offset)
        safe = jnp.clip(input_ids, 0, tc.vocab_size - 1)
        embeds = lp["embed_tokens"]["embedding"][safe].astype(cd)
        embeds = embeds * jnp.asarray(float(tc.hidden_size) ** 0.5, cd)
        is_mm = input_ids >= cfg.vision_config.vocab_offset
        hard = self._embed_hard(params["embed_vision"], input_ids)
        embeds = jnp.where(is_mm[..., None], hard.astype(cd), embeds)
        if pixel_values is not None:
            if pixel_values.ndim == 5:     # [B, I, H, W, C] per-row slots
                flat_imgs = pixel_values.reshape(
                    -1, *pixel_values.shape[2:])
            else:
                flat_imgs = pixel_values
            feats = self.encode_images(params, flat_imgs)
            is_img = (input_ids == cfg.image_token_id).reshape(-1)
            idx = jnp.clip(jnp.cumsum(is_img) - 1, 0, feats.shape[0] - 1)
            gathered = feats[idx].reshape(B, S, -1)
            embeds = jnp.where(is_img.reshape(B, S)[..., None],
                               gathered.astype(cd), embeds)
        return self.language_model.forward_tokens_and_embeds(
            lp, input_ids, embeds, position_ids=position_ids,
            segment_ids=segment_ids, attention_mask=attention_mask,
            return_hidden=return_hidden)

    @property
    def checkpoint_dir(self):
        return getattr(self, "_checkpoint_dir", None)

    @checkpoint_dir.setter
    def checkpoint_dir(self, v):
        self._checkpoint_dir = v

    def flops_per_token(self) -> float:
        return self.language_model.flops_per_token()


def build_gemma3n_vl(config: Optional[dict] = None, **kwargs):
    """YAML-friendly builder (``model._target_``)."""
    if config is not None:
        if hasattr(config, "to_dict"):
            config = config.to_dict()
        cfg = Gemma3nVLConfig.from_hf_config(config)
    else:
        cfg = Gemma3nVLConfig()
    return Gemma3nForConditionalGeneration(cfg, **kwargs)


def build_gemma3n_text(config: Optional[dict] = None, **kwargs):
    """YAML-friendly builder for the text-only family."""
    if config is not None:
        if hasattr(config, "to_dict"):
            config = config.to_dict()
        cfg = Gemma3nTextConfig.from_hf_config(config)
    else:
        cfg = Gemma3nTextConfig()
    return Gemma3nForCausalLM(cfg, **kwargs)
