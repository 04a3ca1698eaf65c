"""Qwen2.5-VL: windowed ViT + M-RoPE decoder, built TPU-first.

What the reference gets from HF transformers via
``NeMoAutoModelForImageTextToText`` (``nemo_automodel/components/
_transformers/auto_model.py:415``) for the Qwen2.5-VL family — paired with
its collator (``components/datasets/vlm/collate_fns.py:120-148``).  Parity
target: ``transformers/models/qwen2_5_vl/modeling_qwen2_5_vl.py``.

TPU re-design (the GPU code is shaped by varlen flash attention; XLA wants
static shapes and batched matmuls):

* **Static image grid.**  The vision tower is built for a fixed patch grid
  ``(t, h, w)`` per call (dynamic-resolution batches group by grid at the
  collator).  Everything grid-derived — window partition indices, their
  inverse permutation, pad masks, and the 2D rotary tables — is computed
  host-side in numpy once per grid and baked into the program as constants.
* **Batched window attention.**  HF reorders the patch stream so windows are
  contiguous and runs varlen flash with ``cu_seqlens``; here windows become
  one more BATCH dim: a static gather lifts ``[N, L, D]`` to
  ``[N * nW, wlen, D]`` (pad slots masked), one batched non-causal attention
  runs on the MXU, and the inverse gather restores canonical order.  Full-
  attention blocks (``fullatt_block_indexes``) attend over the whole image.
  Per-layer routing is a ``lax.cond`` on a flag riding the layer scan, so
  one compiled body serves the whole depth (the Gemma-3 sliding pattern).
* **Canonical patch order.**  HF permutes patches into window order up
  front, runs the merger in that order, and argsorts back.  Window order
  only matters INSIDE attention, so we keep the processor's canonical
  (merge-unit-grouped) order end to end: rope tables attach per patch, the
  pointwise merger needs no reorder, and the window permutation lives
  entirely inside the two static gathers.
* **M-RoPE** (temporal/height/width channel sections) is one einsum over a
  static section-selector matrix; position ids ``[B, S, 3]`` are computed by
  the collator (HF's ``get_rope_index`` is data-dependent Python — host
  work, not device work; see ``datasets/vlm/qwen_rope.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.attention import attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.remat import resolve_remat_policy


@dataclasses.dataclass
class Qwen25VisionConfig:
    """HF ``Qwen2_5_VLVisionConfig`` field names."""

    depth: int = 32
    hidden_size: int = 1280
    intermediate_size: int = 3420
    num_heads: int = 16
    in_channels: int = 3
    patch_size: int = 14
    temporal_patch_size: int = 2
    spatial_merge_size: int = 2
    window_size: int = 112
    fullatt_block_indexes: Tuple[int, ...] = (7, 15, 23, 31)
    out_hidden_size: int = 3584
    tokens_per_second: int = 2
    model_type: str = "qwen2_5_vl"

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Qwen25VisionConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def patch_dim(self) -> int:
        return (self.in_channels * self.temporal_patch_size
                * self.patch_size ** 2)


@dataclasses.dataclass
class Qwen25VLTextConfig(LlamaConfig):
    """Standalone text config (HF ``Qwen2_5_VLTextConfig``): the Qwen2
    architecture — q/k/v biases on — with M-RoPE sections in rope_scaling."""

    def __post_init__(self):
        super().__post_init__()
        self.model_type = "qwen2_5_vl_text"

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Qwen25VLTextConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in hf.items() if k in known}
        kwargs.setdefault("attention_bias", True)
        return cls(**kwargs)


def _mrope_section_of(config: LlamaConfig) -> Tuple[int, ...]:
    rs = config.rope_scaling or {}
    return tuple(rs.get("mrope_section", (16, 24, 24)))


@dataclasses.dataclass
class Qwen25VLConfig:
    """HF ``Qwen2_5_VLConfig``: nested text + vision configs."""

    text_config: Any = None
    vision_config: Any = None
    image_token_id: int = 151655
    video_token_id: int = 151656
    vision_start_token_id: int = 151652
    model_type: str = "qwen2_5_vl"
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if isinstance(self.text_config, dict):
            self.text_config = Qwen25VLTextConfig.from_hf_config(
                self.text_config)
        if isinstance(self.vision_config, dict):
            self.vision_config = Qwen25VisionConfig.from_hf_config(
                self.vision_config)
        self.text_config = self.text_config or Qwen25VLTextConfig(
            attention_bias=True)
        self.vision_config = self.vision_config or Qwen25VisionConfig()
        self.text_config.tie_word_embeddings = self.tie_word_embeddings

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "Qwen25VLConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in known})

    @property
    def mrope_section(self) -> Tuple[int, ...]:
        return _mrope_section_of(self.text_config)


# ---------------------------------------------------------------------------
# Static grid geometry (host-side, cached per grid)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=16)
def _grid_layout(grid: Tuple[int, int, int], spatial_merge_size: int,
                 window_size: int, patch_size: int, head_dim: int):
    """All grid-derived constants, canonical (merge-unit-grouped) order.

    Returns dict of numpy arrays: ``gather`` [nW, wlen_p] patch indices into
    the canonical stream (pads -> 0), ``valid`` [nW, wlen_p] mask,
    ``scatter`` [L] inverse permutation (windowed flat -> canonical), and
    ``cos``/``sin`` [L, head_dim] 2D rotary tables.
    """
    t, h, w = grid
    m = spatial_merge_size
    llm_h, llm_w = h // m, w // m
    unit = m * m
    n_units = t * llm_h * llm_w
    L = n_units * unit

    # window partition over merge units (HF get_window_index semantics;
    # exact-multiple grids get zero pad instead of a full empty window —
    # those windows are all-pad there and contribute nothing anyway)
    wlen = window_size // m // patch_size
    pad_h, pad_w = (-llm_h) % wlen, (-llm_w) % wlen
    nwh, nww = (llm_h + pad_h) // wlen, (llm_w + pad_w) // wlen
    idx = np.arange(n_units).reshape(t, llm_h, llm_w)
    idx = np.pad(idx, ((0, 0), (0, pad_h), (0, pad_w)), constant_values=-1)
    idx = idx.reshape(t, nwh, wlen, nww, wlen).transpose(0, 1, 3, 2, 4)
    win_units = idx.reshape(-1, wlen * wlen)                 # [nW, wu]
    n_win = win_units.shape[0]
    # units -> patches: unit u covers patches [u*unit, (u+1)*unit)
    valid_u = win_units >= 0                                 # [nW, wu]
    gather = (np.where(valid_u, win_units, 0)[..., None] * unit
              + np.arange(unit)[None, None, :])              # [nW, wu, unit]
    gather = gather.reshape(n_win, -1)                       # [nW, wlen_p]
    valid = np.repeat(valid_u, unit, axis=1)                 # [nW, wlen_p]
    # inverse: canonical patch p sits at exactly one windowed slot
    scatter = np.zeros(L, np.int64)
    flat_gather, flat_valid = gather.reshape(-1), valid.reshape(-1)
    scatter[flat_gather[flat_valid]] = np.nonzero(flat_valid)[0]

    # 2D rotary tables in canonical order (HF rot_pos_emb): per patch, h and
    # w coordinates each rotate half the head dim
    hpos = np.arange(h)[:, None] * np.ones((1, w), np.int64)
    wpos = np.ones((h, 1), np.int64) * np.arange(w)[None, :]

    def to_units(x):
        x = x.reshape(llm_h, m, llm_w, m).transpose(0, 2, 1, 3).reshape(-1)
        return np.tile(x, t)

    hpos, wpos = to_units(hpos), to_units(wpos)              # [L]
    inv_freq = 1.0 / (10000.0 ** (
        np.arange(0, head_dim // 2, 2, np.float64) / (head_dim // 2)))
    freqs = np.concatenate(
        [hpos[:, None] * inv_freq[None, :],
         wpos[:, None] * inv_freq[None, :]], axis=-1)        # [L, hd/2]
    emb = np.concatenate([freqs, freqs], axis=-1)            # [L, hd]
    return {
        "gather": gather.astype(np.int32),
        "valid": valid,
        "scatter": scatter.astype(np.int32),
        "cos": np.cos(emb).astype(np.float32),
        "sin": np.sin(emb).astype(np.float32),
        "n_units": n_units, "unit": unit,
    }


def _rot_half(x, cos, sin):
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


class Qwen25VisionTower:
    """Windowed ViT encoder: flat patches -> merged image features."""

    def __init__(self, config: Qwen25VisionConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True,
                 remat_policy: Optional[str] = "nothing_saveable"):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.remat = remat
        self.remat_policy = remat_policy

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        Dp, D, I, O = (cfg.patch_dim, cfg.hidden_size, cfg.intermediate_size,
                       cfg.out_hidden_size)
        depth = cfg.depth
        unit_d = cfg.spatial_merge_size ** 2 * D
        keys = iter(jax.random.split(key, 12))

        def dense(k, shape, stacked=True):
            full = (depth, *shape) if stacked else shape
            return (jax.random.normal(k, full, jnp.float32) * 0.02).astype(
                self.param_dtype)

        zeros = lambda shape: jnp.zeros(shape, self.param_dtype)
        ones = lambda shape: jnp.ones(shape, self.param_dtype)
        return {
            "patch_embed": {"kernel": dense(next(keys), (Dp, D),
                                            stacked=False)},
            "blocks": {
                "norm1": {"weight": ones((depth, D))},
                "attn": {
                    "qkv": {"kernel": dense(next(keys), (D, 3 * D)),
                            "bias": zeros((depth, 3 * D))},
                    "proj": {"kernel": dense(next(keys), (D, D)),
                             "bias": zeros((depth, D))},
                },
                "norm2": {"weight": ones((depth, D))},
                "mlp": {
                    "gate_proj": {"kernel": dense(next(keys), (D, I)),
                                  "bias": zeros((depth, I))},
                    "up_proj": {"kernel": dense(next(keys), (D, I)),
                                "bias": zeros((depth, I))},
                    "down_proj": {"kernel": dense(next(keys), (I, D)),
                                  "bias": zeros((depth, D))},
                },
            },
            "merger": {
                "ln_q": {"weight": ones((D,))},
                "fc1": {"kernel": dense(next(keys), (unit_d, unit_d),
                                        stacked=False),
                        "bias": zeros((unit_d,))},
                "fc2": {"kernel": dense(next(keys), (unit_d, O),
                                        stacked=False),
                        "bias": zeros((O,))},
            },
        }

    def param_axes(self) -> Dict[str, Any]:
        lin = lambda a, b: {"kernel": ("layers", a, b), "bias": ("layers", b)}
        return {
            "patch_embed": {"kernel": (None, "embed")},
            "blocks": {
                "norm1": {"weight": ("layers", "norm")},
                "attn": {"qkv": lin("embed", "qkv3"),
                         "proj": lin("heads", "embed")},
                "norm2": {"weight": ("layers", "norm")},
                "mlp": {"gate_proj": lin("embed", "mlp"),
                        "up_proj": lin("embed", "mlp"),
                        "down_proj": lin("mlp", "embed")},
            },
            "merger": {
                "ln_q": {"weight": ("norm",)},
                "fc1": {"kernel": (None, None), "bias": (None,)},
                "fc2": {"kernel": (None, "embed"), "bias": ("norm",)},
            },
        }

    def __call__(self, params, patches: jnp.ndarray,
                 grid: Tuple[int, int, int]) -> jnp.ndarray:
        """``patches`` [N, L, patch_dim] (canonical processor order; L must
        equal t*h*w of the STATIC ``grid``) -> [N, n_units, out_hidden]."""
        cfg = self.config
        cd = self.compute_dtype
        N, L, _ = patches.shape
        assert L == grid[0] * grid[1] * grid[2], (
            f"patch count {L} != static grid {grid}")
        lay = _grid_layout(tuple(int(g) for g in grid),
                           cfg.spatial_merge_size, cfg.window_size,
                           cfg.patch_size, cfg.head_dim)
        cos = jnp.asarray(lay["cos"])[None, :, None, :]   # [1, L, 1, hd]
        sin = jnp.asarray(lay["sin"])[None, :, None, :]
        gather = jnp.asarray(lay["gather"])               # [nW, wlen_p]
        valid = jnp.asarray(lay["valid"])
        scatter = jnp.asarray(lay["scatter"])             # [L]
        nW, wlen_p = gather.shape
        Hh, Dh = cfg.num_heads, cfg.head_dim
        t_frames, frame_p = grid[0], L // grid[0]

        x = patches.astype(cd) @ params["patch_embed"]["kernel"].astype(cd)

        eps = 1e-6

        def bias_proj(y, p):
            return y @ p["kernel"].astype(cd) + p["bias"].astype(cd)

        def block(x, xs):
            p, full_flag = xs
            y = rms_norm(x, p["norm1"]["weight"], eps)
            qkv = bias_proj(y, p["attn"]["qkv"]).reshape(N, L, 3, Hh, Dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            q = _rot_half(q, cos, sin)
            k = _rot_half(k, cos, sin)

            def full_attn(args):
                # "Full" attention is per temporal frame (HF builds
                # cu_seqlens = repeat_interleave(h*w, t)); canonical order
                # is t-major so frames are contiguous.
                q, k, v = args
                def per_frame(z):
                    return z.reshape(N * t_frames, frame_p, Hh, Dh)
                out = attention(per_frame(q), per_frame(k), per_frame(v),
                                causal=False)
                return out.reshape(N, L, Hh, Dh)

            def window_attn(args):
                q, k, v = args
                def to_win(z):
                    zw = jnp.take(z, gather.reshape(-1), axis=1)
                    return zw.reshape(N * nW, wlen_p, Hh, Dh)
                mask = jnp.broadcast_to(valid[None], (N, nW, wlen_p)
                                        ).reshape(N * nW, wlen_p)
                out = attention(to_win(q), to_win(k), to_win(v),
                                causal=False, attention_mask=mask)
                out = out.reshape(N, nW * wlen_p, Hh, Dh)
                return jnp.take(out, scatter, axis=1)

            attn_out = lax.cond(full_flag, full_attn, window_attn, (q, k, v))
            x = x + bias_proj(attn_out.reshape(N, L, Hh * Dh), p["attn"]["proj"])
            y = rms_norm(x, p["norm2"]["weight"], eps)
            gate = bias_proj(y, p["mlp"]["gate_proj"])
            up = bias_proj(y, p["mlp"]["up_proj"])
            x = x + bias_proj(jax.nn.silu(gate) * up, p["mlp"]["down_proj"])
            return x, None

        full_flags = jnp.asarray(
            [i in set(cfg.fullatt_block_indexes) for i in range(cfg.depth)])
        body = block
        if self.remat:
            body = jax.checkpoint(
                body, policy=resolve_remat_policy(self.remat_policy),
                prevent_cse=False)
        x, _ = lax.scan(body, x, (params["blocks"], full_flags))

        # merger (canonical order: pointwise per merge unit)
        m = params["merger"]
        y = rms_norm(x, m["ln_q"]["weight"], eps)
        y = y.reshape(N, lay["n_units"], lay["unit"] * cfg.hidden_size)
        y = y @ m["fc1"]["kernel"].astype(cd) + m["fc1"]["bias"].astype(cd)
        y = jax.nn.gelu(y, approximate=False)
        return y @ m["fc2"]["kernel"].astype(cd) + m["fc2"]["bias"].astype(cd)


class Qwen25VLTextModel(LlamaForCausalLM):
    """Qwen2 decoder with multimodal 3-section rope.

    ``position_ids`` may be [B, S] (plain rope — text-only, identical to the
    1D case since all three sections then share positions) or [B, S, 3]
    (temporal/height/width, the collator-computed M-RoPE ids)."""

    def __init__(self, config: LlamaConfig, mrope_section=None, **kwargs):
        super().__init__(config, **kwargs)
        if mrope_section is None:
            mrope_section = _mrope_section_of(config)
        half = config.head_dim // 2
        assert sum(mrope_section) == half, (mrope_section, half)
        sel = np.zeros((3, half), np.float32)
        off = 0
        for axis, n in enumerate(mrope_section):
            sel[axis, off:off + n] = 1.0
            off += n
        self._mrope_sel = sel                       # [3, half] one-hot

    def _apply_rope(self, q, k, position_ids, inv_freq, rope_scale=1.0):
        if position_ids.ndim == 2:
            from automodel_tpu.ops.rotary import apply_rope

            return apply_rope(q, k, position_ids, inv_freq,
                              attention_scaling=rope_scale)
        # [B, S, 3] -> per-channel section select (HF
        # apply_multimodal_rotary_pos_emb: first half channels split into
        # t/h/w blocks, second half mirrors)
        angles3 = (position_ids.astype(jnp.float32)[..., None]
                   * inv_freq[None, None, None, :])          # [B, S, 3, half]
        angles = jnp.einsum("bsth,th->bsh", angles3,
                            jnp.asarray(self._mrope_sel))
        cos = jnp.cos(angles)[:, :, None, :] * rope_scale
        sin = jnp.sin(angles)[:, :, None, :] * rope_scale

        def rot(x):
            # f32 math, bf16 halves out before concat (same traffic fix as
            # ops/rotary.apply_rope — keeps the fused transpose downstream
            # of rope on bf16 buffers).
            x1, x2 = jnp.split(x, 2, axis=-1)
            x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
            return jnp.concatenate(
                [(x1f * cos - x2f * sin).astype(x.dtype),
                 (x2f * cos + x1f * sin).astype(x.dtype)], axis=-1)

        return rot(q), rot(k)


class Qwen25VLForConditionalGeneration:
    """``model._target_: automodel_tpu.models.qwen2_5_vl.build_qwen25_vl``

    ``image_grid`` / ``video_grid``: the STATIC per-image / per-video patch
    grids (t, h, w) this program is compiled for (dynamic resolution = one
    compile per distinct grid; batches group by grid at the collator).
    ``image_grid_thw`` / ``video_grid_thw`` batch data are accepted for
    HF-contract parity; the VLM recipe validates them host-side against the
    static grids (``recipes/vlm/finetune.py:_device_batch``), and
    ``encode_images`` asserts patch-count divisibility at trace time.
    """

    extra_batch_keys = ("image_grid_thw", "pixel_values_videos",
                        "video_grid_thw")

    def __init__(self, config: Qwen25VLConfig,
                 param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                 remat: bool = True, image_grid: Optional[Tuple] = None,
                 video_grid: Optional[Tuple] = None, **kwargs):
        self.config = config
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.image_grid = tuple(image_grid) if image_grid else None
        self.video_grid = tuple(video_grid) if video_grid else None
        self.language_model = Qwen25VLTextModel(
            config.text_config, mrope_section=config.mrope_section,
            param_dtype=param_dtype, compute_dtype=compute_dtype,
            remat=remat, **kwargs)
        self.visual = Qwen25VisionTower(
            config.vision_config, param_dtype=param_dtype,
            compute_dtype=compute_dtype, remat=remat)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        kt, kv = jax.random.split(key)
        return {"language_model": self.language_model.init(kt),
                "visual": self.visual.init(kv)}

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_axes(self) -> Dict[str, Any]:
        return {"language_model": self.language_model.param_axes(),
                "visual": self.visual.param_axes()}

    def init_kv_cache(self, batch: int, max_len: int, dtype=None):
        return self.language_model.init_kv_cache(batch, max_len, dtype)

    def encode_images(self, params, pixel_values: jnp.ndarray,
                      grid: Tuple[int, int, int]) -> jnp.ndarray:
        """Flat HF patches [n_patches_total, patch_dim] -> merged features
        [n_images * n_units, out_hidden] (placeholder-scatter order)."""
        t, h, w = grid
        L = t * h * w
        if pixel_values.shape[0] % L != 0:
            raise ValueError(
                f"pixel patch count {pixel_values.shape[0]} does not divide "
                f"the static grid {grid} ({L} patches per item): the batch "
                "was produced for a different resolution — group batches by "
                "grid at the collator or set model.image_grid/video_grid to "
                "match the processor's output")
        n = pixel_values.shape[0] // L
        feats = self.visual(params["visual"],
                            pixel_values.reshape(n, L, -1), grid)
        return feats.reshape(n * feats.shape[1], feats.shape[2])

    def _scatter_modality(self, embeds, input_ids, feats, token_id):
        """Scatter merged vision features onto their placeholder tokens."""
        B, S = input_ids.shape
        is_tok = (input_ids == token_id).reshape(-1)
        idx = jnp.clip(jnp.cumsum(is_tok) - 1, 0, feats.shape[0] - 1)
        gathered = feats[idx].reshape(B, S, -1)
        return jnp.where(is_tok.reshape(B, S)[..., None],
                         gathered.astype(embeds.dtype), embeds)

    def __call__(self, params, input_ids, pixel_values=None,
                 image_grid_thw=None, pixel_values_videos=None,
                 video_grid_thw=None, position_ids=None, segment_ids=None,
                 attention_mask=None, return_hidden: bool = False,
                 kv_cache=None) -> Dict[str, jnp.ndarray]:
        lm = self.language_model
        lp = params["language_model"]
        B, S = input_ids.shape
        embeds = lp["embed_tokens"]["embedding"][input_ids].astype(
            self.compute_dtype)
        if pixel_values is not None:
            if self.image_grid is None:
                raise ValueError(
                    "Qwen2.5-VL needs a static image_grid=(t, h, w): set "
                    "model.image_grid (the jitted program is compiled per "
                    "grid; image_grid_thw arrays are data, not shapes)")
            img_flat = self.encode_images(params, pixel_values,
                                          self.image_grid)
            embeds = self._scatter_modality(
                embeds, input_ids, img_flat, self.config.image_token_id)
        if pixel_values_videos is not None:
            if self.video_grid is None:
                raise ValueError(
                    "Qwen2.5-VL needs a static video_grid=(t, h, w) to "
                    "consume pixel_values_videos: set model.video_grid")
            vid_flat = self.encode_images(params, pixel_values_videos,
                                          self.video_grid)
            embeds = self._scatter_modality(
                embeds, input_ids, vid_flat, self.config.video_token_id)
        if position_ids is not None and position_ids.ndim == 3 \
                and position_ids.shape[-1] != 3:
            raise ValueError("M-RoPE position_ids must be [B, S, 3]")
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


def build_qwen25_vl(config: Optional[dict] = None, **kwargs):
    """YAML-friendly builder (``model._target_``)."""
    if config is not None:
        if hasattr(config, "to_dict"):
            config = config.to_dict()
        cfg = Qwen25VLConfig.from_hf_config(config)
    else:
        cfg = Qwen25VLConfig()
    return Qwen25VLForConditionalGeneration(cfg, **kwargs)
