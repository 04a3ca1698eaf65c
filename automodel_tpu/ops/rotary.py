"""Rotary position embeddings (RoPE), including Llama-3 frequency scaling.

Computed on the fly from ``position_ids`` — no precomputed cache buffer to
shard.  Packing support falls out naturally: per-pack ``position_ids`` restart
at 0 at each segment boundary (reference packed-sequence convention,
``datasets/llm/packed_sequence.py:153-221``), and CP shards simply pass their
global positions.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np


def rope_parameters(
    head_dim: int,
    theta: float = 10000.0,
    scaling: Optional[dict] = None,
    *,
    max_position_embeddings: Optional[int] = None,
    original_max_position_embeddings: Optional[int] = None,
    seq_len: Optional[int] = None,
):
    """``(inverse frequencies [D/2] f32, attention_scaling float)``.

    Mirrors HF ``modeling_rope_utils.py`` (the reference consumes it through
    ``_transformers/auto_model.py:384``): ``rope_scaling.rope_type`` selects
    default / linear / llama3 / yarn / longrope.  ``attention_scaling``
    multiplies the rope cos/sin amplitudes (yarn mscale, longrope sqrt-log
    factor); callers that ignore it must only do so for types where it is
    1.0 (see :func:`rope_frequencies`).

    ``longrope`` picks the per-dim ``long_factor`` rescale when ``seq_len``
    exceeds ``original_max_position_embeddings`` and ``short_factor``
    otherwise — pass the trace-time sequence length as ``seq_len``.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    attention_scaling = 1.0
    rope_type = "default"
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
    if rope_type == "llama3":
        factor = scaling["factor"]
        low_factor = scaling["low_freq_factor"]
        high_factor = scaling["high_freq_factor"]
        old_len = scaling["original_max_position_embeddings"]
        wavelen = 2 * np.pi / inv_freq
        low_wavelen = old_len / low_factor
        high_wavelen = old_len / high_factor
        scaled = np.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
        smooth = (old_len / wavelen - low_factor) / (high_factor - low_factor)
        smoothed = (1 - smooth) / factor * inv_freq + smooth * inv_freq
        is_medium = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        inv_freq = np.where(is_medium, smoothed, scaled)
    elif rope_type == "linear":
        inv_freq = inv_freq / scaling["factor"]
    elif rope_type == "yarn":
        # HF _compute_yarn_parameters: blend interpolated (long-context)
        # and extrapolated (original) frequencies over a correction ramp.
        # old_len precedence matches HF exactly: the rope_scaling dict's own
        # original_max key, else max_position_embeddings — HF does NOT
        # consult a config-level original_max_position_embeddings for yarn
        # (only longrope does, below), so neither do we.
        factor = scaling["factor"]
        old_len = (scaling.get("original_max_position_embeddings")
                   or max_position_embeddings)
        beta_fast = scaling.get("beta_fast") or 32.0
        beta_slow = scaling.get("beta_slow") or 1.0
        mscale = scaling.get("mscale")
        mscale_all_dim = scaling.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
            return 0.1 * m * np.log(scale) + 1.0 if scale > 1 else 1.0

        attention_scaling = scaling.get("attention_factor")
        if attention_scaling is None:
            if mscale and mscale_all_dim:
                attention_scaling = float(
                    get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim))
            else:
                attention_scaling = float(get_mscale(factor))

        def correction_dim(num_rotations):
            return (head_dim * np.log(old_len / (num_rotations * 2 * np.pi))
                    ) / (2 * np.log(theta))

        low, high = correction_dim(beta_fast), correction_dim(beta_slow)
        if scaling.get("truncate", True):
            low, high = np.floor(low), np.ceil(high)
        low = max(float(low), 0.0)
        high = min(float(high), head_dim - 1)
        rmin, rmax = low, max(high, low + 0.001)
        ramp = np.clip(
            (np.arange(head_dim // 2, dtype=np.float64) - rmin) / (rmax - rmin),
            0, 1)
        extrapolation_factor = 1.0 - ramp
        inv_freq = (inv_freq / factor * (1 - extrapolation_factor)
                    + inv_freq * extrapolation_factor)
    elif rope_type == "longrope":
        # HF _compute_longrope_parameters (Phi-3 long variants): per-dim
        # rescale lists; long_factor beyond the original context length.
        # Precedence mirrors HF exactly: a config-level
        # original_max_position_embeddings (the ``original_max_position_
        # embeddings`` argument here) force-overrides ``factor`` with
        # max/original; without it the dict's ``factor`` applies and the
        # short/long threshold is max_position_embeddings (HF does not read
        # the rope_scaling dict's own original_max key for longrope).
        if original_max_position_embeddings:
            old_len = original_max_position_embeddings
            factor = (max_position_embeddings / old_len
                      if max_position_embeddings else None)
        else:
            old_len = max_position_embeddings
            factor = scaling.get("factor")
        use_long = seq_len is not None and old_len and seq_len > old_len
        ext = np.asarray(scaling["long_factor" if use_long else "short_factor"],
                         dtype=np.float64)
        inv_freq = inv_freq / ext
        attention_scaling = scaling.get("attention_factor")
        if attention_scaling is None:
            if factor is None or factor <= 1.0:
                attention_scaling = 1.0
            else:
                attention_scaling = float(
                    np.sqrt(1 + np.log(factor) / np.log(old_len)))
    # "default"/"dynamic" fall through (dynamic only matters for inference
    # beyond trained context).
    return inv_freq.astype(np.float32), float(attention_scaling)


def rope_frequencies(
    head_dim: int,
    theta: float = 10000.0,
    scaling: Optional[dict] = None,
) -> np.ndarray:
    """Inverse frequencies only — for rope types whose attention_scaling is
    always 1.0.  yarn/longrope must go through :func:`rope_parameters` (and
    plumb the scaling), so they fail loudly here."""
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
        if rope_type in ("yarn", "longrope"):
            raise ValueError(
                f"rope_type {rope_type!r} carries an attention_scaling "
                "factor; use rope_parameters() and apply the returned "
                "scaling in apply_rope")
    return rope_parameters(head_dim, theta, scaling)[0]


def apply_rope(
    q: jnp.ndarray,           # [B, S, Hq, D]
    k: jnp.ndarray,           # [B, S, Hk, D]
    position_ids: jnp.ndarray,  # [B, S]
    inv_freq: jnp.ndarray,      # [D/2]
    attention_scaling: float = 1.0,
    rotary_dim: Optional[int] = None,
):
    """Rotate q and k by position-dependent phases (HF half-split convention:
    the rotation pairs element i with element i + D/2).  ``attention_scaling``
    multiplies cos/sin (yarn mscale / longrope factor from
    :func:`rope_parameters`).  ``rotary_dim`` (partial rotary; ``inv_freq``
    then has ``rotary_dim / 2`` entries) rotates the first that many
    channels of each head, pairing i with i + rotary_dim/2, and passes the
    rest through unscaled."""
    angles = position_ids[..., None].astype(jnp.float32) * inv_freq  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if attention_scaling != 1.0:
        cos = cos * attention_scaling
        sin = sin * attention_scaling

    def rot(x):
        # f32 math with the casts INSIDE each half: the concat (and any
        # downstream layout transpose for the attention kernel) then runs on
        # bf16 buffers.  Same numerics as computing the whole rotation in
        # f32 and casting at the end — round-5 profiling found the f32
        # [B, S, Hq, D] rope intermediates materialized at 2x traffic in
        # every scan iteration (fwd + remat recompute).
        if rotary_dim is not None and rotary_dim != x.shape[-1]:
            return jnp.concatenate(
                [rot(x[..., :rotary_dim]), x[..., rotary_dim:]], axis=-1)
        x1, x2 = jnp.split(x, 2, axis=-1)
        x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate(
            [(x1f * cos - x2f * sin).astype(x.dtype),
             (x2f * cos + x1f * sin).astype(x.dtype)], axis=-1)

    return rot(q), rot(k)
