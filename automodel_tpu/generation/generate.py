"""Autoregressive generation: jitted prefill + static-shape decode loop.

The reference generates through HF ``model.generate`` on eager torch
(``examples/vlm_generate/generate.py:120-180``); the TPU shape is different
by necessity: everything under jit, no data-dependent Python control flow.

* **Left-padded batching**: prompts are aligned to the right edge so every
  row's last prompt token sits at the same position — the whole batch then
  decodes in lockstep (one shared write offset in the cache), pad positions
  are excluded via the kv padding mask, and rope positions are 0-based per
  row.
* **Prefill**: one forward over the padded prompt block writes the kv cache
  and the last-position logits give every row's first sampled token.
* **Decode**: ``lax.scan`` over ``max_new_tokens`` single-token steps —
  static trip count; finished rows keep emitting ``pad_token_id`` under a
  done-mask (the jit-friendly early exit).
* **Sampling**: greedy / temperature / top-k / top-p, all shape-static.

Two compiled programs total (prefill + decode step), reused across calls
with the same bucket shapes.

This is the EVAL path: one lockstep batch, dense per-request cache, every
row padded to the longest prompt and resident until the slowest finishes.
For batch > 1 serving workloads — mixed lengths, continuous arrivals,
many concurrent requests — use the decode engine
(``automodel_tpu/serving``, ``docs/guides/serving.md``): block-paged KV
cache, chunked prefill, continuous batching, optional int8 KV — and
token-identical greedy output to this function (the tier-1 parity
oracle).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from automodel_tpu.generation.dense_kv import DenseKVView


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 64
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    do_sample: bool = False           # False -> greedy
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0


def sample_logits(logits: jnp.ndarray, cfg: GenerationConfig,
                  key: jax.Array) -> jnp.ndarray:
    """[B, V] logits -> [B] token ids under the configured strategy."""
    if not cfg.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p is not None:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        cumulative = jnp.cumsum(jax.nn.softmax(sorted_desc, axis=-1), axis=-1)
        # smallest prefix whose mass exceeds top_p; top-1 always survives
        cutoff_idx = jnp.sum(cumulative < cfg.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def left_align(input_ids: jnp.ndarray, prompt_lens: jnp.ndarray,
               pad_token_id: int) -> jnp.ndarray:
    """Right-padded [B, S] prompts -> left-padded (right-aligned)."""
    B, S = input_ids.shape
    shift = S - prompt_lens                       # [B]
    idx = jnp.arange(S)[None, :] - shift[:, None]  # source column per target
    rolled = jnp.take_along_axis(input_ids, jnp.clip(idx, 0, S - 1), axis=1)
    return jnp.where(idx < 0, pad_token_id, rolled)


@partial(jax.jit, static_argnames=("model", "cfg"))
def _generate_jit(model, params, left_ids, prompt_lens, cfg: GenerationConfig,
                  key, prefill_kwargs):
    B, S = left_ids.shape
    max_len = S + cfg.max_new_tokens
    shift = S - prompt_lens                        # pad count per row

    # kv padding mask over the whole cache: prompt pads invalid, everything
    # from position S on (generated tokens) always valid.
    positions = jnp.arange(max_len)[None, :]
    kv_mask = (positions >= shift[:, None])        # [B, max_len]

    # rope positions are 0-based per row (pads clamp to 0; they are masked)
    prefill_pos = jnp.maximum(jnp.arange(S)[None, :] - shift[:, None], 0)

    # The dense cache (``DenseKVView``): its state rides each forward's
    # layer scan as carry; the view holds the write offset and the mask.
    cache = model.init_kv_cache(B, max_len)
    out = model(params, left_ids, position_ids=prefill_pos.astype(jnp.int32),
                kv_cache=DenseKVView.at(cache, 0, S, kv_mask),
                **prefill_kwargs)
    cache = out["kv_cache"]
    next_tok = sample_logits(out["logits"][:, -1], cfg, key)

    def step(carry, xs):
        cache, tok, done = carry
        t, step_key = xs
        pos_ids = (prompt_lens + t)[:, None].astype(jnp.int32)
        out = model(params, tok[:, None], position_ids=pos_ids,
                    kv_cache=DenseKVView.at(cache, S + t, 1, kv_mask))
        cache = out["kv_cache"]
        sampled = sample_logits(out["logits"][:, 0], cfg, step_key)
        emitted = jnp.where(done, cfg.pad_token_id, tok)
        if cfg.eos_token_id is not None:
            done = done | (tok == cfg.eos_token_id)
        return (cache, sampled, done), emitted

    # N tokens need only N-1 decode forwards: each scan step emits its
    # carry token and samples the next; the final carry is emitted without
    # another model call.
    steps = cfg.max_new_tokens - 1
    done = jnp.zeros((B,), bool)
    if steps > 0:
        (_, last, done), emitted = lax.scan(
            step, (cache, next_tok, done),
            (jnp.arange(steps), jax.random.split(jax.random.fold_in(key, 1),
                                                 steps)))
    else:
        last, emitted = next_tok, jnp.zeros((0, B), jnp.int32)
    final = jnp.where(done, cfg.pad_token_id, last)[None]
    return jnp.concatenate([emitted, final], axis=0).T  # [B, max_new_tokens]


def generate(model, params, input_ids, prompt_lens=None,
             config: Optional[GenerationConfig] = None,
             key: Optional[jax.Array] = None,
             **prefill_kwargs) -> np.ndarray:
    """Generate continuations for right-padded ``input_ids`` [B, S].

    ``prompt_lens`` [B] are the true prompt lengths (default: S for all
    rows).  Extra kwargs (e.g. ``pixel_values`` for VLMs) go to the prefill
    forward only.  Returns [B, max_new_tokens] int32, ``pad_token_id``
    after eos.

    NOTE: with ``pixel_values``, prompts must already be left-padded (pass
    ``prompt_lens=None``) — image placeholder positions must match the ids.
    """
    config = config or GenerationConfig()
    key = key if key is not None else jax.random.key(0)
    input_ids = jnp.asarray(input_ids, jnp.int32)
    B, S = input_ids.shape
    prompt_lens = (jnp.full((B,), S, jnp.int32) if prompt_lens is None
                   else jnp.asarray(prompt_lens, jnp.int32))
    left_ids = left_align(input_ids, prompt_lens, config.pad_token_id)
    return np.asarray(jax.device_get(_generate_jit(  # lint: disable=L004 (one fetch per generate() call AFTER the whole decode scan; the per-token loop is a device-side lax.scan)
        model, params, left_ids, prompt_lens, config, key, prefill_kwargs)))
