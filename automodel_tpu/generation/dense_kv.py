"""``generate()``'s decode cache: dense, one row of ``S_max`` slots per
request, as the cache protocol of ``models/layer_scan.py``.

The serving engine's ``PagedKVView`` (``serving/kv_cache.py``) is the other
implementation of the same three methods; a model's attention calls
``write`` then ``attend`` and cannot tell which it was handed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.ops.attention import attention, cached_attention


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseKVView:
    """The dense cache as one model forward sees it.  ``pools`` is the
    state the layer scan carries, ``{"k"|"v": [L, B, S_max, heads, D]}``
    (``model.init_kv_cache``); the batch decodes in lockstep, so ONE
    ``cache_index`` says where this forward's tokens are written, and
    ``attention_mask [B, S_max]`` marks the cache's valid slots (left
    padding).  A forward of several tokens is a prefill: it attends over
    its own keys alone; a forward of one token attends over the cache."""

    pools: Dict[str, jnp.ndarray]
    cache_index: jnp.ndarray              # int32 scalar
    positions: jnp.ndarray                # [B, S] int32, cache_index + 0..S-1
    attention_mask: Optional[jnp.ndarray] = None
    layer: Any = None                     # int32 scalar (traced in the scan)

    def tree_flatten(self):
        return (self.pools, self.cache_index, self.positions,
                self.attention_mask, self.layer), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def at(cls, pools, cache_index, q_len: int,
           attention_mask=None) -> "DenseKVView":
        """The view for a forward of ``q_len`` tokens written from
        ``cache_index`` on."""
        cache_index = jnp.asarray(cache_index, jnp.int32)
        batch = next(iter(pools.values())).shape[1]
        positions = cache_index + jnp.broadcast_to(
            jnp.arange(q_len, dtype=jnp.int32), (batch, q_len))
        return cls(pools, cache_index, positions, attention_mask)

    def at_layer(self, pools, layer, group=None) -> "DenseKVView":
        """Standing at ``layer`` of the whole stack.  ``group`` (a family
        whose paged cache is of several block groups names the layer's
        there) is not this cache's concern: it keeps every token of every
        layer, and a window layer masks."""
        return dataclasses.replace(self, pools=pools, layer=layer)

    def valid_tokens(self) -> jnp.ndarray:
        """``[B, S]`` bool: this forward's columns that hold a token (the
        batch is left-padded: a prefill's pad columns are its first)."""
        B, S = self.positions.shape
        if self.attention_mask is None:
            return jnp.ones((B, S), bool)
        return lax.dynamic_slice_in_dim(
            self.attention_mask.astype(bool), self.cache_index, S, axis=1)

    def write(self, k: jnp.ndarray, v: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """``[B, S, heads, D]`` k/v into the view's layer at
        ``cache_index``; returns the stacked state."""
        at = (self.layer, 0, self.cache_index, 0, 0)
        return {name: lax.dynamic_update_slice(
            self.pools[name], x[None].astype(self.pools[name].dtype), at)
            for name, x in (("k", k), ("v", v))}

    def attend(self, q: jnp.ndarray, pools: Dict[str, jnp.ndarray], *,
               scale=None, logits_soft_cap=None, local_window_size=None
               ) -> jnp.ndarray:
        """Attention of ``q [B, S, Hq, D]`` over the view's layer of the
        (freshly written) state.  A prefill reads its own S keys back
        (attending the whole cache would spend the work on slots the
        causal mask forbids anyway) and goes through the framework's
        ``attention`` chain; a decode step attends the cache."""
        S = q.shape[1]
        mask = self.attention_mask
        if S > 1:
            k, v = (lax.dynamic_slice(
                pools[name],
                (self.layer, 0, self.cache_index, 0, 0),
                (1, q.shape[0], S, *pools[name].shape[3:]))[0]
                for name in ("k", "v"))
            if mask is not None:
                mask = lax.dynamic_slice_in_dim(mask, self.cache_index, S,
                                                axis=1)
            return attention(q, k, v, causal=True, attention_mask=mask,
                             scale=scale, logits_soft_cap=logits_soft_cap,
                             local_window_size=local_window_size)
        k, v = (lax.dynamic_index_in_dim(pools[name], self.layer, 0,
                                         keepdims=False)
                for name in ("k", "v"))
        return cached_attention(
            q, k, v, cache_index=self.cache_index, q_len=S,
            attention_mask=mask, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size)

    # -- per-sequence state (power retention: models/brumby.py) ------------
    def retain(self, q, k, v, log_g):
        """The state-plane call of the protocol (``serving/kv_cache.
        StatePlaneView.retain`` is the engine's): ``pools`` are then
        ``{"state", "norm": [L, B, ...]}`` (``model.init_kv_cache``), a row
        a request.  The batch is left-padded, so a prefill's valid columns
        are its LAST ones: it runs the chunked form under the mask; a
        decode step goes down the ``attention.retention_decode`` chain."""
        from automodel_tpu.ops import power_retention as pr

        B, S = q.shape[:2]
        valid = self.valid_tokens()
        state, norm = self.pools["state"], self.pools["norm"]
        if S == 1:
            o, state, norm = pr.retention(
                q, k, v, log_g, state, norm, layer=self.layer,
                n_valid=valid[:, 0].astype(jnp.int32),
                reset=jnp.zeros((B,), bool))
        else:
            o, s, z = pr.retention_scan(
                q, k, v, log_g, *pr.read_state(state, norm, self.layer),
                valid, jnp.zeros((B, S), bool))
            state, norm = pr.write_state(state, norm, self.layer, s, z)
        return o, {"state": state, "norm": norm}
