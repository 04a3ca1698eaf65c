"""The layer loop and the head of every decoder family, in one place.

A family says WHAT a layer is (``SubStack.layer``) and which stacked
parameters it runs over; :func:`scan_layers` owns HOW the layers run: one
``lax.scan`` per homogeneous sub-stack under the ``layers`` scope, one
layer index running across the sub-stacks, the decode cache's state as
scan CARRY, ``scan_block`` grouping and ``jax.checkpoint`` with the
model's remat policy when not decoding.

The decode cache is a protocol of a few methods, and the loop and the
families know it by those names alone (``serving/kv_cache.PagedKVView``
and ``generation/dense_kv.DenseKVView`` implement it; nothing here imports
either):

* ``cache.pools`` — the state the scan carries (stacked over ALL layers);
  ``cache.positions`` — ``[B, S]`` positions of this step's tokens;
* ``cache.at_layer(state, idx)`` — the cache standing at layer ``idx``; a
  family whose serving cache is of several BLOCK GROUPS (window and full
  layers in one stack) adds ``group=(name, index among the group's
  layers)``, which the paged view stands at and the dense view ignores;
* ``cache.valid_tokens()`` — ``[B, S]`` bool, the columns of this forward
  that hold a token (a routed layer keeps the others out of its routing);
* ``cache.write(k, v) -> state`` and ``cache.attend(q, state, *, scale,
  logits_soft_cap, local_window_size)`` — what a layer's attention calls.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.distributed.shardings import constrain
from automodel_tpu.ops.remat import resolve_remat_policy


@dataclasses.dataclass
class SubStack:
    """A run of like layers.  ``params`` is stacked ``[n, ...]``; ``xs`` is
    whatever else is per layer and stacked the same way (adapters, rope
    tables, a full/sliding flag) or None; ``layer(hidden, layer_params,
    layer_xs, idx, cache) -> (hidden, cache_state, y)`` runs one layer,
    where ``idx`` counts across all sub-stacks, ``cache`` stands at that
    layer (None when not decoding: return None for ``cache_state``) and
    ``y`` is the layer's stacked by-product (an aux loss, expert counts) or
    None.  What the layer closes over is shared by all layers."""

    params: Any
    layer: Callable
    xs: Any = None


def default_position_ids(kv_cache, batch: int, seq: int) -> jnp.ndarray:
    """Positions when the caller gave none: the cache's own while decoding,
    ``0..S-1`` otherwise."""
    if kv_cache is not None:
        return kv_cache.positions
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))


def dense_kv_state(num_layers: int, batch: int, max_len: int,
                   per_slot: Tuple[int, int], dtype) -> dict:
    """The state of a dense decode cache (``generation.DenseKVView``):
    ``{"k"|"v": [L, B, max_len, heads, head_dim]}`` of zeros."""
    shape = (num_layers, batch, max_len, *per_slot)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def scan_layers(hidden, stacks: Sequence[SubStack], kv_cache=None, *,
                remat: bool, remat_policy: Optional[str],
                scan_block: int = 1, scan_unroll: int = 1
                ) -> Tuple[jnp.ndarray, Any, List[Any]]:
    """Run ``hidden`` through the sub-stacks in order.  Returns ``(hidden,
    cache_state, ys)``: the cache's state after every layer wrote to it
    (None without a cache) and, per sub-stack, its layers' ``y`` stacked
    ``[n, ...]``.

    The cache state rides every scan as carry beside the hidden state and
    each layer addresses it at its own index: as ``xs``/``ys`` a scan
    would slice one layer of it out and stack one back into a fresh buffer
    per layer.  ``scan_block`` layers share one checkpointed body (only
    the group-boundary hidden state is saved; the backward recomputes a
    block-sized window); a decode step runs no backward and takes one
    layer a body."""
    decoding = kv_cache is not None
    if scan_block < 1:
        raise ValueError(f"model.scan_block must be >= 1, got {scan_block}")
    block = 1 if decoding else scan_block
    state = kv_cache.pools if decoding else None
    first, ys = 0, []
    for stack in stacks:
        n = jax.tree.leaves(stack.params)[0].shape[0]
        if n % block:
            raise ValueError(
                f"model.scan_block={block} must divide the {n} layers of "
                "each stack it scans (num_hidden_layers; under pipeline "
                "parallelism the per-stage slab L/pp)")

        def one_layer(carry, xs, layer=stack.layer):
            h, st = carry
            p, extra, idx = xs
            cache = kv_cache.at_layer(st, idx) if decoding else None
            h, st, y = layer(h, p, extra, idx, cache)
            return (h, st), y

        body = one_layer
        if block > 1:
            def body(carry, xs, one_layer=one_layer):
                out = []
                for i in range(block):
                    carry, y = one_layer(
                        carry, jax.tree.map(lambda a: a[i], xs))
                    out.append(y)
                return carry, jax.tree.map(lambda *a: jnp.stack(a), *out)

        if remat and not decoding:
            body = jax.checkpoint(
                body, policy=resolve_remat_policy(remat_policy),
                prevent_cse=False)
        xs = (stack.params, stack.xs,
              jnp.arange(first, first + n, dtype=jnp.int32))
        if block > 1:
            xs = jax.tree.map(
                lambda a: a.reshape(n // block, block, *a.shape[1:]), xs)
        with jax.named_scope("layers"):
            (hidden, state), y = lax.scan(body, (hidden, state), xs,
                                          unroll=scan_unroll)
        if block > 1:
            y = jax.tree.map(lambda a: a.reshape(n, *a.shape[2:]), y)
        ys.append(y)
        first += n
    return hidden, state, ys


def norm_and_head(hidden, params, norm: Callable, *, tied: bool,
                  compute_dtype, return_hidden: bool = False,
                  logits_divisor: float = 1.0) -> dict:
    """Final norm, then the head: ``{"logits"}`` or, for a fused
    hidden-state loss, ``{"hidden_states", "lm_head_kernel"}`` (no kernel
    for a headless backbone).  ``norm(hidden, params["norm"])`` is the
    family's own; a divisor is folded into the kernel on the hidden-state
    path so the fused loss sees the scaled logits too."""
    with jax.named_scope("final_norm"):
        hidden = norm(hidden, params["norm"])
    with jax.named_scope("lm_head"):
        lm_kernel = (params["embed_tokens"]["embedding"].T if tied
                     else params.get("lm_head", {}).get("kernel"))
        if return_hidden:
            out = {"hidden_states": hidden}
            if lm_kernel is not None:
                if logits_divisor != 1.0:
                    lm_kernel = lm_kernel / jnp.asarray(logits_divisor,
                                                        lm_kernel.dtype)
                out["lm_head_kernel"] = lm_kernel
            return out
        logits = hidden @ lm_kernel.astype(compute_dtype)
        if logits_divisor != 1.0:
            logits = logits / jnp.asarray(logits_divisor, logits.dtype)
        return {"logits": constrain(
            logits, ("act_batch", "act_seq_nosp", "act_vocab"))}
