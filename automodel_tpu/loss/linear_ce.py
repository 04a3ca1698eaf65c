"""Fused linear + cross-entropy: CE from hidden states without materializing
the full [B, S, V] logit tensor.

TPU re-design of the reference's ``FusedLinearCrossEntropy`` wrapping Apple
cut-cross-entropy (``nemo_automodel/components/loss/linear_ce.py:118-170``):
the model returns ``hidden_states`` + the lm_head kernel (reference
``logits_to_keep=1`` path, ``recipes/llm/train_ft.py:436-460``).

Two execution paths, picked per call:

* **Pallas kernel** (TPU, 128-aligned H/V): one fused pass computes each
  row's ``(logsumexp, picked-logit)`` on-chip with online softmax — see
  ``ops/linear_ce_kernel.py``.  Under an active sharding context the kernel
  runs per-shard via ``shard_map``: vocab-parallel shards compute local
  lse/pick on their ``[H, V/tp]`` slice and combine with psum collectives
  (the TPU equivalent of the reference's Triton vocab-parallel CE,
  ``loss/triton/te_cross_entropy.py:49-291``); the FSDP-sharded hidden dim
  is gathered per-shard exactly like GSPMD would.
* **XLA chunk scan** (CPU / odd shapes): logits exist one sequence chunk at
  a time inside a ``lax.scan`` and are rematerialized in the backward
  (``jax.checkpoint``), so peak memory is O(B*C*V) instead of O(B*S*V).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.loss.masked_ce import IGNORE_INDEX


def _rule_axes(rules, name) -> Tuple[str, ...]:
    """Mesh-axes tuple for a logical axis, for collective axis_name args.
    Raises on unknown names (same contract as ``shardings.spec_for``: a
    missing rule must not silently disable the vocab-parallel combine)."""
    if name not in rules:
        raise KeyError(
            f"Unknown logical axis {name!r}; known: {sorted(rules)}")
    v = rules[name]
    return tuple(v) if v else ()


def _sharded_lse_pick(hidden, kernel, labels, mesh, rules, bwd_mode):
    """Per-token ``lse - picked`` under the active parallel plan.

    Returns ``tok_loss [B, S]`` sharded like ``labels``; the caller's global
    ``jnp.sum`` is the cross-shard reduction.  Vocab-parallel combine:
    ``lse = logsumexp_tp(lse_local)``, ``picked = psum_tp(picked_local)``
    (only the owning shard's pick is nonzero).  The max subtraction uses
    ``stop_gradient`` so the backward stays the plain softmax rule — the
    kernel's ``(dlse, dpick)`` cotangents then come out exactly right.
    """
    from automodel_tpu.distributed.shardings import spec_for
    from automodel_tpu.ops.linear_ce_kernel import (
        linear_ce_kernel_available,
        lse_and_pick,
    )

    vocab_ax = _rule_axes(rules, "act_vocab")
    embed_ax = _rule_axes(rules, "embed")

    h_spec = spec_for(("act_batch", "act_seq_nosp", None), rules)
    w_spec = spec_for(("embed", "vocab"), rules)
    lab_spec = spec_for(("act_batch", "act_seq_nosp"), rules)

    def local(h, w, lab):
        if embed_ax:
            w = lax.all_gather(w, embed_ax, axis=0, tiled=True)
        v_local = w.shape[1]
        b, s, hd = h.shape
        t = b * s
        offset = jnp.int32(0)
        for ax in vocab_ax:
            offset = offset * lax.axis_size(ax) + lax.axis_index(ax)
        lab_flat = lab.reshape(t).astype(jnp.int32) - offset * v_local
        if linear_ce_kernel_available(t, hd, v_local):
            lse, pick = lse_and_pick(h.reshape(t, hd), w, lab_flat, bwd_mode)
        else:  # e.g. vocab shard not lane-aligned: plain XLA, same contract
            logits = jnp.dot(h.reshape(t, hd), w,
                             preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            in_range = (lab_flat >= 0) & (lab_flat < v_local)
            safe = jnp.clip(lab_flat, 0, v_local - 1)
            pick = jnp.where(
                in_range,
                jnp.take_along_axis(logits, safe[:, None], -1)[:, 0], 0.0)
        if vocab_ax:
            gmax = lax.pmax(lax.stop_gradient(lse), vocab_ax)
            lse = gmax + jnp.log(lax.psum(jnp.exp(lse - gmax), vocab_ax))
            pick = lax.psum(pick, vocab_ax)
        valid = lab.reshape(t) != IGNORE_INDEX
        return jnp.where(valid, lse - pick, 0.0).reshape(b, s)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(h_spec, w_spec, lab_spec),
        out_specs=lab_spec, check_vma=False,
    )(hidden, kernel, labels)


class FusedLinearCrossEntropy:
    needs_hidden = True
    reduction = "sum"  # framework loss contract: see training/train_step.py

    def __init__(self, chunk_len: int = 512, ignore_index: int = IGNORE_INDEX,
                 use_kernel: Optional[bool] = None, bwd_mode: str = "pallas"):
        assert ignore_index == IGNORE_INDEX
        self.chunk_len = chunk_len
        self.use_kernel = use_kernel  # None = auto (TPU + aligned shapes)
        self.bwd_mode = bwd_mode

    def _kernel_path(self, hidden_states, lm_head_kernel, labels):
        from automodel_tpu.distributed.shardings import current_sharding
        from automodel_tpu.ops.linear_ce_kernel import lse_and_pick

        B, S, H = hidden_states.shape
        sh = current_sharding()
        if sh is not None:
            mesh, rules = sh
            tok = _sharded_lse_pick(hidden_states, lm_head_kernel, labels,
                                    mesh, rules, self.bwd_mode)
            return jnp.sum(tok)
        lse, pick = lse_and_pick(
            hidden_states.reshape(B * S, H),
            lm_head_kernel, labels.reshape(B * S).astype(jnp.int32),
            self.bwd_mode)
        valid = labels.reshape(B * S) != IGNORE_INDEX
        return jnp.sum(jnp.where(valid, lse - pick, 0.0))

    def __call__(
        self,
        hidden_states: jnp.ndarray,    # [B, S, H]
        lm_head_kernel: jnp.ndarray,   # [H, V]
        labels: jnp.ndarray,           # [B, S]
        mask: Optional[jnp.ndarray] = None,
        num_label_tokens: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        B, S, H = hidden_states.shape
        if mask is not None:
            labels = jnp.where(mask.astype(bool), labels, IGNORE_INDEX)

        use_kernel = self.use_kernel
        if use_kernel is None:
            # data-driven dispatch: the linear_ce chain resolves to the
            # Pallas rung on TPU/aligned shapes, the chunked XLA rung
            # otherwise (same availability predicate as before, owned by
            # the kernel registry instead of this call site)
            from automodel_tpu.ops.kernel_lib import (
                registry as kernel_registry,
            )

            spec = kernel_registry.resolve(
                "linear_ce.pallas",
                {"kind": "linear_ce", "t": B * S, "h": H,
                 "v": lm_head_kernel.shape[1], "bwd_mode": self.bwd_mode})
            use_kernel = spec.name == "linear_ce.pallas"
        if use_kernel:
            total = self._kernel_path(hidden_states, lm_head_kernel, labels)
            if num_label_tokens is not None:
                total = total / num_label_tokens
            return total

        C = min(self.chunk_len, S)
        n_chunks = -(-S // C)
        pad = n_chunks * C - S
        if pad:
            hidden_states = jnp.pad(hidden_states, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)),
                             constant_values=IGNORE_INDEX)
        hs = hidden_states.reshape(B, n_chunks, C, H).swapaxes(0, 1)
        lb = labels.reshape(B, n_chunks, C).swapaxes(0, 1)
        kernel = lm_head_kernel.astype(hidden_states.dtype)

        @jax.checkpoint
        def chunk_loss(h, l):
            logits = (h @ kernel).astype(jnp.float32)   # [B, C, V] — transient
            valid = l != IGNORE_INDEX
            safe = jnp.where(valid, l, 0)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, safe[..., None], -1).squeeze(-1)
            return jnp.sum(jnp.where(valid, lse - picked, 0.0))

        def body(acc, args):
            h, l = args
            return acc + chunk_loss(h, l), None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, lb))
        if num_label_tokens is not None:
            total = total / num_label_tokens
        return total


# ---------------------------------------------------------------------------
# Registry rung: the chunked-XLA anchor of the linear_ce chain
# ---------------------------------------------------------------------------
def _chunked_probe(request) -> bool:
    return True


def _chunked_impl(request, h, w, labels):
    """(lse, picked) per row via a chunk scan: logits exist one row chunk
    at a time — the XLA strategy with the kernel's exact contract
    (out-of-range labels pick 0), so the parity harness can hold both
    rungs to the same oracle."""
    t, hd = h.shape
    v = w.shape[1]
    c = min(int(request.get("chunk_rows", 512)), t)
    n = -(-t // c)
    pad = n * c - t
    hp = jnp.pad(h, ((0, pad), (0, 0))) if pad else h
    labp = (jnp.pad(labels, (0, pad), constant_values=-1) if pad
            else labels)
    wd = w.astype(h.dtype)

    def body(_, args):
        hc, labc = args
        logits = jnp.dot(hc, wd, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        safe = jnp.clip(labc, 0, v - 1)
        pick = jnp.where(
            (labc >= 0) & (labc < v),
            jnp.take_along_axis(logits, safe[:, None], -1)[:, 0], 0.0)
        return None, (lse, pick)

    _, (lse, pick) = lax.scan(
        body, None, (hp.reshape(n, c, hd), labp.reshape(n, c)))
    return lse.reshape(-1)[:t], pick.reshape(-1)[:t]


def _register():
    # the oracle lives in kernel_lib.parity (jnp-only, importable even on
    # a JAX where the Pallas kernel module cannot be): the chain's anchor
    # rung must always register
    from automodel_tpu.ops.kernel_lib import registry as kernel_registry
    from automodel_tpu.ops.kernel_lib.parity import dense_lse_pick_reference

    kernel_registry.register_kernel(
        "linear_ce.chunked", probe=_chunked_probe, impl=_chunked_impl,
        fallback=None, reference=dense_lse_pick_reference)


_register()
