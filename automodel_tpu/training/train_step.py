"""The jitted train/eval step: one XLA program per optimizer step.

TPU-native collapse of the reference's eager hot loop
(``nemo_automodel/recipes/llm/train_ft.py:630-731``): where PyTorch needs
``no_sync`` contexts, explicit H2D copies, DDP loss scaling and a separate
clip/optimizer/scheduler sequence, here **grad accumulation is a
``lax.scan`` over microbatches inside one jit** — XLA overlaps the FSDP
all-gathers/reduce-scatters with compute, grads are accumulated in fp32, and
the optimizer update runs sharded in the same program.

Loss convention (framework-wide, reference ``loss/masked_ce.py:20-76`` +
``train_ft.py:425-474``): per-microbatch losses are **sums** of token CE;
the final division is by the **global** label-token count of the whole
optimizer step (all microbatches, all dp/cp shards) — under jit the batch is
a global array, so a plain ``jnp.sum`` is the psum.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from automodel_tpu.distributed.mesh import AXIS_PP
from automodel_tpu.distributed.shardings import (
    ParallelPlan,
    sharding_context,
    stage_boundary_spec,
    state_partition_specs,
    to_named_shardings,
)
from automodel_tpu.loss.masked_ce import IGNORE_INDEX, MaskedCrossEntropy
from automodel_tpu.training.pipeline import (
    PipelineConfig,
    PIPELINE_BATCH_KEYS,
    ensure_pp_compatible,
    schedule_slots,
    split_microbatches,
    stage_embed,
    stage_head_loss,
    run_stage_layers,
)

# Keys the model forward consumes; models with extra modalities extend this
# via an ``extra_batch_keys`` attribute (e.g. Qwen2.5-VL's image_grid_thw).
_MODEL_KEYS = ("input_ids", "position_ids", "segment_ids", "attention_mask",
               "pixel_values")
# Keys the step itself consumes outside the model forward.
_STEP_KEYS = ("labels", "dropout_rng")

# Order contract for the fused ``metrics["_packed"]`` device buffer: packed
# here, unpacked by ``recipes/llm/train_ft.py::_finalize_metrics`` — both
# sites MUST iterate this one list, so adding a metric cannot silently
# desynchronize them.  Everything rides as float32 (one dtype, one d2h
# transfer); note ``num_label_tokens`` is therefore exact only below 2^24
# (~16.7M) label tokens per optimizer step — beyond that, carry it as a
# separate int32 leaf instead of widening this buffer.
_PACKED_KEYS = ("loss", "grad_norm", "num_label_tokens")


def _model_keys(model) -> Tuple[str, ...]:
    return _MODEL_KEYS + tuple(getattr(model, "extra_batch_keys", ()))


def _microbatch_loss(model, loss_fn, params, mb: Dict[str, jnp.ndarray]):
    """Sum-CE of one microbatch. Routes the fused-linear-CE path when the
    loss wants hidden states (reference ``calculate_loss`` routing,
    ``train_ft.py:425-474``)."""
    model_keys = _model_keys(model)
    # Fail loudly on batch keys nothing consumes: a collator emitting e.g.
    # audio embeddings for a model without an audio path would otherwise
    # train with that context silently dropped (supervising answers whose
    # inputs are missing).  Keys are static under jit, so this is trace-time.
    unconsumed = set(mb) - set(model_keys) - set(_STEP_KEYS)
    if unconsumed:
        raise ValueError(
            f"batch keys {sorted(unconsumed)} are not consumed by "
            f"{type(model).__name__} (accepts {sorted(model_keys)}) nor by "
            "the train step — training would silently supervise answers "
            "whose inputs were dropped. Use a model family that implements "
            "this modality (a model declares extra inputs via "
            "`extra_batch_keys`), or a collator that does not emit these "
            "keys.")
    kwargs = {k: mb[k] for k in model_keys[1:] if mb.get(k) is not None}
    if mb.get("dropout_rng") is not None:
        # [2] uint32 key data per microbatch (LoRA dropout; see the recipe's
        # _device_batch) — absent at eval, so dropout is train-only.
        kwargs["dropout_rng"] = jax.random.wrap_key_data(mb["dropout_rng"])
    labels = mb["labels"]
    if getattr(loss_fn, "needs_hidden", False):
        out = model(params, mb["input_ids"], return_hidden=True, **kwargs)
        loss = loss_fn(out["hidden_states"], out["lm_head_kernel"], labels)
    else:
        out = model(params, mb["input_ids"], **kwargs)
        loss = loss_fn(out["logits"], labels)
    if "aux_loss" in out:
        # MoE load-balancing penalty (already coef-scaled by the model).
        # The step divides every microbatch's sum by the global label-token
        # count, so scaling by this microbatch's count makes the final loss
        # CE_mean + token-weighted-mean(aux) — HF's ``loss + coef * aux``.
        n_mb = jnp.sum(labels != IGNORE_INDEX).astype(loss.dtype)
        loss = loss + out["aux_loss"].astype(loss.dtype) * n_mb
    return loss


# ---------------------------------------------------------------------------
# Pipelined microbatch loss (pp > 1): the 1F1B/GPipe schedule
# ---------------------------------------------------------------------------
def _make_pp_shift(mesh, boundary_spec, pp: int):
    """The stage-boundary send: ``[pp, B_mb, S, H]`` buffers move one stage
    forward (``s -> s+1``) via ``jax.lax.ppermute`` under a FULL-MANUAL
    ``shard_map`` — the one place activations (fwd) and, through the AD
    transpose, activation-grads (bwd) cross the ``pp`` seam.  The buffer is
    constrained to ``boundary_spec`` by the caller, so the shard_map neither
    reshards on entry nor exit; the permute is the only traffic.

    This is also the census anchor: the ``pp2xdp2`` golden census pins these
    ppermutes keyed to the ``pp`` axis, and lint rule L007 keeps raw
    ``ppermute`` construction confined to ``ops/`` and this module so the
    census can always name the home of every permute it counts.
    """
    from jax import lax as _lax

    perm = [(i, i + 1) for i in range(pp - 1)]

    def _shift(y_local):
        return _lax.ppermute(y_local, AXIS_PP, perm)

    return jax.shard_map(_shift, mesh=mesh, in_specs=boundary_spec,
                         out_specs=boundary_spec, check_vma=False)


def _build_pipeline_loss(model, loss_fn, plan: ParallelPlan,
                         pipeline: PipelineConfig):
    """``fn(params, mb) -> loss_sum`` for ONE grad-accumulation microbatch
    (``mb`` = dict of ``[B, S]`` arrays), pipelined over the mesh's ``pp``
    axis with ``pipeline.num_microbatches`` microbatches.

    Execution (see ``training/pipeline.py`` for the design):
      * the layer slab ``[L, ...]`` (sharded over pp) is viewed as
        ``[pp, L/pp, ...]`` and stage compute is vmapped over the stage dim
        (``spmd_axis_name="pp"`` keeps FSDP/TP/SP constraints inside a
        stage working unchanged — PR-10 qdot and the quant plumbing ride
        along because the stage body calls the same ``_decoder_layer``);
      * a rolled loop of ``num_slots`` iterations runs
        warmup/steady/cooldown; boundary activations move via
        :func:`_make_pp_shift`; under the ``1f1b`` schedule the shift for
        microbatch ``m+1`` is issued while stage compute for ``m`` runs
        (double-buffered boundary: the permute has no data dependency on
        the slot's compute);
      * the last stage's output runs final-norm + lm-head + sum-CE; slots
        still in warmup are masked out of the accumulator (their inputs
        are clamped REAL microbatches, so no NaN can leak through the
        mask's cotangent).
    """
    import jax.numpy as _jnp
    from jax import lax as _lax
    from jax.sharding import NamedSharding as _NS

    mesh = plan.mesh
    pp = plan.pp_size
    k = pipeline.resolved_microbatches()
    num_slots, warmup, stride = schedule_slots(pp, k, pipeline.schedule)
    boundary_spec = stage_boundary_spec(plan.rules)
    boundary_sh = _NS(mesh, boundary_spec)
    pp_shift = _make_pp_shift(mesh, boundary_spec, pp)
    L = model.config.num_hidden_layers
    if L % pp:
        raise ValueError(
            f"pipeline: num_hidden_layers={L} is not divisible by "
            f"pp_size={pp} — stages must hold equal layer slabs")

    layer_specs = plan.param_specs["layers"]

    def _to_stage_slab(leaf, spec):
        # [L, ...] -> [pp, L/pp, ...]; the leading block-sharded layer dim
        # splits locally (each device's slab reshapes to [1, L/pp, ...]).
        st = leaf.reshape(pp, L // pp, *leaf.shape[1:])
        parts = list(spec)
        new_spec = P(parts[0] if parts else AXIS_PP, None, *parts[1:])
        return _lax.with_sharding_constraint(st, _NS(mesh, new_spec))

    from automodel_tpu.distributed.shardings import spec_for

    def _c(x, spec_parts):
        """Pin an intermediate to an explicit layout.  GSPMD left to itself
        propagates stage shardings BACKWARD into the loop-invariant
        microbatch stacks, which then reshard every slot (involuntary
        remats, and — the census pin violation — all-gathers over pp), so
        every per-slot tensor is constrained at its definition."""
        return _lax.with_sharding_constraint(x, _NS(mesh, P(*spec_parts)))

    tok_spec = tuple(spec_for(("act_batch", "act_seq_nosp"), plan.rules))

    def pipeline_loss(params, mb):
        unconsumed = set(mb) - set(PIPELINE_BATCH_KEYS)
        if unconsumed:
            raise ValueError(
                f"pipeline: batch keys {sorted(unconsumed)} are not "
                f"consumed by the pipelined step (accepts "
                f"{sorted(PIPELINE_BATCH_KEYS)}) — model families needing "
                "other modalities are pp-unsafe (see training/pipeline.py).")
        mbs = split_microbatches(mb, k)
        # The stacked [k, B/k, S] microbatch arrays stay pp-REPLICATED
        # (batch over dp, seq over cp, never pp) for the whole loop.
        mbs = {key: _c(v, (None,) + tok_spec) for key, v in mbs.items()}
        ids, labels = mbs["input_ids"], mbs["labels"]
        b, S = ids.shape[1], ids.shape[2]
        pos = mbs.get("position_ids")
        if pos is None:
            pos = _c(_jnp.broadcast_to(
                _jnp.arange(S, dtype=_jnp.int32), (k, b, S)),
                (None,) + tok_spec)
        sides = {"position_ids": pos}
        for key in ("segment_ids", "attention_mask"):
            if key in mbs:
                sides[key] = mbs[key]

        slab = jax.tree.map(_to_stage_slab, params["layers"], layer_specs)
        stage_ids = _jnp.arange(pp, dtype=_jnp.int32)
        mask0 = (stage_ids == 0)[:, None, None, None]

        # All k microbatch embeddings are computed ONCE, before the slot
        # loop, exactly like the dense step would (the FSDP-sharded table's
        # lookup resolves its dp_shard conflict with dp_shard gathers,
        # outside the loop and with no pp in sight); per slot the stages
        # just SELECT their row — a local index into a pp-replicated
        # buffer.  An in-loop lookup instead hands GSPMD a per-slot
        # table/index sharding conflict that it resolves by resharding
        # across pp (the all-gather-over-pp class the census pins to zero).
        ids_flat = _c(ids.reshape(k * b, S), tok_spec)
        embs = stage_embed(model, params, ids_flat)
        embs = _c(embs.reshape(k, b, S, embs.shape[-1]),
                  (None,) + tuple(boundary_spec)[1:])

        # The slot body runs the layer slab, head and loss vmapped over the
        # stage dim — everything [pp, ...]-sharded, so the only cross-pp
        # traffic is the boundary ppermute plus the tiny all-reduces AD
        # inserts for the pp-broadcast head params.  (Per-stage head
        # compute costs nothing extra: pp-replicated compute would run the
        # identical FLOPs on every device anyway.)  Each stage's head
        # result is masked off except on the last stage; its inputs are
        # clamped REAL microbatches, so no NaN can leak through the mask's
        # cotangent.
        def _staged(slab_s, x_s, sides_s, sid, lbl):
            y = run_stage_layers(model, slab_s, x_s,
                                 sides_s["position_ids"],
                                 sides_s.get("segment_ids"),
                                 sides_s.get("attention_mask"))
            loss_s = stage_head_loss(model, loss_fn, params, y, lbl)
            return y, _jnp.where(sid == pp - 1,
                                 loss_s.astype(_jnp.float32), 0.0)

        _staged_v = jax.vmap(_staged, in_axes=(0, 0, 0, 0, None),
                             spmd_axis_name=AXIS_PP)

        def staged(slab_a, x_a, sides_a, sids_a, lbl_a):
            y, losses = _staged_v(slab_a, x_a, sides_a, sids_a, lbl_a)
            # the carry's sharding must be pinned: an unconstrained scan
            # carry lets the while-loop pick a layout that mismatches the
            # body's, resharding (over pp!) every slot
            return (_lax.with_sharding_constraint(y, boundary_sh),
                    _lax.with_sharding_constraint(losses,
                                                  _NS(mesh, P(AXIS_PP))))

        def _embs_at(ts):
            # [pp, B_mb, S, H]: the entry embedding each stage would start
            # at slot ts (only stage 0's is consumed; clamping keeps the
            # rest real data so masked branches stay finite)
            m = _jnp.clip(ts - stride * stage_ids, 0, k - 1)
            return _lax.with_sharding_constraint(embs[m], boundary_sh)

        def _sides_at(t):
            m = _jnp.clip(t - stride * stage_ids, 0, k - 1)   # [pp]
            return jax.tree.map(
                lambda a: _c(a[m], (AXIS_PP,) + tok_spec), sides)

        def _label_at(t):
            m_out = t - warmup
            return _c(_lax.dynamic_index_in_dim(
                labels, _jnp.clip(m_out, 0, k - 1), 0, keepdims=False),
                tok_spec)

        zero_buf = _lax.with_sharding_constraint(
            _jnp.zeros((pp, b, S, model.config.hidden_size),
                       model.compute_dtype), boundary_sh)

        if pipeline.schedule == "1f1b":
            # Double-buffered boundary: the shift of slot t's carry (the
            # activations stage s computed at t-1) is issued at the TOP of
            # slot t, while slot t's compute consumes the ALREADY-received
            # x_cur — no data dependency between the permute and the
            # compute, so XLA overlaps them (one extra warmup/cooldown slot
            # pair per stage buys the overlap; stage stride 2).
            def slot(carry, t):
                x_cur, y_prev, acc = carry
                x_recv = pp_shift(y_prev)
                y, losses = staged(slab, x_cur, _sides_at(t), stage_ids,
                                   _label_at(t))
                x_next = _lax.with_sharding_constraint(
                    _jnp.where(mask0, _embs_at(t + 1), x_recv), boundary_sh)
                acc = acc + _jnp.where(t - warmup >= 0,
                                       _jnp.sum(losses), 0.0)
                return (x_next, y, acc), None

            x0 = _lax.with_sharding_constraint(
                _jnp.where(mask0, _embs_at(0), zero_buf), boundary_sh)
            init = (x0, zero_buf, _jnp.float32(0.0))
            (_, _, total), _ = _lax.scan(slot, init,
                                         _jnp.arange(num_slots))
        else:  # gpipe: synchronous boundary (permute -> compute dependency)
            def slot(carry, t):
                y_prev, acc = carry
                x_recv = pp_shift(y_prev)
                buf = _lax.with_sharding_constraint(
                    _jnp.where(mask0, _embs_at(t), x_recv), boundary_sh)
                y, losses = staged(slab, buf, _sides_at(t), stage_ids,
                                   _label_at(t))
                acc = acc + _jnp.where(t - warmup >= 0,
                                       _jnp.sum(losses), 0.0)
                return (y, acc), None

            init = (zero_buf, _jnp.float32(0.0))
            (_, total), _ = _lax.scan(slot, init, _jnp.arange(num_slots))
        return total

    return pipeline_loss


def _build_degenerate_pipeline_loss(model, loss_fn, k: int):
    """The pp == 1 pipeline: no stages, no permutes — just the microbatch
    split.  At ``k == 1`` this is LITERALLY the dense microbatch body (same
    call graph, bitwise-identical step); ``k > 1`` sums the split's
    sub-losses (same math, float re-association only).

    ``dropout_rng`` is a per-grad-accum-microbatch KEY, not a batch-row
    array — it must never ride the row split (reshaping its (2,) key data
    would mangle the key).  Each sub-microbatch instead folds its index
    into the group's key, so LoRA dropout masks stay decorrelated across
    the split."""
    from jax import lax as _lax

    import jax.numpy as _jnp

    def loss(params, mb):
        if k == 1:
            return _microbatch_loss(model, loss_fn, params, mb)
        # Same key gate as the pp>1 path: the split reshapes dim 0 as batch
        # ROWS, which is only true for the token-stream keys — a VLM's
        # pixel_values/image_grid_thw lead with image counts, and silently
        # row-splitting those would re-pair images with the wrong text.
        unconsumed = set(mb) - set(PIPELINE_BATCH_KEYS) - {"dropout_rng"}
        if unconsumed:
            raise ValueError(
                f"pipeline: batch keys {sorted(unconsumed)} are not "
                "row-splittable by the microbatch split (accepts "
                f"{sorted(PIPELINE_BATCH_KEYS)} + dropout_rng) — model "
                "families needing other modalities cannot use "
                "pipeline.num_microbatches > 1 (see training/pipeline.py).")
        rng_data = mb.get("dropout_rng")
        mbs = split_microbatches(
            {key: v for key, v in mb.items() if key != "dropout_rng"}, k)

        def micro_k(acc, args):
            sub, i = args
            if rng_data is not None:
                sub = dict(sub)
                sub["dropout_rng"] = jax.random.key_data(jax.random.fold_in(
                    jax.random.wrap_key_data(rng_data), i))
            return acc + _microbatch_loss(model, loss_fn, params,
                                          sub).astype(_jnp.float32), None

        total, _ = _lax.scan(micro_k, _jnp.float32(0.0),
                             (mbs, _jnp.arange(k)))
        return total

    return loss


@dataclasses.dataclass
class TrainStepFns:
    """Compiled step functions + the state shardings they were built with."""

    train_step: Callable
    eval_step: Callable
    init_opt_state: Callable
    opt_state_sharding: Any
    microbatch_sharding: Any
    # Sequence layout over the cp axis: "zigzag" makes shard_batch apply the
    # host-side zig-zag reorder (ops/zigzag.py) before placement, matching
    # the position vectors the ring derives per shard.
    cp_layout: str = "contiguous"
    cp_size: int = 1
    # Pipeline metadata (logging / bench / bubble accounting); pp_size 1
    # means the dense step (possibly with a degenerate microbatch split).
    pp_size: int = 1
    pp_schedule: Optional[str] = None
    pp_num_microbatches: Optional[int] = None

    def shard_batch(self, stacked: Dict[str, Any],
                    process_local: bool = False) -> Dict[str, Any]:
        """Place a stacked microbatch dict on the mesh with per-key specs:
        [A, B, S] token arrays get the dp x cp batch sharding; pixel_values
        [A, B, I, H, W, C] (per-row image slots, the collator contract)
        shard the batch dim over dp only (images have no sequence dim to
        context-parallelize); legacy flat [A, B_img, H, W, C] image stacks
        shard when the dp split divides, else replicate; anything else is
        replicated.

        When the plan's ``cp_layout`` is zig-zag, the batch is first
        REORDERED on the host (tokens/labels/segment ids/masks permuted
        along S, true positions injected as ``position_ids``) — once per
        step, before the async H2D staging, so the device only ever sees
        layout-ordered arrays.  The inverse is never needed: training loss
        is invariant under a consistent token/label permutation.

        ``process_local``: [A, B_local, ...] arrays hold only THIS host's dp
        rows (per-host input pipeline) — assembled into global arrays via
        ``make_array_from_process_local_data`` instead of ``device_put``.
        Replicated leaves must be host-invariant either way.

        Every placement here is an ASYNC enqueue (``device_put``/
        ``make_array_from_process_local_data`` return before the copy
        lands), which is what makes the recipe's double-buffered staging
        work: issued for batch N+1 right after step N dispatches, the H2D
        transfers overlap step N's compute instead of serializing in the
        gap between dispatches (``train_ft.py::_pull_staged``)."""
        if self.microbatch_sharding is None:
            return stacked
        if self.cp_layout == "zigzag" and self.cp_size > 1:
            from automodel_tpu.ops.zigzag import permute_batch_for_cp

            stacked = permute_batch_for_cp(stacked, self.cp_size)
        mesh = self.microbatch_sharding.mesh
        spec = self.microbatch_sharding.spec  # P(None, dp_axes, cp_axes)
        rep = NamedSharding(mesh, P())

        def axis_size(spec_entry) -> int:
            axes = (spec_entry,) if isinstance(spec_entry, str) else (
                spec_entry or ())
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            return size

        def place(key, v):
            if key in ("image_grid_thw", "video_grid_thw"):
                # [A, N, 3] grid metadata: host-invariant, replicated
                return jax.device_put(v, rep)
            if key == "position_ids" and getattr(v, "ndim", 0) == 4:
                # M-RoPE ids [A, B, S, 3]: batch/seq shard like the tokens
                sh = NamedSharding(mesh, P(*spec, None))
                if process_local:
                    return jax.make_array_from_process_local_data(
                        sh, np.asarray(v))
                return jax.device_put(v, sh)
            if key in ("pixel_values", "pixel_values_videos"):
                ndim = getattr(v, "ndim", 0)
                if ndim == 6:
                    # [A, B, I, H, W, C]: rows shard exactly like the token
                    # batch dim — this is what makes per-host VLM input work
                    sh = NamedSharding(mesh, P(*spec[:2]))
                    if process_local:
                        return jax.make_array_from_process_local_data(
                            sh, np.asarray(v))
                    return jax.device_put(v, sh)
                # legacy flat image stack: counts are data-dependent; shard
                # when the dp split divides, else replicate
                assert not process_local, (
                    "per-host input sharding needs the per-row image-slot "
                    "layout ([A, B, I, H, W, C]); flat pixel_values cannot "
                    "be assembled across hosts")
                if v.shape[1] % axis_size(spec[1]) == 0:
                    return jax.device_put(v, NamedSharding(mesh, P(*spec[:2])))
                return jax.device_put(v, rep)
            if getattr(v, "ndim", 0) == 3:
                if process_local:
                    return jax.make_array_from_process_local_data(
                        self.microbatch_sharding, np.asarray(v))
                return jax.device_put(v, self.microbatch_sharding)
            if key == "labels" and getattr(v, "ndim", 0) == 2:
                # sequence classification: one label per example [A, B] —
                # the batch dim shards like the token arrays' (and per-host
                # loaders hold only local rows, so replication would both
                # violate host-invariance and mismatch the global logits)
                sh = NamedSharding(mesh, P(*spec[:2]))
                if process_local:
                    return jax.make_array_from_process_local_data(
                        sh, np.asarray(v))
                return jax.device_put(v, sh)
            return jax.device_put(v, rep)

        return {k: place(k, v) for k, v in stacked.items()}


def build_train_step(
    model,
    tx: optax.GradientTransformation,
    loss_fn: Optional[Any] = None,
    plan: Optional[ParallelPlan] = None,
    grad_dtype: Any = jnp.float32,
    trainable_mask: Optional[Any] = None,
    pipeline: Optional[PipelineConfig] = None,
) -> TrainStepFns:
    """Build jitted ``train_step(params, opt_state, batch) ->
    (params, opt_state, metrics)`` and ``eval_step(params, batch) -> metrics``.

    ``batch`` arrays are shaped ``[A, B, S]`` with ``A`` = grad-accumulation
    steps (``A=1`` for no accumulation); the scan over ``A`` replaces the
    reference's microbatch loop + sync ctx (``train_ft.py:653-684``).

    ``trainable_mask`` (PEFT / freezing): a boolean pytree over params.
    Gradients, accumulation buffers and optimizer state then exist ONLY for
    the trainable subtree — at 1B+ scale this saves a full-model grad buffer
    per step vs masking the optimizer, and it is what allows a
    non-differentiable (e.g. int8 weight-only quantized) frozen base.
    ``tx`` must be UNMASKED in this mode; frozen leaves are closed over.

    ``pipeline`` (:class:`~automodel_tpu.training.pipeline.PipelineConfig`):
    when the plan's mesh has ``pp > 1`` the per-A-microbatch loss runs the
    pipelined 1F1B/GPipe schedule (stage-sharded layer slab, boundary
    ``ppermute``s — see ``_build_pipeline_loss``) INSIDE the same step:
    grad accumulation, per-token normalization, clipping, the optimizer
    update and the quantized-compute plumbing are all shared with the dense
    path.  A pp=1 mesh with an explicit ``pipeline`` runs the degenerate
    schedule (microbatch split only; ``num_microbatches=1`` is bitwise the
    dense step).
    """
    loss_fn = loss_fn if loss_fn is not None else MaskedCrossEntropy()
    # Loss contract (typed, not by accident): a loss object must carry
    # ``reduction`` and ``needs_hidden`` attributes; this step normalizes by
    # the global label-token count itself, so only sum-reduction losses fit.
    for attr in ("reduction", "needs_hidden"):
        if not hasattr(loss_fn, attr):
            raise TypeError(
                f"loss_fn {type(loss_fn).__name__} does not satisfy the loss "
                f"contract: missing attribute {attr!r} (see "
                "automodel_tpu/loss/*.py for conforming implementations)")
    if loss_fn.reduction != "sum":
        raise ValueError(
            "build_train_step normalizes by the global label-token count "
            "itself; configure the loss with reduction='sum' (got "
            f"{loss_fn.reduction!r}) or it would be normalized twice.")
    # Activation sharding constraints (TP/SP plan) are read from this context
    # at trace time; identity when no plan is given.  The plan's cp layout
    # rides along so the attention dispatcher picks the matching ring
    # position scheme.
    if plan is not None:
        ctx = functools.partial(sharding_context, plan.mesh, plan.rules,
                                cp_layout=getattr(plan, "cp_layout",
                                                  "contiguous"))
    else:
        ctx = contextlib.nullcontext

    # Pipeline routing: a >1 pp extent on the plan's mesh selects the
    # pipelined microbatch loss; the schedule knobs come from ``pipeline``
    # (defaulting to 1f1b with k = pp microbatches).
    pp_size = int(getattr(plan, "pp_size", 1)) if plan is not None else 1
    if pipeline is not None and pipeline.pp_size > 1:
        if plan is None:
            raise ValueError(
                "pipeline.pp_size > 1 needs a ParallelPlan built on a mesh "
                "whose pp axis matches — the pipelined step cannot run "
                "unsharded")
        if pipeline.pp_size != pp_size:
            raise ValueError(
                f"pipeline.pp_size={pipeline.pp_size} disagrees with the "
                f"mesh's pp extent {pp_size} (distributed.pp_size) — size "
                "the mesh and the schedule identically")
    if pp_size > 1:
        if pipeline is None:
            pipeline = PipelineConfig(pp_size=pp_size)
        elif pipeline.pp_size == 1:
            # an explicit config that only picks schedule knobs: adopt the
            # mesh's pp (mirrors the recipe's _apply_pipeline_policy) so
            # num_microbatches resolves against the REAL stage count
            # instead of silently running k=1
            pipeline = dataclasses.replace(pipeline, pp_size=pp_size)
        ensure_pp_compatible(model, loss_fn, trainable_mask)
        mb_loss = _build_pipeline_loss(model, loss_fn, plan, pipeline)
    elif pipeline is not None:
        mb_loss = _build_degenerate_pipeline_loss(
            model, loss_fn, pipeline.resolved_microbatches())
    else:
        mb_loss = functools.partial(_microbatch_loss, model, loss_fn)

    def count_label_tokens(labels):
        return jnp.sum(labels != IGNORE_INDEX).astype(jnp.float32)

    from automodel_tpu.utils.pytree import combine, partition

    def split_params(params):
        """(trainable, frozen): identity split when no mask is given."""
        if trainable_mask is None:
            return params, None
        return partition(params, trainable_mask)

    def join_params(trainable, frozen):
        return trainable if frozen is None else combine(trainable, frozen)

    def train_step(params, opt_state, batch):
        num_label_tokens = count_label_tokens(batch["labels"])
        denom = jnp.maximum(num_label_tokens, 1.0)
        trainable, frozen = split_params(params)

        def loss_of(tr, mb):
            return mb_loss(join_params(tr, frozen), mb)

        grad_fn = jax.value_and_grad(loss_of)

        def micro(grads_acc, mb):
            loss_sum, grads = grad_fn(trainable, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(grad_dtype), grads_acc, grads)
            return grads_acc, loss_sum

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, grad_dtype), trainable)
        with ctx():
            grads, loss_sums = jax.lax.scan(micro, zero_grads, batch)
        # Per-token normalization across the *global* step (dp_cp psum
        # equivalent of reference base_recipe.py:354 + train_ft.py:676-681).
        grads = jax.tree.map(lambda g: g / denom, grads)
        grad_norm = optax.global_norm(grads)

        updates, opt_state = tx.update(grads, opt_state, trainable)
        trainable = optax.apply_updates(trainable, updates)
        params = join_params(trainable, frozen)
        metrics = {
            "loss": jnp.sum(loss_sums) / denom,
            "grad_norm": grad_norm,
            "num_label_tokens": num_label_tokens,
        }
        # One fused buffer alongside the per-key scalars: a device_get of
        # the dict costs one d2h round trip PER LEAF (remote runtimes pay
        # ~10 ms each; the recipe's metrics pipeline was losing ~36 ms of
        # device idle per step to exactly this), while "_packed" fetches
        # everything in a single transfer.
        metrics["_packed"] = jnp.stack(
            [metrics[k].astype(jnp.float32) for k in _PACKED_KEYS])
        return params, opt_state, metrics

    def eval_step(params, batch):
        num_label_tokens = count_label_tokens(batch["labels"])

        def micro(loss_acc, mb):
            return loss_acc + mb_loss(params, mb), None

        with ctx():
            total, _ = jax.lax.scan(micro, jnp.float32(0.0), batch)
        return {
            "loss": total / jnp.maximum(num_label_tokens, 1.0),
            "num_label_tokens": num_label_tokens,
        }

    def init_opt(params):
        # Initialize against GRAD-dtype params: with ``mu_dtype=None`` optax
        # infers moment (and injected-hyperparam) dtypes from its input, but
        # ``tx.update`` consumes ``grad_dtype`` (f32) gradients — an init
        # from raw bf16 params would flip the opt-state dtypes on the first
        # update, churning the step's jit cache key into a guaranteed
        # second XLA compile (caught by the dryrun recompile guard).  An
        # explicit ``mu_dtype`` still wins: scale_by_adam casts either way.
        trainable = split_params(params)[0]
        as_grad = jax.tree.map(
            lambda p: (p.astype(grad_dtype)
                       if jnp.issubdtype(p.dtype, jnp.floating) else p),
            trainable)
        return tx.init(as_grad)

    if plan is not None:
        mesh = plan.mesh
        abs_params = model.abstract_params()
        abs_train, _ = split_params(abs_params)
        train_specs, _ = split_params(plan.param_specs)
        abs_opt = jax.eval_shape(tx.init, abs_train)
        opt_specs = state_partition_specs(abs_opt, abs_train, train_specs)
        opt_sharding = to_named_shardings(mesh, opt_specs)
        # [A, B, S]: grad-acc axis unsharded, batch over dp, seq over cp.
        mb_sharding = NamedSharding(
            mesh, P(None, *plan.batch_sharding.spec))
        rep = NamedSharding(mesh, P())

        # The batch entry is None (inferred from the committed arrays) —
        # keys and ranks vary per recipe (VLM adds pixel_values), so a fixed
        # sharding pytree cannot cover it; ``shard_batch`` commits each leaf.
        train_jit = jax.jit(
            train_step,
            in_shardings=(plan.param_sharding, opt_sharding, None),
            out_shardings=(plan.param_sharding, opt_sharding, rep),
            donate_argnums=(0, 1),
        )
        eval_jit = jax.jit(
            eval_step,
            in_shardings=(plan.param_sharding, None),
            out_shardings=rep,
        )
        init_opt_jit = jax.jit(init_opt, out_shardings=opt_sharding)
        return TrainStepFns(train_jit, eval_jit, init_opt_jit,
                            opt_sharding, mb_sharding,
                            cp_layout=getattr(plan, "cp_layout",
                                              "contiguous"),
                            cp_size=int(dict(mesh.shape).get("cp", 1)),
                            pp_size=pp_size,
                            pp_schedule=(pipeline.schedule
                                         if pipeline is not None else None),
                            pp_num_microbatches=(
                                pipeline.resolved_microbatches()
                                if pipeline is not None else None))

    return TrainStepFns(
        jax.jit(train_step, donate_argnums=(0, 1)),
        jax.jit(eval_step),
        jax.jit(init_opt),
        None, None,
        pp_schedule=(pipeline.schedule if pipeline is not None else None),
        pp_num_microbatches=(pipeline.resolved_microbatches()
                             if pipeline is not None else None),
    )


def stack_microbatches(microbatches) -> Dict[str, jnp.ndarray]:
    """Stack a list of collated microbatch dicts into [A, B, S] arrays.

    Every microbatch must carry the same keys — a key present in some but not
    all microbatches is a collation bug (e.g. segment_ids emitted for only
    part of a packed batch), so it raises instead of silently dropping.
    Microbatches collated to different sequence lengths are right-padded to
    the longest using the per-key pad convention (labels -> -100 etc.).
    """
    from automodel_tpu.datasets.utils import get_pad_token_from_key

    keys = set(microbatches[0])
    for mb in microbatches[1:]:
        if set(mb) != keys:
            raise ValueError(
                f"Inconsistent microbatch keys: {sorted(keys)} vs {sorted(mb)}")
    out = {}
    for k in sorted(keys):
        arrs = [np.asarray(mb[k]) for mb in microbatches]
        if all(a.shape == arrs[0].shape for a in arrs[1:]):
            # fixed-shape fast path (packed sequences, pad_seq_len_divisible
            # with one bucket, A=1): no per-key pad scan, straight to stack —
            # this is the hot-loop common case
            out[k] = np.stack(arrs, axis=0)
            continue
        if k in ("pixel_values", "pixel_values_videos"):
            # Image counts vary per microbatch.  Per-row slot layout
            # [B, I, ...]: pad the slot dim I; legacy flat [B_img, ...]: pad
            # the image list.  Trailing pads are never referenced (each
            # row's placeholder count matches its real images).
            if arrs[0].ndim == 5:
                max_slots = max(a.shape[1] for a in arrs)
                arrs = [
                    np.pad(a, [(0, 0), (0, max_slots - a.shape[1])]
                           + [(0, 0)] * (a.ndim - 2))
                    for a in arrs
                ]
            else:
                max_imgs = max(a.shape[0] for a in arrs)
                arrs = [
                    np.pad(a,
                           [(0, max_imgs - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
                    for a in arrs
                ]
        elif k in ("image_grid_thw", "video_grid_thw"):
            # image counts vary per microbatch: zero-pad the image dim
            max_n = max(a.shape[0] for a in arrs)
            arrs = [np.pad(a, [(0, max_n - a.shape[0]), (0, 0)])
                    for a in arrs]
        elif k == "input_audio_embeds":
            # [B, T, input_size]: the varying dim is T (longest clip per
            # microbatch), not the trailing feature dim — zero-pad frames
            # (audio_attention_mask is [B, T], covered by last-dim padding)
            max_t = max(a.shape[1] for a in arrs)
            arrs = [np.pad(a, [(0, 0), (0, max_t - a.shape[1]), (0, 0)])
                    for a in arrs]
        elif k == "position_ids" and arrs[0].ndim == 3:
            # M-RoPE ids [B, S, 3]: the padded dim is S, not the trailing
            # section axis; pad value 1 (the HF masked-position convention)
            max_s = max(a.shape[1] for a in arrs)
            arrs = [np.pad(a, [(0, 0), (0, max_s - a.shape[1]), (0, 0)],
                           constant_values=1)
                    for a in arrs]
        else:
            max_s = max(a.shape[-1] for a in arrs)
            if any(a.shape[-1] != max_s for a in arrs):
                pad_val = get_pad_token_from_key(k) or 0
                arrs = [
                    np.pad(a,
                           [(0, 0)] * (a.ndim - 1) + [(0, max_s - a.shape[-1])],
                           constant_values=pad_val)
                    for a in arrs
                ]
        out[k] = np.stack(arrs, axis=0)
    return out
