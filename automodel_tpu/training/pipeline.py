"""Pipeline parallelism over the mesh's ``pp`` axis: config, microbatch
splitting, the 1F1B/GPipe schedule arithmetic, and the per-stage forward.

The mesh has carried ``pp`` as an explicit seam since the seed
(``distributed/mesh.py``); this module is the machinery that makes it real.
The execution model (see the pipelined step in ``training/train_step.py``):

* **Stage splitting** — every ``[L, ...]`` layer-stacked parameter is
  sharded over ``pp`` along its leading dim (``shardings.default_rules(
  pipeline_parallel=True)``), so stage ``s`` owns layers
  ``[s*L/pp, (s+1)*L/pp)``.  Inside the step the slab is viewed as
  ``[pp, L/pp, ...]`` and stage compute is ``jax.vmap(...,
  spmd_axis_name="pp")`` over the leading dim: within a stage the existing
  FSDP/TP/SP activation rules apply unchanged (``spmd_axis_name`` prefixes
  ``pp`` onto every sharding constraint the model emits).
* **Schedule** — each grad-accumulation microbatch ``[B, S]`` splits into
  ``num_microbatches`` pipeline microbatches ``[k, B/k, S]`` and runs a
  rolled loop of ``num_slots`` iterations: warmup (stages fill), steady
  state, cooldown (stages drain).  Boundary activations move to the next
  stage via ``jax.lax.ppermute`` under a full-manual ``shard_map``
  (``training/train_step.py::_make_pp_shift`` — the census-pinned seam);
  the backward pass is the AD mirror, so activation-grads ride the inverse
  permutes through the same seam.  Grad ACCUMULATION stays outside the
  microbatch loop: the ``[A, ...]`` scan of the dense step wraps the whole
  pipeline, exactly as it wraps the dense microbatch body.
* **Schedules** — ``1f1b`` (default) double-buffers the stage boundary:
  each iteration issues the permute for the PREVIOUS iteration's boundary
  activation while computing the current microbatch, so the send for
  microbatch ``m+1`` overlaps stage compute for ``m`` (cost: one extra
  warmup/cooldown slot pair per stage).  ``gpipe`` sends synchronously
  (permute -> compute dependency, smaller bubble, no overlap).  Both are
  mathematically exact: loss/grads match the dense step to float
  re-association.

Model compatibility: the stage forward re-plays the STOCK Llama-family
forward (``models/llama.py::forward_embeds``) split at layer-slab
boundaries, so it is valid exactly for models that use that forward and
carry ``pp_safe = True``.  Models that consume the stream by scan order or
pool a last token (sequence classification), merge modality features
(VLMs), own a different forward (Gemma/DeepSeek/GPT-2), or emit per-layer
aux losses (MoE) are rejected loudly — see :func:`ensure_pp_compatible`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

# Pipeline schedule domain ("pipeline.schedule", validated at config load +
# after CLI overrides via config/loader._enum_fields).
PP_SCHEDULES = ("1f1b", "gpipe")
PP_SCHEDULE_DEFAULT = "1f1b"

# Batch keys the pipelined step understands.  The stage forward consumes the
# per-token aux keys; labels feed the last stage's loss.  Anything else
# (pixel_values, audio, M-RoPE ids) belongs to model families that are
# pp-unsafe anyway.
PIPELINE_BATCH_KEYS = ("input_ids", "labels", "position_ids",
                       "segment_ids", "attention_mask")


def normalize_pp_schedule(v: Any) -> Optional[str]:
    """Null spellings -> None (use the default); lower-cases real names."""
    from automodel_tpu.config.loader import normalize_null_spelling

    v = normalize_null_spelling(v)
    if v is None:
        return None
    return str(v).lower()


def validate_pp_schedule(v: Optional[str]) -> str:
    v = normalize_pp_schedule(v)
    if v is None:
        return PP_SCHEDULE_DEFAULT
    if v not in PP_SCHEDULES:
        raise ValueError(
            f"pipeline.schedule must be one of {list(PP_SCHEDULES)} (or "
            f"null for the default {PP_SCHEDULE_DEFAULT!r}), got {v!r}")
    return v


@dataclasses.dataclass
class PipelineConfig:
    """``pipeline:`` YAML section.

    ``pp_size``: pipeline stages.  Must agree with ``distributed.pp_size``
    when both are given; when only this one is set the recipe injects it
    into the mesh build.  ``num_microbatches`` (k): pipeline microbatches
    per grad-accumulation microbatch; None resolves to ``pp_size`` (the
    smallest schedule that keeps every stage busy once).  ``schedule``:
    see :data:`PP_SCHEDULES`.
    """

    pp_size: int = 1
    schedule: str = PP_SCHEDULE_DEFAULT
    num_microbatches: Optional[int] = None

    def __post_init__(self):
        from automodel_tpu.config.loader import normalize_null_spelling

        pp = normalize_null_spelling(self.pp_size)
        self.pp_size = 1 if pp is None else int(pp)  # 0 must REACH the guard
        self.schedule = validate_pp_schedule(self.schedule)
        nm = normalize_null_spelling(self.num_microbatches)
        self.num_microbatches = None if nm is None else int(nm)
        if self.pp_size < 1:
            raise ValueError(
                f"pipeline.pp_size must be >= 1, got {self.pp_size}")
        if self.num_microbatches is not None and self.num_microbatches < 1:
            raise ValueError(
                f"pipeline.num_microbatches must be >= 1 (or null for the "
                f"pp_size default), got {self.num_microbatches}")

    def resolved_microbatches(self) -> int:
        return (self.num_microbatches if self.num_microbatches is not None
                else self.pp_size)


def build_pipeline_config(cfg) -> PipelineConfig:
    """PipelineConfig from a ConfigNode/dict (None -> pp disabled)."""
    if cfg is None:
        return PipelineConfig()
    raw = cfg.to_dict() if hasattr(cfg, "to_dict") else dict(cfg)
    fields = {f.name for f in dataclasses.fields(PipelineConfig)}
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"unknown pipeline keys: {sorted(unknown)} "
                         f"(known: {sorted(fields)})")
    return PipelineConfig(**raw)


def validate_pipeline_batch(global_batch_size: int, num_microbatches: int,
                            dp_size: int) -> None:
    """The config-level divisibility contract: every pipeline microbatch
    must still span the full dp extent, so the global batch has to split
    evenly into ``num_microbatches`` groups of ``dp_size``-divisible rows.
    Raised at recipe setup — before any mesh or step is built — with the
    numbers spelled out."""
    denom = num_microbatches * dp_size
    if global_batch_size % denom:
        raise ValueError(
            f"pipeline: step_scheduler.global_batch_size="
            f"{global_batch_size} is not divisible by "
            f"pipeline.num_microbatches x dp_size = {num_microbatches} x "
            f"{dp_size} = {denom}; every pipeline microbatch must hold an "
            "equal, dp-shardable slice of the batch — adjust "
            "global_batch_size or num_microbatches")


def split_microbatches(mb: Dict[str, Any], k: int) -> Dict[str, Any]:
    """Split one grad-accumulation microbatch ``{key: [B, ...]}`` into
    ``{key: [k, B/k, ...]}`` pipeline microbatches (contiguous row groups,
    so host-side batch semantics are unchanged).  Raises on non-divisible
    batch dims — a silent drop or pad here would change the loss
    normalization."""
    import jax

    if k < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {k}")

    def split(x):
        b = x.shape[0]
        if b % k:
            raise ValueError(
                f"pipeline: batch dim {b} is not divisible by "
                f"num_microbatches={k} — the microbatch splitter cannot "
                "form equal pipeline microbatches (check "
                "step_scheduler.global_batch_size vs "
                "pipeline.num_microbatches)")
        return x.reshape(k, b // k, *x.shape[1:])

    return {key: split(v) for key, v in mb.items() if v is not None}


def schedule_slots(pp_size: int, num_microbatches: int,
                   schedule: str = PP_SCHEDULE_DEFAULT
                   ) -> Tuple[int, int, int]:
    """``(num_slots, warmup_slots, stage_stride)`` of the rolled schedule.

    ``stage_stride`` is the iteration gap between stage ``s`` and ``s+1``
    working on the same microbatch: 1 for ``gpipe`` (synchronous boundary),
    2 for ``1f1b`` (double-buffered boundary — the permute issued at slot
    ``t`` delivers the input consumed at ``t+1``, overlapping slot ``t``'s
    compute).  ``warmup_slots`` is also the cooldown length; microbatch
    ``m`` leaves the last stage at slot ``m + warmup_slots``.
    """
    schedule = validate_pp_schedule(schedule)
    stride = 2 if schedule == "1f1b" else 1
    warmup = stride * (pp_size - 1)
    return num_microbatches + warmup, warmup, stride


# ---------------------------------------------------------------------------
# pp-compatibility gate
# ---------------------------------------------------------------------------
def ensure_pp_compatible(model, loss_fn=None, trainable_mask=None) -> None:
    """Raise (loudly, naming the model) unless the pipelined step can run
    this configuration.

    The stage forward replays the stock Llama-family forward split at layer
    boundaries, so pipelining is valid exactly when the model (a) opts in
    via ``pp_safe = True``, and (b) actually uses that forward.  Models that
    pool a last token (sequence classification), merge modality features by
    scan order (VLMs), or own a different decoder loop are rejected here;
    MoE aux losses are additionally rejected at trace time (the per-layer
    aux would need cross-stage combination that is not wired).
    """
    name = type(model).__name__
    if not getattr(model, "pp_safe", False):
        raise ValueError(
            f"pipeline parallelism: {name} is not pp-safe — its forward "
            "consumes the stream in a way stage splitting would break "
            "(last-token pooling, modality-feature merge, or a family-"
            "specific decoder loop).  Set pp_size 1 / remove the pipeline: "
            "block, or pick a Llama-family causal LM (pp_safe = True).")
    from automodel_tpu.models.llama import LlamaForCausalLM

    if type(model).forward_embeds is not LlamaForCausalLM.forward_embeds:
        raise ValueError(
            f"pipeline parallelism: {name} overrides forward_embeds — the "
            "stage forward replays the stock Llama-family layer scan and "
            "cannot reproduce a family-specific forward; pp for this "
            "family needs its own stage decomposition.")
    if loss_fn is not None and getattr(loss_fn, "needs_hidden", False):
        raise ValueError(
            "pipeline parallelism: hidden-state losses "
            f"({type(loss_fn).__name__}) are not wired through the "
            "pipelined step yet — its last stage computes logits and a "
            "logits loss.  Use loss_fn reduction='sum' masked CE "
            "(automodel_tpu.loss.masked_ce.MaskedCrossEntropy).")
    if trainable_mask is not None:
        raise ValueError(
            "pipeline parallelism: PEFT / parameter freezing "
            "(trainable_mask) is not wired through the pipelined step — "
            "adapters ride the layer stack and would need the stage-slab "
            "treatment; train full-parameter under pp or drop pp_size to 1.")


# ---------------------------------------------------------------------------
# Per-stage forward (mirrors models/llama.py::forward_embeds, split at the
# layer-slab boundary; one compiled body per stage via the pp-vmapped scan)
# ---------------------------------------------------------------------------
def stage_embed(model, params, input_ids):
    """Stage 0's entry: token embedding + scale + activation constraint —
    byte-for-byte the head of the stock forward."""
    import jax.numpy as jnp

    from automodel_tpu.distributed.shardings import constrain

    hidden = params["embed_tokens"]["embedding"][input_ids].astype(
        model.compute_dtype)
    if model._embedding_scale != 1.0:
        hidden = hidden * jnp.asarray(model._embedding_scale,
                                      model.compute_dtype)
    return constrain(hidden, ("act_batch", "act_seq", "act_embed"))


def run_stage_layers(model, slab_params, hidden, position_ids, segment_ids,
                     attention_mask):
    """One stage's local ``L/pp`` layer scan over ``hidden`` [B_mb, S, H].

    ``slab_params`` is the stage's layer slab (leading dim ``L/pp``); the
    loop is the stock forward's (``models/layer_scan.scan_layers``: remat
    and ``model.scan_block`` grouping apply per stage as they do there, so
    the pp path cannot silently grow saved-residual memory).  MoE aux
    losses are rejected at trace time — the pipelined loss has no
    cross-stage aux combination.
    """
    from automodel_tpu.models.layer_scan import SubStack, scan_layers

    inv_freq, rope_scale = model._rope_tables(position_ids)

    def layer(h, layer_params, _, idx, cache):
        h, _, aux = model._decoder_layer(
            h, layer_params, position_ids, segment_ids, attention_mask,
            inv_freq, rope_scale=rope_scale)
        if aux is not None:
            raise NotImplementedError(
                f"pipeline parallelism: {type(model).__name__} emits a "
                "per-layer aux loss (MoE load balancing) — combining aux "
                "terms across pipeline stages is not wired; use pp_size 1 "
                "for MoE families.")
        return h, None, None

    hidden, _, _ = scan_layers(
        hidden, [SubStack(slab_params, layer)], remat=model.remat,
        remat_policy=model.remat_policy, scan_block=model.scan_block,
        scan_unroll=model.scan_unroll)
    return hidden


def stage_head_loss(model, loss_fn, params, hidden, labels):
    """Last stage's exit: the stock forward's final norm + lm head
    (``models/layer_scan.norm_and_head``), then the dense step's loss
    call."""
    from automodel_tpu.models.layer_scan import norm_and_head

    cfg = model.config
    out = norm_and_head(
        hidden, params, lambda h, p: model._norm(h, p, cfg.rms_norm_eps),
        tied=cfg.tie_word_embeddings, compute_dtype=model.compute_dtype,
        logits_divisor=model._logits_divisor)
    return loss_fn(out["logits"], labels)
