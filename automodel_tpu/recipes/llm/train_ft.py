"""The LLM SFT / PEFT / pretraining trainer.

Reference parity: ``nemo_automodel/recipes/llm/train_ft.py:71-847``
(``TrainFinetuneRecipeForNextTokenPrediction``) — same YAML schema
(``step_scheduler``, ``model``, ``distributed``, ``loss_fn``, ``dataset``,
``packed_sequence``, ``dataloader``, ``optimizer``, ``lr_scheduler``,
``checkpoint``, ``rng``, ``peft``), same ``setup()`` +
``run_train_validation_loop()`` surface.

TPU-native hot loop: the reference's eager microbatch loop with no_sync /
CP contexts / clip / optim / LR-step (``train_ft.py:630-731``) is one jitted
train step (``automodel_tpu.training.train_step``); this file only stacks
microbatches, feeds the device, steps the host-side schedules, and logs.
"""

from __future__ import annotations

import inspect
import logging
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from automodel_tpu.checkpoint.checkpointing import build_checkpoint_config
from automodel_tpu.config.arg_parser import parse_args_and_load_config
from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.datasets.dataloader import StatefulDataLoader
from automodel_tpu.datasets.llm.packed_sequence import PackedSequence
from automodel_tpu.distributed.init import initialize_distributed
from automodel_tpu.distributed.mesh import MeshManager
from automodel_tpu.distributed.shardings import build_parallel_plan
from automodel_tpu.loss.masked_ce import MaskedCrossEntropy
from automodel_tpu.ops.kernel_lib import registry
from automodel_tpu.optim import (
    OptimizerParamScheduler,
    build_optimizer,
    set_hyperparams,
)
from automodel_tpu.recipes.base_recipe import BaseRecipe
from automodel_tpu.training.rng import StatefulRNG
from automodel_tpu.training.step_scheduler import StepScheduler
from automodel_tpu.training.timers import Timers, build_profiling_config
from automodel_tpu.training.train_step import (
    _PACKED_KEYS,
    build_train_step,
    stack_microbatches,
)
from automodel_tpu.training.utils import count_tokens

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Stateless builders (reference train_ft.py:71-423)
# ---------------------------------------------------------------------------
def build_model(cfg_model: ConfigNode):
    """Instantiate the model from YAML (``model._target_``)."""
    return cfg_model.instantiate()


def build_tokenizer(cfg: ConfigNode, model) -> Optional[Any]:
    tok_cfg = cfg.get("tokenizer")
    if isinstance(tok_cfg, ConfigNode) and "_target_" in tok_cfg:
        return tok_cfg.instantiate()
    # fall back to the model's checkpoint dir (AutoTokenizer, offline cache)
    ckpt_dir = getattr(model, "checkpoint_dir", None)
    if ckpt_dir is not None:
        try:
            from transformers import AutoTokenizer

            return AutoTokenizer.from_pretrained(ckpt_dir)
        except Exception:
            logger.warning("No tokenizer found at %s", ckpt_dir)
    return None


def _accepts_kwarg(fn, name: str) -> bool:
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    if any(p.kind == inspect.Parameter.VAR_KEYWORD
           for p in sig.parameters.values()):
        return True
    return name in sig.parameters


def build_dataset(cfg_ds: ConfigNode, tokenizer=None):
    target = cfg_ds.get("_target_")
    if target is None:
        raise ValueError("dataset config needs a _target_")
    from automodel_tpu.config.loader import resolve_target

    fn = resolve_target(target)
    if tokenizer is not None and _accepts_kwarg(fn, "tokenizer"):
        return cfg_ds.instantiate(tokenizer=tokenizer)
    return cfg_ds.instantiate()


_ATTN_BLOCKS_SAMPLE = 1024     # rows of a packed dataset the log line reads


def _attn_blocks(batches) -> Dict[str, int]:
    """``attn_blocks_run`` / ``attn_blocks_static`` of host rows that carry
    segment ids: the blocks splash attention's per-row map runs a layer and
    head against those of the causal mask alone (``ops/splash_attention.py
    ::segment_block_counts``).  Empty where the rows carry none."""
    from automodel_tpu.ops.splash_attention import segment_block_counts

    segs = [np.atleast_2d(b["segment_ids"]) for b in batches
            if b.get("segment_ids") is not None]
    if not segs:
        return {}
    run, static = segment_block_counts(np.concatenate(segs))
    return {"attn_blocks_run": run, "attn_blocks_static": static}


def _attn_blocks_note(packs) -> str:
    blocks = _attn_blocks(packs[:_ATTN_BLOCKS_SAMPLE])
    if not blocks:
        return ""
    return ", attn_blocks_run_share %.4f over the first %d rows" % (
        blocks["attn_blocks_run"] / blocks["attn_blocks_static"],
        min(len(packs), _ATTN_BLOCKS_SAMPLE))


def build_dataloader(cfg: ConfigNode, dataset, cfg_key: str = "dataloader",
                     local_batch_size: int = 1, seed: int = 0,
                     host_rows=None):
    """Dataset (+ optional packing) -> StatefulDataLoader.

    Reference ``build_dataloader`` (``train_ft.py:226-307``): PackedSequence
    wrapping when ``packed_sequence.packed_sequence_size > 0``, collate_fn
    from YAML, batch sharding handled by the device placement (not a
    per-rank sampler — see ``datasets/dataloader.py``).

    ``<cfg_key>.prefetch_depth`` >= 1 wraps the loader in the async input
    pipeline (``datasets/prefetch.py``): host-side tokenize/collate runs in
    a background producer thread with that many batches of bounded
    lookahead; 0 is the synchronous path."""
    packed_cfg = cfg.get("packed_sequence")
    if packed_cfg is not None and int(packed_cfg.get("packed_sequence_size", 0) or 0) > 0:
        dataset = PackedSequence(
            dataset,
            packed_sequence_size=int(packed_cfg.get("packed_sequence_size")),
            split_across_pack=bool(packed_cfg.get("split_across_pack", False)),
        ).pack()
        logger.info("%s: pack_fill %.4f (%d rows hold %d tokens)%s", cfg_key,
                    dataset.fill, dataset.rows, dataset.tokens,
                    _attn_blocks_note(dataset.packed_dataset))

    dl_cfg = cfg.get(cfg_key)
    kwargs: Dict[str, Any] = {}
    if isinstance(dl_cfg, ConfigNode):
        kwargs = {k: v for k, v in dl_cfg.to_dict().items()
                  if k not in ("_target_",)}
    kwargs.setdefault("batch_size", local_batch_size)
    kwargs.setdefault("seed", seed)
    if host_rows is not None:
        kwargs.setdefault("host_rows", host_rows)
    prefetch_depth = int(kwargs.pop("prefetch_depth", 0) or 0)
    target = dl_cfg.get("_target_") if isinstance(dl_cfg, ConfigNode) else None
    if target:
        from automodel_tpu.config.loader import resolve_target

        cls = resolve_target(target)
        loader = cls(dataset, **kwargs)
    else:
        loader = StatefulDataLoader(dataset, **kwargs)
    from automodel_tpu.datasets.prefetch import wrap_prefetch

    return wrap_prefetch(loader, prefetch_depth)


def build_step_scheduler(cfg_ss: Optional[ConfigNode], dp_size: int) -> StepScheduler:
    kwargs: Dict[str, Any] = dict(dp_size=dp_size)
    if cfg_ss is not None:
        kwargs.update(cfg_ss.to_dict())
    return StepScheduler(**kwargs)


def build_lr_scheduler(cfg_lr: Optional[ConfigNode],
                       opt_cfg: Optional[ConfigNode],
                       total_steps: int) -> OptimizerParamScheduler:
    lr = float(opt_cfg.get("lr", 1e-4)) if opt_cfg is not None else 1e-4
    wd = float(opt_cfg.get("weight_decay", 0.0) or 0.0) if opt_cfg is not None else 0.0
    defaults = dict(
        init_lr=0.0, max_lr=lr,
        min_lr=float(opt_cfg.get("min_lr", 0.0) or 0.0) if opt_cfg is not None else 0.0,
        lr_warmup_steps=0, lr_decay_steps=max(total_steps, 1),
        lr_decay_style="constant",
        start_wd=wd, end_wd=wd, wd_incr_steps=0, wd_incr_style="constant",
    )
    if cfg_lr is not None:
        # None-valued keys mean "unset" (keep the derived default) — so
        # ``--lr_scheduler.lr_decay_steps null`` falls back to the
        # epochs-derived horizon instead of passing None through.
        overrides = {k: v for k, v in cfg_lr.to_dict().items()
                     if k != "_target_" and v is not None}
        defaults.update(overrides)
    return OptimizerParamScheduler(**defaults)


def build_wandb(cfg: ConfigNode):
    wandb_cfg = cfg.get("wandb")
    if wandb_cfg is None or jax.process_index() != 0:
        return None
    from automodel_tpu.utils.safe_import import safe_import

    ok, wandb = safe_import("wandb")
    if not ok:
        logger.warning("wandb disabled: %s", wandb)
        return None
    try:
        return wandb.init(**{k: v for k, v in wandb_cfg.to_dict().items()})
    except Exception as e:  # offline / misconfigured
        logger.warning("wandb disabled: %s", e)
        return None


# ---------------------------------------------------------------------------
# Recipe
# ---------------------------------------------------------------------------
class TrainFinetuneRecipeForNextTokenPrediction(BaseRecipe):
    """``setup()`` then ``run_train_validation_loop()``."""

    # Reference parity: the LLM recipe does not clip unless asked; the VLM
    # recipe clips at 1.0 by default (``vlm/finetune.py:641``).
    _default_max_grad_norm: Optional[float] = None

    # Whether this recipe's batches tolerate the zig-zag cp sequence layout
    # (ops/zigzag.py).  Plain token streams do: the loss is a per-token sum,
    # invariant under a consistent permutation, and true positions ride
    # ``position_ids``.  The VLM recipe overrides this to False — its models
    # scatter image/audio features into placeholder tokens by SEQUENCE-SCAN
    # order (models/vlm.py::merge_image_embeds cumsum), which a permuted
    # stream would scramble.
    _zigzag_cp_safe: bool = True

    def __init__(self, cfg: ConfigNode):
        super().__init__()
        self.cfg = cfg

    # -- setup -------------------------------------------------------------
    def setup(self):
        cfg = self.cfg
        self.dist_info = initialize_distributed(
            **(cfg.get("dist_env").to_dict()
               if cfg.get("dist_env") is not None else {}))

        # Persistent XLA compile cache (the torch.compile-config analogue;
        # BaseRecipe hook shared with the VLM recipe).  The first train-step
        # dispatch logs its wall time so cache hits are visible.
        self._setup_compile_cache(cfg)

        # RNG
        rng_cfg = cfg.get("rng")
        self.rng = (rng_cfg.instantiate() if isinstance(rng_cfg, ConfigNode)
                    and "_target_" in rng_cfg else StatefulRNG(
                        seed=int(rng_cfg.get("seed", 42)) if rng_cfg else 42,
                        ranked=bool(rng_cfg.get("ranked", False)) if rng_cfg else False))

        # Pipeline parallelism (``pipeline:`` YAML block): resolved BEFORE
        # the mesh so ``pipeline.pp_size`` can size the pp axis when
        # ``distributed.pp_size`` is unset (both set and disagreeing is a
        # config error — one mesh, one schedule).
        from automodel_tpu.config.loader import normalize_null_spelling
        from automodel_tpu.training.pipeline import build_pipeline_config

        self.pipeline_config = build_pipeline_config(cfg.get("pipeline"))
        if self.pipeline_config.pp_size > 1:
            existing = normalize_null_spelling(cfg.get("distributed.pp_size"))
            if existing is None:
                cfg.set_by_dotted("distributed.pp_size",
                                  self.pipeline_config.pp_size)
            elif int(existing) != self.pipeline_config.pp_size:
                raise ValueError(
                    f"pipeline.pp_size={self.pipeline_config.pp_size} "
                    f"disagrees with distributed.pp_size={existing} — set "
                    "one of them (they must size the same pp axis)")

        # Mesh
        dist_cfg = cfg.get("distributed")
        if isinstance(dist_cfg, ConfigNode) and "_target_" in dist_cfg:
            self.mesh_manager = dist_cfg.instantiate()
        else:
            kwargs = dist_cfg.to_dict() if dist_cfg is not None else {}
            self.mesh_manager = MeshManager(**kwargs)
        self._apply_pipeline_policy()

        # Model + plan (cp layout policy needs the model: families can opt
        # out of the zig-zag permutation via ``zigzag_cp_safe = False``)
        self.model = build_model(cfg.get("model"))
        self._apply_cp_layout_policy()
        self._apply_moe_dispatch_policy()
        self.plan = build_parallel_plan(self.model, self.mesh_manager)
        self.param_sharding = self.plan.param_sharding

        # Loss
        loss_cfg = cfg.get("loss_fn")
        self.loss_fn = (loss_cfg.instantiate()
                        if isinstance(loss_cfg, ConfigNode) and "_target_" in loss_cfg
                        else MaskedCrossEntropy())

        # FP8/int8 quantized compute (optional)
        fp8_cfg = cfg.get("fp8")
        if fp8_cfg is not None:
            from automodel_tpu.quantization.fp8 import (
                apply_fp8_to_model,
                build_fp8_config,
            )

            apply_fp8_to_model(self.model, build_fp8_config(fp8_cfg))

        # PEFT (optional)
        self.peft_config = None
        peft_cfg = cfg.get("peft")
        mask = None
        if isinstance(peft_cfg, ConfigNode):
            from automodel_tpu.peft.lora import PeftConfig, build_lora

            self.peft_config = (peft_cfg.instantiate()
                                if "_target_" in peft_cfg
                                else PeftConfig(**peft_cfg.to_dict()))
            self.model, mask = build_lora(self.model, self.peft_config)
            self.plan = build_parallel_plan(self.model, self.mesh_manager)
            self.param_sharding = self.plan.param_sharding

        # Parameter freezing (optax mask; reference applies requires_grad
        # freezing before optimizer construction, ``vlm/finetune.py:70-89``)
        freeze_mask = self._build_freeze_mask()
        if freeze_mask is not None:
            mask = freeze_mask if mask is None else jax.tree.map(
                lambda a, b: bool(a) and bool(b), mask, freeze_mask)

        # Optimizer
        opt_cfg = cfg.get("optimizer")
        opt_kwargs = {k: v for k, v in (opt_cfg.to_dict() if opt_cfg else {}).items()
                      if k != "_target_"}
        target = opt_cfg.get("_target_") if opt_cfg is not None else None
        step_mask = None
        if isinstance(target, str) and not target.startswith("torch.optim"):
            from automodel_tpu.config.loader import resolve_target

            if getattr(getattr(self.model, "base_model", None),
                       "weight_only_quant", None):
                raise ValueError(
                    "peft.quantize_base requires the built-in optimizer "
                    "path (trainable-subtree gradients); a custom "
                    "optimizer._target_ would differentiate the int8 base")
            # custom optimizer factories own their masking (old contract)
            self.optimizer = resolve_target(target)(mask=mask, **opt_kwargs)
        else:
            # Top-level ``max_grad_norm`` (reference passes it per-call,
            # ``train_ft.py:630,689``; here clipping is fused into the
            # optimizer chain so the update stays one XLA program).  Custom
            # optimizer factories above manage their own clipping.
            max_gn = cfg.get("max_grad_norm", self._default_max_grad_norm)
            if max_gn is not None:
                opt_kwargs.setdefault("grad_clip_norm", float(max_gn))
            if isinstance(target, str):
                opt_kwargs.setdefault("name", target.rsplit(".", 1)[-1].lower())
            if opt_kwargs.get("param_groups"):
                # per-group lr_mult/wd_mult resolve against the tree the
                # optimizer actually updates (the trainable subtree under
                # PEFT/freezing) — reference optim/scheduler.py:143
                abs_p = self.model.abstract_params()
                if mask is not None:
                    from automodel_tpu.utils.pytree import partition

                    abs_p = partition(abs_p, mask)[0]
                opt_kwargs["params"] = abs_p
            # Freezing via the train step's trainable-subtree mode: grads,
            # accumulation buffers and optimizer state exist only for the
            # trainable leaves (vs optax.masked, which still pays a
            # full-tree grad buffer per step).
            self.optimizer = build_optimizer(**opt_kwargs)
            step_mask = mask

        # Jitted step; ``training.grad_dtype: bfloat16`` switches the
        # grad-accumulation buffers off fp32 (the fast SFT default in the
        # example YAMLs; fp32 remains the built-in default).
        tr_cfg = cfg.get("training")
        self._check_for_nan = bool(
            tr_cfg.get("check_for_nan", True)) if tr_cfg is not None else True
        step_kwargs: Dict[str, Any] = {}
        if tr_cfg is not None and tr_cfg.get("grad_dtype"):
            import jax.numpy as jnp

            step_kwargs["grad_dtype"] = jnp.dtype(str(tr_cfg.get("grad_dtype")))
        if (self.mesh_manager.pp_size > 1
                or cfg.get("pipeline") is not None):
            # the pipelined (or degenerate-split) step; pp-unsafe models
            # (seqcls pooling, VLMs, MoE aux) fail HERE, loudly, at setup
            step_kwargs["pipeline"] = self.pipeline_config
        self.step_fns = build_train_step(
            self.model, self.optimizer, loss_fn=self.loss_fn, plan=self.plan,
            trainable_mask=step_mask, **step_kwargs)
        # Elastic recovery hook: how to rebuild plan + step functions on a
        # SHRUNK mesh after a slice loss (BaseRecipe.recover_from_slice_loss
        # -> _rebuild_parallelism).  Captures this setup's masking/dtype
        # choices so the rebuilt step is the same program on fewer devices.
        def _parallelism_builder(mm):
            plan = build_parallel_plan(self.model, mm)
            return plan, build_train_step(
                self.model, self.optimizer, loss_fn=self.loss_fn, plan=plan,
                trainable_mask=step_mask, **step_kwargs)

        self._parallelism_builder = _parallelism_builder

        # Params: stream HF weights into shards, or fresh init
        ckpt_dir = getattr(self.model, "checkpoint_dir", None)
        if ckpt_dir is not None:
            from automodel_tpu.models.hf_io import load_hf_weights

            if self.peft_config is not None:
                if getattr(self.model.base_model, "weight_only_quant", None):
                    from automodel_tpu.quantization.weight_only import (
                        load_quantized_hf_base,
                    )

                    base = load_quantized_hf_base(
                        self.model.base_model, ckpt_dir,
                        shardings=self.param_sharding["base"])
                else:
                    base = load_hf_weights(
                        self.model.base_model, ckpt_dir,
                        shardings=self.param_sharding["base"])
                from automodel_tpu.peft.lora import init_lora_params

                self.params = init_lora_params(
                    self.model, base, self.peft_config,
                    self.rng.next_key(), self.param_sharding)
            else:
                self.params = load_hf_weights(
                    self.model, ckpt_dir, shardings=self.param_sharding)
        else:
            with self.rng:
                self.params = jax.jit(
                    self.model.init,
                    out_shardings=self.param_sharding)(self.rng.next_key())
        self.opt_state = self.step_fns.init_opt_state(self.params)

        # Data
        ss_cfg = cfg.get("step_scheduler")
        local_bs = int(ss_cfg.get("local_batch_size", 1)) if ss_cfg else 1
        # The loader yields GLOBAL microbatches (see datasets/dataloader.py):
        # reference local_batch_size is per-dp-rank, so the global microbatch
        # is local_bs x dp_size.
        global_mb = local_bs * self.mesh_manager.dp_size
        self.timers = Timers()      # before the loader: its thread records
        self._setup_data(global_mb)

        # Schedules
        ss_kwargs = ss_cfg.to_dict() if ss_cfg is not None else {}
        ss_kwargs.pop("local_batch_size", None)
        self.step_scheduler = StepScheduler(
            dp_size=self.mesh_manager.dp_size,
            local_batch_size=local_bs,
            dataloader=self.dataloader, **ss_kwargs)
        total = self._total_optim_steps(ss_kwargs)
        self.lr_scheduler = build_lr_scheduler(
            cfg.get("lr_scheduler"), cfg.get("optimizer"), total)
        # Checkpointed regime record for elastic recovery: the rescale after
        # a slice loss is computed from the regime the RESTORED checkpoint
        # was saved under (utils/elastic.ElasticState).
        from automodel_tpu.utils.elastic import ElasticState

        self.elastic_state = ElasticState(
            self.mesh_manager.dcn_dp_size, self.step_scheduler.grad_acc_steps)

        # Kernel block-size autotune (after the compile cache so the
        # winner cache lands beside it; before the first train-step trace
        # so a cold sweep's choices are what the step compiles with)
        self._setup_kernel_autotune(
            cfg, model=self.model,
            # packed rows pin S exactly; the VLM subclass pins it via
            # dataloader.fixed_length; unpacked-variable runs sweep nothing
            # (their bucketed shapes still hit any warm cache entries)
            seq_len=(int(cfg.get("packed_sequence.packed_sequence_size", 0)
                         or 0)
                     or int(cfg.get("dataloader.fixed_length", 0) or 0)
                     or None),
            local_batch=local_bs,
            # cp>1 dispatch resolves to the ring, so the plan sweeps the
            # ring's inner-tile key instead of splash
            cp=getattr(self.mesh_manager, "cp_size", 1))

        self.checkpoint_config = build_checkpoint_config(cfg.get("checkpoint"))
        if self.peft_config is not None:
            self.checkpoint_config.is_peft = True
        # Elastic multi-slice recovery (``elastic:`` YAML section): slice-
        # loss detection + in-place shrink/restore (utils/elastic.py).
        from automodel_tpu.utils.elastic import build_elastic_config

        self.elastic_config = build_elastic_config(cfg.get("elastic"))
        if (self.elastic_config.enabled
                and self.mesh_manager.dcn_dp_size < 2):
            logger.warning(
                "elastic.enabled with dcn_dp_size=%d: slice loss is only "
                "recoverable in-place with >= 2 slices (a single-slice "
                "loss is a full-pool loss — resume happens via relaunch)",
                self.mesh_manager.dcn_dp_size)
        self.profiling = build_profiling_config(cfg.get("profiling"))
        self._tracing = False
        self.wandb = build_wandb(cfg)
        # resume if a checkpoint exists
        self.load_checkpoint()
        return self

    def _total_optim_steps(self, ss_kwargs: Dict[str, Any]) -> int:
        """LR-decay horizon: ``max_steps`` when set, else epochs x
        steps-per-epoch from the dataloader length (the reference derives it
        from the scheduler, ``train_ft.py:350-380``) — an epochs-driven run
        must not decay over an arbitrary 1000-step horizon."""
        if ss_kwargs.get("max_steps"):
            return int(ss_kwargs["max_steps"])
        sched = self.step_scheduler
        try:
            steps_per_epoch = len(self.dataloader) // sched.grad_acc_steps
        except TypeError:  # iterable dataset without a length
            logger.warning(
                "lr horizon: no max_steps and the dataloader has no length; "
                "defaulting lr_decay_steps to 1000 — set "
                "step_scheduler.max_steps or lr_scheduler.lr_decay_steps")
            return 1000
        return max(steps_per_epoch * max(sched.num_epochs, 1), 1)

    def _apply_cp_layout_policy(self):
        """Resolve the cp sequence layout before any plan is built.

        The MeshManager defaults to zig-zag when cp > 1 (causal load
        balancing, ``ops/zigzag.py``); recipes whose batches are NOT
        permutation-safe (``_zigzag_cp_safe``) drop that default back to
        contiguous unless the YAML asked for zig-zag explicitly.  Every
        plan/train-step built afterwards inherits the decision, and
        ``shard_batch`` applies the matching host-side batch reorder."""
        cp = getattr(self.mesh_manager, "cp_size", 1)
        layout = getattr(self.mesh_manager, "cp_layout", "contiguous")
        if cp <= 1:
            return
        from automodel_tpu.ops.zigzag import normalize_cp_layout

        # Null spellings mean "use the default" (same normalization as
        # MeshManager) — only a real layout name is an explicit user choice
        # that overrides the safety fallback below.
        explicit = normalize_cp_layout(
            self.cfg.get("distributed.cp_layout")) is not None
        safe = (self._zigzag_cp_safe
                and getattr(self.model, "zigzag_cp_safe", True))
        if layout == "zigzag" and not safe and not explicit:
            logger.warning(
                "cp=%d: dropping the default zig-zag sequence layout back "
                "to contiguous — %s/%s consumes the token stream by "
                "sequence-scan order (modality-feature merge or last-token "
                "pooling), which a permuted stream would scramble (set "
                "distributed.cp_layout: zigzag to force it anyway)",
                cp, type(self).__name__, type(self.model).__name__)
            self.mesh_manager.cp_layout = layout = "contiguous"
        if self.dist_info.is_main:
            logger.info("context parallelism: cp=%d, sequence layout %r%s",
                        cp, layout,
                        " (causal load-balanced ring, masked kv tiles "
                        "skipped)" if layout == "zigzag" else "")

    def _apply_moe_dispatch_policy(self):
        """Thread the top-level ``moe.dispatch`` knob ({sorted, onehot};
        enum-validated at config load like ``distributed.cp_layout``) into
        the model config.  Models resolve None to the sorted default at
        call time (``ops/moe.py``), so this only acts on an explicit
        choice; asking for it on a non-MoE model is a loud error — the knob
        would otherwise silently do nothing."""
        from automodel_tpu.ops.moe import (
            normalize_moe_dispatch,
            validate_moe_dispatch,
        )

        dispatch = validate_moe_dispatch(
            normalize_moe_dispatch(self.cfg.get("moe.dispatch")))
        if dispatch is None:
            return
        cfg_obj = getattr(self.model, "config", None)
        if not hasattr(cfg_obj, "moe_dispatch"):
            raise ValueError(
                f"moe.dispatch={dispatch!r} set but "
                f"{type(self.model).__name__} has no routed-expert block "
                "(no model.config.moe_dispatch) — remove the knob or pick "
                "an MoE model family")
        cfg_obj.moe_dispatch = dispatch
        if self.dist_info.is_main:
            logger.info("MoE expert dispatch: %s%s", dispatch,
                        " (sort-based grouped matmuls)"
                        if dispatch == "sorted" else
                        " (GShard one-hot dispatch/combine oracle)")

    def _apply_pipeline_policy(self):
        """Reconcile the ``pipeline:`` block with the built mesh and check
        the batch arithmetic BEFORE any step is built.

        ``distributed.pp_size > 1`` without a ``pipeline:`` block gets the
        default schedule (1f1b, k = pp).  The divisibility contract is
        validated here with the numbers spelled out: the global batch must
        split into ``num_microbatches`` equal dp-shardable groups
        (``training/pipeline.py::validate_pipeline_batch``), and each
        grad-accumulation microbatch's ``local_batch_size`` must split into
        ``num_microbatches`` pipeline rows."""
        import dataclasses as _dc

        from automodel_tpu.training.pipeline import validate_pipeline_batch
        from automodel_tpu.training.timers import pp_bubble_fraction

        pp = self.mesh_manager.pp_size
        self._pp_bubble = None
        if pp <= 1:
            # the degenerate (pp=1) microbatch split still needs the
            # divisibility contract enforced at SETUP, not at first trace
            k = self.pipeline_config.resolved_microbatches()
            if k > 1:
                ss = self.cfg.get("step_scheduler")
                local_bs = int(ss.get("local_batch_size", 1)) if ss else 1
                if local_bs % k:
                    raise ValueError(
                        f"pipeline: step_scheduler.local_batch_size="
                        f"{local_bs} is not divisible by "
                        f"pipeline.num_microbatches={k} — the microbatch "
                        "split needs equal dp-shardable groups even on a "
                        "pp=1 mesh")
            return
        if self.pipeline_config.pp_size == 1:
            # distributed.pp_size sized the axis: adopt it, KEEPING any
            # explicit schedule/num_microbatches knobs from the pipeline:
            # block (replacing the whole config would silently drop them)
            self.pipeline_config = _dc.replace(self.pipeline_config,
                                               pp_size=pp)
        k = self.pipeline_config.resolved_microbatches()
        dp = self.mesh_manager.dp_size
        ss = self.cfg.get("step_scheduler")
        gbs = ss.get("global_batch_size") if ss is not None else None
        if gbs is not None:
            validate_pipeline_batch(int(gbs), k, dp)
        local_bs = int(ss.get("local_batch_size", 1)) if ss else 1
        if local_bs % k:
            raise ValueError(
                f"pipeline: step_scheduler.local_batch_size={local_bs} is "
                f"not divisible by pipeline.num_microbatches={k} — each "
                "grad-accumulation microbatch (local_batch_size x dp rows) "
                "must split into num_microbatches equal dp-shardable "
                "pipeline microbatches; raise local_batch_size or lower "
                "num_microbatches")
        self._pp_bubble = pp_bubble_fraction(
            pp, k, self.pipeline_config.schedule)
        if self.dist_info.is_main:
            logger.info(
                "pipeline parallelism: pp=%d, schedule %r, "
                "num_microbatches=%d (bubble fraction %.3f — "
                "warmup+cooldown idle over step wall; raise "
                "num_microbatches to shrink it)",
                pp, self.pipeline_config.schedule, k, self._pp_bubble)

    # -- overridable setup hooks (the VLM recipe swaps these) ---------------
    def _build_freeze_mask(self):
        """Optax trainable-mask (True = trainable) from ``freeze_config``
        YAML, or None when nothing is frozen."""
        freeze_cfg = self.cfg.get("freeze_config")
        if freeze_cfg is None:
            return None
        from automodel_tpu.utils.model_utils import apply_parameter_freezing

        return apply_parameter_freezing(
            self.model.abstract_params(), freeze_cfg)

    def _setup_data(self, global_mb: int) -> None:
        cfg = self.cfg
        self.tokenizer = build_tokenizer(cfg, self.model)
        # Leader-first dataset build: host 0 populates the shared HF
        # datasets cache (download/tokenize/map) before the others read it
        # (the reference's FirstRankPerNode role, ``utils/dist_utils.py:30``).
        from automodel_tpu.utils.dist_utils import first_rank_first

        with first_rank_first("dataset_build"):
            dataset = build_dataset(cfg.get("dataset"),
                                    tokenizer=self.tokenizer)
        # Per-host input sharding: on a multi-host mesh each host tokenizes
        # and collates only its own dp rows of every global microbatch
        # (reference: per-rank sampler, ``train_ft.py:283-307``); the shared
        # permutation seed keeps hosts agreed on row contents.
        self._host_rows = None
        if jax.process_count() > 1:
            from automodel_tpu.distributed.shardings import process_batch_rows

            self._host_rows = process_batch_rows(
                self.mesh_manager.mesh, global_mb)
            packed = cfg.get("packed_sequence.packed_sequence_size", 0)
            if not packed and cfg.get("dataset.seq_length") is None:
                logger.warning(
                    "per-host input sharding with variable-length rows: "
                    "hosts must collate to identical [B_local, S] shapes — "
                    "set packed_sequence.packed_sequence_size or "
                    "dataset.seq_length to guarantee a fixed S")
        # Unpacked training batches pad to multiples of 128 by default: the
        # splash-attention fast path needs S % 128 == 0 (ops/splash_attention
        # .py:38-48), and without this the user-facing unpacked recipes fell
        # back to XLA SDPA while only the packed bench config hit the kernel.
        if (not int(cfg.get("packed_sequence.packed_sequence_size", 0) or 0)
                and "dataloader.pad_seq_len_divisible" not in cfg):
            cfg.set_by_dotted("dataloader.pad_seq_len_divisible", 128)
        # Async input pipeline on by default for TRAINING input (2 batches of
        # background lookahead + the consumer-side staging double buffer;
        # docs/guides/input_pipeline.md).  ``dataloader.prefetch_depth: 0``
        # restores the synchronous path; validation stays synchronous (tiny,
        # and interleaved with the train stream).
        if "dataloader.prefetch_depth" not in cfg:
            cfg.set_by_dotted("dataloader.prefetch_depth", 2)
        self.dataloader = build_dataloader(
            cfg, dataset, "dataloader",
            local_batch_size=global_mb, seed=self.rng.seed,
            host_rows=self._host_rows)
        if hasattr(self.dataloader, "timers"):
            # the prefetching wrapper: its producer thread records
            # ``input_produce`` beside the loop's own timers
            self.dataloader.timers = self.timers
        self.val_dataloader = None
        if cfg.get("validation_dataset") is not None:
            val_ds = build_dataset(cfg.get("validation_dataset"),
                                   tokenizer=self.tokenizer)
            # Bucket val sequence lengths to multiples of 128: every distinct
            # [B, S] shape is a fresh XLA compile of eval_step, and unpadded
            # val batches recompile per batch (VERDICT weak #9).
            if "validation_dataloader.pad_seq_len_divisible" not in cfg:
                cfg.set_by_dotted(
                    "validation_dataloader.pad_seq_len_divisible", 128)
            # Validation stays on the GLOBAL loader even when training input
            # is host-sharded: with variable-length rows each host would pad
            # its local slice to a different S and the global [B, S] could
            # not be assembled; val sets are small, so the global collate
            # cost is irrelevant.
            self.val_dataloader = build_dataloader(
                cfg, val_ds, "validation_dataloader",
                local_batch_size=global_mb, seed=self.rng.seed)

    # -- hot loop ----------------------------------------------------------
    def _device_batch(self, batches: List[Dict[str, np.ndarray]],
                      train: bool = True,
                      process_local: Optional[bool] = None):
        if process_local is None:
            process_local = getattr(self, "_host_rows", None) is not None
        stacked = stack_microbatches(batches)
        stacked.pop("loss_mask", None)  # already folded into labels
        if train and getattr(self.model, "wants_dropout_rng", False):
            # One rng per microbatch (LoRA dropout); derived from (seed,
            # optimizer step) — NOT the ranked per-host stream — so every
            # host agrees on the replicated key data, and key data rides the
            # batch so the jitted step stays rng-free state-wise.
            step_key = jax.random.fold_in(
                jax.random.key(self.rng.seed), self.step_scheduler.step)
            stacked["dropout_rng"] = np.stack([
                np.asarray(jax.random.key_data(k))
                for k in jax.random.split(step_key, len(batches))])
        return self.step_fns.shard_batch(stacked, process_local=process_local)

    def _run_train_optim_step(self, batches: List[Dict[str, np.ndarray]]):
        """Dispatch one optimizer step and return metrics WITHOUT stalling
        the device pipeline.

        The jitted step is async; fetching ``loss`` right here would insert
        a host<->device round trip between every two steps (cost on a
        directly attached chip not measured).  Instead the device metrics of
        step N are fetched when step N+1 has been dispatched — the transfer
        overlaps compute and the loop stays full.  The returned dict is the
        *latest finalized* metrics (step N-1 in steady state, tagged with
        its own ``step``); ``flush_metrics()`` drains the tail.

        Input side: when the async loop pre-staged this group
        (``_pull_staged`` parked it in ``self._staged_input`` — device batch
        plus the dataloader's resume snapshot), the H2D transfer was already
        issued while the previous step computed; the snapshot is committed
        to the loader right after dispatch, so checkpoints persist the state
        of the last batch actually trained on (never a staged-but-
        undispatched lookahead).  Direct callers (bench, tests) stage inline
        as before.
        """
        num_tokens, _ = count_tokens(batches)
        prof = self.profiling
        self._profile_trace_window()
        self.lr_scheduler.step(1)
        self.opt_state = set_hyperparams(
            self.opt_state, lr=self.lr_scheduler.current_lr,
            wd=self.lr_scheduler.current_wd)
        staged = self.__dict__.pop("_staged_input", None)
        if staged is None:
            with self.timers.record("data_staging"):
                batch = self._device_batch(batches)
            dl_state = None
        else:
            batch, dl_state = staged
        t0 = time.perf_counter()
        if prof.enabled and prof.barrier:
            # Measurement mode: block on this step's device results so
            # step_e2e is true per-step latency (forfeits dispatch overlap).
            with self.timers.record("step_e2e", step=self.step_scheduler.step):
                self.params, self.opt_state, metrics = self.step_fns.train_step(
                    self.params, self.opt_state, batch)
                jax.block_until_ready(metrics)  # lint: disable=L004 (profiling.barrier measurement mode only: per-step latency is the thing being measured; dispatch overlap is forfeited on purpose)
        else:
            # once the step has resolved splash attention, its span says
            # how many blocks this step's rows make the kernel run
            blocks = (_attn_blocks(batches) if registry.resolved_rungs().get(
                "attention.splash") else {})
            with self.timers.record("dispatch", step=self.step_scheduler.step,
                                    **blocks):
                self.params, self.opt_state, metrics = self.step_fns.train_step(
                    self.params, self.opt_state, batch)
        if not getattr(self, "_first_dispatch_logged", False):
            # The first dispatch traces + XLA-compiles before returning;
            # later dispatches are sub-ms enqueues.  Logging the wall time
            # makes persistent-compile-cache hits visible: warm, this drops
            # from tens of seconds to about one (utils/compile_utils.py).
            self._first_dispatch_logged = True
            logger.info(
                "first train-step dispatch took %.2fs (includes XLA "
                "compile; persistent compile cache at %s)",
                time.perf_counter() - t0,
                jax.config.jax_compilation_cache_dir or "none")
        if dl_state is not None and hasattr(self.dataloader, "commit_state"):
            # this group is now consumed: a checkpoint from here on resumes
            # at the batch AFTER it
            self.dataloader.commit_state(dl_state)
        pending = {
            "device_metrics": metrics,
            "step": self.step_scheduler.step,
            "lr": self.lr_scheduler.current_lr,
            "num_tokens": num_tokens,
            "t_dispatch": t0,
        }
        prev, self._pending_metrics = (
            getattr(self, "_pending_metrics", None), pending)
        if prev is not None and not prev.get("reported"):
            self.last_metrics = self._finalize_metrics(prev)
        elif prev is None:
            # First step after start/flush: nothing pending — finalize this
            # one immediately (pays one sync, once) and mark it reported so
            # the next call doesn't emit the same step twice.
            self.last_metrics = self._finalize_metrics(pending)
            pending["reported"] = True
        return self.last_metrics

    def _finalize_metrics(self, pending) -> Dict[str, Any]:
        dmv = pending["device_metrics"]
        # the fetch of a dispatched step's scalars: where the loop waits for
        # the device
        with self.timers.record("finalize_metrics", step=pending["step"]):
            if "_packed" in dmv:
                # single d2h transfer for all scalars; element order is owned
                # by train_step._PACKED_KEYS (f32 buffer — token counts exact
                # below 2^24 per step, see the list's comment)
                vals = jax.device_get(dmv["_packed"])
                dm = {k: float(v) for k, v in zip(_PACKED_KEYS, vals)}
            else:
                dm = jax.device_get(dmv)
        dt = time.perf_counter() - pending["t_dispatch"]
        # NaN/inf guard (the reference's check_for_nan_in_grad role,
        # ``distributed/parallelizer.py:478``): fail fast instead of
        # training on garbage; ``training.check_for_nan: false`` disables.
        if getattr(self, "_check_for_nan", True) and not (
                np.isfinite(dm["loss"]) and np.isfinite(dm["grad_norm"])):
            raise FloatingPointError(
                f"non-finite training signal at step {pending['step']}: "
                f"loss={float(dm['loss'])}, grad_norm="
                f"{float(dm['grad_norm'])} (divergence or bad batch; "
                "set training.check_for_nan: false to continue anyway)")
        out = {
            "loss": float(dm["loss"]),
            "grad_norm": float(dm["grad_norm"]),
            "lr": pending["lr"],
            "num_label_tokens": int(dm["num_label_tokens"]),
            "step": pending["step"],
            "tps": pending["num_tokens"] / dt,
            "step_time": dt,
        }
        # Peak device memory (reference logs GiB per step,
        # ``train_ft.py:813-825``; JAX exposes a running peak, no reset).
        # The CPU backend reports no stats (None); a backend that reports
        # must carry the peak — no guessing around a missing key.
        stats = jax.local_devices()[0].memory_stats()
        if stats is not None:
            out["peak_memory_gb"] = round(
                stats["peak_bytes_in_use"] / 1024**3, 3)
        return out

    def _profile_trace_window(self):
        """Windowed ``jax.profiler`` xplane capture: tracing spans optimizer
        steps ``[trace_start_step, trace_stop_step)`` (the nsys-window
        equivalent of reference ``timers.py:433-538``-era profiling)."""
        prof = self.profiling
        if not (prof.enabled and prof.trace_dir):
            return
        step = self.step_scheduler.step
        if (not self._tracing and prof.trace_start_step <= step
                < prof.trace_stop_step):
            jax.profiler.start_trace(prof.trace_dir)
            self._tracing = True
        elif self._tracing and step >= prof.trace_stop_step:
            self.flush_metrics()  # close the window on finished device work
            jax.profiler.stop_trace()
            self._tracing = False

    def _stop_trace(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def _timed_iter(self, iterable):
        """Yield from the step scheduler, timing the data wait (host-side
        tokenize/collate time the device spends idle)."""
        it = iter(iterable)
        while True:
            t = self.timers("data_wait")
            t.start()
            try:
                batches = next(it)
            except StopIteration:
                t.discard()
                return
            t.stop()
            yield batches

    def flush_metrics(self) -> Optional[Dict[str, Any]]:
        """Finalize the in-flight step's metrics (end of epoch / before
        checkpointing / end of bench window)."""
        pending = getattr(self, "_pending_metrics", None)
        if pending is not None:
            if not pending.get("reported"):
                self.last_metrics = self._finalize_metrics(pending)
            self._pending_metrics = None
        return getattr(self, "last_metrics", None)

    def _run_validation_epoch(self) -> Optional[float]:
        """Token-weighted mean val loss with NO per-batch host sync: each
        ``eval_step`` dispatch used to be followed by ``int(m[...])`` — a
        device round trip per val batch that stalled the pipeline.  The
        weighted sums now accumulate ON DEVICE (tiny replicated scalar adds,
        dispatched async like the eval steps themselves) and the host
        fetches once at epoch end."""
        if self.val_dataloader is None:
            return None
        import jax.numpy as jnp

        total_loss = total_tokens = None
        n_dispatched = 0
        for vb in self.val_dataloader:
            # val batches are global on every host (see _setup_data)
            batch = self._device_batch([vb], train=False,
                                       process_local=False)
            m = self.step_fns.eval_step(self.params, batch)
            n = m["num_label_tokens"]
            wl = m["loss"] * jnp.maximum(n, 1.0)  # back to the batch's sum-CE
            if total_loss is None:
                total_loss, total_tokens = wl, n
            else:
                total_loss = total_loss + wl
                total_tokens = total_tokens + n
            n_dispatched += 1
            if n_dispatched % 8 == 0:
                # Backpressure, not a fetch: without any sync the host can
                # stage the whole val set ahead of the device and every
                # in-flight input buffer stays live in HBM at once (worst
                # for VLM pixel_values).  Blocking on the running total
                # bounds the pipeline at 8 staged batches.
                jax.block_until_ready(total_loss)  # lint: disable=L004 (every-8-batches backpressure bounding staged val input in HBM, not a per-batch fetch)
        if total_loss is None:
            return None
        loss, tokens = jax.device_get((total_loss, total_tokens))  # lint: disable=L004 (the PR-2 once-per-epoch fetch: val loss accumulates on device, one d2h at epoch end)
        return float(loss) / max(float(tokens), 1.0)

    def run_train_validation_loop(self):
        sched = self.step_scheduler
        is_main = self.dist_info.is_main
        prof = self.profiling
        from automodel_tpu.utils.elastic import (
            ElasticCoordinator,
            SliceLostError,
            SliceReturnedError,
        )
        from automodel_tpu.utils.sig_utils import (
            DistributedSignalHandler,
            get_signal_name,
        )

        self.preempted = False
        # anchor the first profiling window at loop start — without it the
        # first interval's window is zero-length and ckpt_stall_fraction
        # reports 0 even when a save stalled inside it
        self._prof_window_t0 = time.perf_counter()
        ecfg = self.elastic_config
        recoveries = 0
        import signal as _signal

        try:
            # SIGTERM (pool preemption) + SIGINT (operator ^C) both take
            # the grace-window save path; a SECOND ^C still hard-aborts
            # (sig_utils chains the stdlib handler on repeat)
            with DistributedSignalHandler(
                    (_signal.SIGTERM, _signal.SIGINT)) as preempt:
                self._elastic = (
                    ElasticCoordinator(
                        self.mesh_manager,
                        heartbeat_timeout_s=ecfg.heartbeat_timeout_s,
                        signal_handler=preempt,
                        readmit_probation_polls=(
                            ecfg.readmit_probation_polls))
                    if ecfg.enabled else None)
                while True:
                    try:
                        self._train_epochs(sched, is_main, prof, preempt)
                        break
                    except SliceReturnedError as e:
                        # Grow-back: a retired slice passed probation and
                        # was admitted at a committed-checkpoint boundary
                        # (_post_step raised right after the commit landed,
                        # so the restore below loses zero steps).  A healed
                        # pool regains its FULL recovery headroom — healing
                        # must not count against the shrink budget.
                        logger.warning(
                            "slice %d re-admitted at step %d: growing the "
                            "mesh back", e.slice_id, e.detected_at_step)
                        # a grow-back admitted MID-REPLAY: bank the partial
                        # replay window first — reconfigure's wall time is
                        # elastic_rebuild, and leaving the replay timer
                        # running would double-count it in recovery_time_s
                        replay_target = getattr(self, "_replay_until", None)
                        if replay_target is not None:
                            self.timers("elastic_replay").stop()
                            self._replay_until = None
                        self.reconfigure(e)
                        self._post_slice_recovery()
                        self._elastic.mesh_manager = self.mesh_manager
                        recoveries = 0
                        if (replay_target is not None
                                and sched.step < replay_target):
                            # steps between the admission checkpoint and
                            # the original failure step are still replay
                            self._replay_until = replay_target
                            self.timers("elastic_replay").start()
                    except SliceLostError as e:
                        recoveries += 1
                        if (self._elastic is None
                                or recoveries > ecfg.max_recoveries):
                            raise
                        if e.local:
                            # THIS host's slice is the lost one: the shrunk
                            # mesh contains none of its devices — in-place
                            # recovery is impossible; exit so the relaunch
                            # path (resume-from-last-committed) takes over
                            raise
                        # the step the failure was DETECTED at (sched.step
                        # may sit one ahead under the async input lookahead)
                        failed_step = (e.detected_at_step
                                       if e.detected_at_step >= 0
                                       else sched.step)
                        logger.warning(
                            "slice loss detected at step %d: %s — "
                            "recovering (%d/%d)", failed_step, e,
                            recoveries, ecfg.max_recoveries)
                        # goodput: the failure went unseen for at most one
                        # poll interval; rebuild+restore times itself
                        self.timers("elastic_detect").add(
                            self._elastic.detect_latency_s())
                        if getattr(self, "_replay_until", None) is not None:
                            # a second loss DURING replay: bank the partial
                            # replay time before restarting the window
                            self.timers("elastic_replay").stop()
                            self._replay_until = None
                        self.recover_from_slice_loss(e)
                        self._post_slice_recovery()
                        self._elastic.mesh_manager = self.mesh_manager
                        if sched.step < failed_step:
                            # goodput: steps between the restored checkpoint
                            # and the failure are RE-trained — pure loss;
                            # the timer closes in _post_step when the run
                            # re-reaches the failed step
                            self._replay_until = failed_step
                            self.timers("elastic_replay").start()
        except BaseException:
            # teardown must not mask the propagating failure with a
            # background-save error — log it instead
            self.teardown(raise_error=False)
            raise
        # join-on-teardown: the final (possibly end-of-training) async save
        # lands — or surfaces its error — before the loop returns
        self.teardown()
        if self.preempted and is_main:
            logger.warning(
                "preemption (%s) handled at step %d: %s, exiting cleanly",
                get_signal_name(preempt.received_signal or preempt.sig),
                sched.step,
                "checkpoint saved" if getattr(self, "_preempt_saved", False)
                else "checkpointing disabled, nothing saved")

    def _post_slice_recovery(self):
        """Recipe half of an elastic topology change (shrink OR grow-back):
        rebuild the INPUT pipeline for the new mesh.  The rescale rule pins
        the per-device batch — the global microbatch is ``local_batch_size
        x dp_size`` and ``dp_size`` just changed — so the loader is rebuilt
        at the new width and resumed from the restored sample index (state
        is a SAMPLE count, so it is batch-size-independent)."""
        ss_cfg = self.cfg.get("step_scheduler")
        local_bs = int(ss_cfg.get("local_batch_size", 1)) if ss_cfg else 1
        old_loader = self.dataloader
        state = (old_loader.state_dict()
                 if hasattr(old_loader, "state_dict") else None)
        if hasattr(old_loader, "close"):
            old_loader.close()
        self._setup_data(local_bs * self.mesh_manager.dp_size)
        if state is not None and hasattr(self.dataloader, "load_state_dict"):
            self.dataloader.load_state_dict(state)
        self.step_scheduler.set_dataloader(self.dataloader)

    def _pull_staged(self, groups):
        """Pull the next grad-acc group and immediately issue its device
        staging (the second half of the async input pipeline): called right
        after step N dispatches, so batch N+1's H2D transfers overlap step
        N's compute instead of serializing before dispatch N+1.  Returns
        ``(batches, device_batch, dl_state)`` or None at exhaustion;
        ``dl_state`` is the dataloader's resume snapshot for this group —
        committed only when the group is actually dispatched, so a staged
        lookahead abandoned by preemption/max_steps is never recorded as
        consumed."""
        try:
            batches = next(groups)
        except StopIteration:
            return None
        dl_state = self.dataloader.pending_state()
        # distinct timer name: this staging runs while the previous step
        # computes (overlapped), so it must not count toward the
        # INPUT_TIMERS device-idle sum the way the sync path's inline
        # "data_staging" does
        with self.timers.record("data_staging_overlap"):
            device_batch = self._device_batch(batches)
        return batches, device_batch, dl_state

    def _run_epoch_async(self, sched, epoch, is_main, prof, preempt):
        """Hot loop over one epoch with double-buffered input staging.

        The step-N cadence flags are captured BEFORE the lookahead pull —
        pulling group N+1 advances ``sched.step`` — so logging/val/ckpt/
        preemption all see the step they belong to, and a checkpoint inside
        the body persists the state committed at dispatch N (the lookahead
        only moved the loader's *pending* snapshot).  Returns True when a
        preemption was handled."""
        groups = self._timed_iter(sched)
        try:
            staged = self._pull_staged(groups)
            while staged is not None:
                batches, device_batch, dl_state = staged
                self._staged_input = (device_batch, dl_state)
                metrics = self._run_train_optim_step(batches)
                step, is_val, is_ckpt = (sched.step, sched.is_val_step,
                                         sched.is_ckpt_step)
                # double buffer: stage batch N+1 while step N computes
                staged = self._pull_staged(groups)
                # The lookahead pull advanced sched.step to N+1 (the
                # scheduler increments at yield) — but a checkpoint inside
                # _post_step pickles the LIVE scheduler state, and saving
                # {step: N+1} against a dataloader committed at batch N
                # would shift every post-resume step number (and end a
                # max_steps run one real step early).  Hold the counter at
                # the dispatched step for the bookkeeping window; on
                # preemption leave it there — only N steps were trained.
                # CONTRACT for code inside this window: use the captured
                # step/is_val/is_ckpt arguments, never read sched.step or
                # its cadence properties directly — the generator is one
                # group ahead of the counter until the restore below.
                lookahead_step, sched.step = sched.step, step
                preempted = False
                try:
                    with self.timers.record("post_step", step=step):
                        preempted = self._post_step(
                            epoch, step, is_val, is_ckpt, metrics, is_main,
                            prof, preempt)
                finally:
                    if not preempted:
                        sched.step = lookahead_step
                if preempted:
                    return True
        finally:
            # synchronously unwind sched -> dataloader -> producer thread
            # (rewinds the loader to the last yielded batch)
            groups.close()
        return False

    def _run_epoch_sync(self, sched, epoch, is_main, prof, preempt):
        """Legacy synchronous epoch (``prefetch_depth: 0``): stage-then-
        dispatch inside ``_run_train_optim_step``, loader state read live at
        checkpoint time.  Returns True when a preemption was handled."""
        for batches in self._timed_iter(sched):
            metrics = self._run_train_optim_step(batches)
            with self.timers.record("post_step", step=sched.step):
                preempted = self._post_step(
                    epoch, sched.step, sched.is_val_step, sched.is_ckpt_step,
                    metrics, is_main, prof, preempt)
            if preempted:
                return True
        return False

    def _log_step_metrics(self, metrics, is_main) -> None:
        """Emit one finalized step's metrics, once.  Metrics lag dispatch by
        a step, so the loop calls this per step AND after the end-of-epoch
        flush — otherwise a run's last step would never be reported."""
        if (not is_main or metrics is None or metrics["step"] == getattr(
                self, "_last_logged_step", -1)):
            return
        self._last_logged_step = metrics["step"]
        logger.info(
            "step %d | loss %.4f | grad_norm %.3f | lr %.2e | "
            "tps %.0f | tokens %d",
            metrics["step"], metrics["loss"],
            metrics["grad_norm"], metrics["lr"], metrics["tps"],
            metrics["num_label_tokens"])
        if self.wandb is not None:
            self.wandb.log(metrics, step=metrics["step"])

    def _post_step(self, epoch, step, is_val, is_ckpt, metrics,
                   is_main, prof, preempt) -> bool:
        """Per-step bookkeeping after dispatch: logging, profiling cadence,
        validation, checkpointing, preemption poll.  ``step``/``is_val``/
        ``is_ckpt`` are the dispatched step's values (captured by the caller
        before any input lookahead).  Returns True when a preemption was
        handled and the epoch loop must return."""
        self._log_step_metrics(metrics, is_main)
        if (prof.enabled and step % prof.log_interval == 0):
            # per-step ms over the window; host-local, logged on main
            elapsed = self.timers.get_elapsed(
                reset=True, normalizer=prof.log_interval)
            now = time.perf_counter()
            window = now - getattr(self, "_prof_window_t0", now)
            self._prof_window_t0 = now
            if is_main and elapsed:
                from automodel_tpu.training.timers import ckpt_stall_fraction

                # fraction of the window the loop spent BLOCKED on
                # checkpointing (snapshot/join under async_save, the whole
                # save inline) — the metric the async save path exists to
                # drive toward 0; elapsed is per-step, so un-normalize
                frac = ckpt_stall_fraction(
                    {"ckpt_stall":
                     elapsed.get("ckpt_stall", 0.0) * prof.log_interval},
                    window)
                # pipeline bubble: schedule-derived warmup+cooldown idle
                # over step wall (training/timers.py::pp_bubble_fraction),
                # logged each window so the pp=​k trade-off stays visible
                bubble = getattr(self, "_pp_bubble", None)
                logger.info(
                    "step %d | time (ms)%s%s%s", step,
                    "".join(f" | {n}: {v * 1e3:.2f}"
                            for n, v in elapsed.items()),
                    (f" | ckpt_stall_fraction: {frac:.4f}"
                     if "ckpt_stall" in elapsed else ""),
                    (f" | pp_bubble_fraction: {bubble:.4f}"
                     if bubble is not None else ""))
                if self.wandb is not None:
                    log = {f"timers/{n}": v for n, v in elapsed.items()}
                    if "ckpt_stall" in elapsed:
                        log["timers/ckpt_stall_fraction"] = frac
                    if bubble is not None:
                        log["timers/pp_bubble_fraction"] = bubble
                    self.wandb.log(log, step=step)
        if is_val:
            self.flush_metrics()
            val_loss = self._run_validation_epoch()
            if val_loss is not None and is_main:
                logger.info("step %d | val_loss %.4f", step, val_loss)
                if self.wandb is not None:
                    self.wandb.log({"val_loss": val_loss}, step=step)
        if is_ckpt and self.checkpoint_config.enabled:
            # Drain the in-flight step first so its NaN guard runs
            # before the params it produced are persisted.  Under
            # checkpoint.async_save this blocks only for the host
            # snapshot (timed as ckpt_stall); the commit overlaps the
            # following steps and any failure surfaces at the next join
            # point (next save, preemption save, or end of training).
            self.flush_metrics()
            self.save_checkpoint(epoch, step)
            self._last_ckpt_step = step
            el = getattr(self, "_elastic", None)
            pending = getattr(self, "_pending_readmit", None)
            if el is not None and (pending is not None
                                   or (jax.process_count() > 1
                                       and el.mesh_manager.retired_slices)):
                # Grow-back admission happens ONLY here, at a COMMITTED
                # checkpoint boundary.  Three gates before the mesh grows:
                # (1) REVALIDATE the latch — the slice may have flapped
                #     since the poll that latched it (probation restarted);
                #     growing back over a dead slice would trade a healthy
                #     shrunk run for a broken full one;
                # (2) multi-host: the UNANIMOUS agree_readmit vote —
                #     per-host probation streaks can diverge by one poll,
                #     and every survivor (latched or not) reaches this
                #     boundary, so the vote is collective by construction;
                # (3) the commit itself: join the async save so the grow
                #     restores from it and zero steps are lost (a commit
                #     failure surfaces like any other join point).
                self._pending_readmit = None
                # per-slice readiness, NOT ready_to_readmit() equality: a
                # second retired slice finishing probation after the latch
                # must not read as a flap of the first
                candidate = (pending if pending is not None
                             and el.is_ready(pending) else None)
                if pending is not None and candidate is None:
                    logger.warning(
                        "re-admission of slice %d abandoned at step %d: "
                        "its probation streak reset since it was latched "
                        "(slice flapped); it re-qualifies after a fresh "
                        "probation window", pending, step)
                if jax.process_count() > 1:
                    candidate = el.agree_readmit(candidate, step)
                if candidate is not None:
                    self.join_pending_save()
                    from automodel_tpu.utils.dist_utils import (
                        CollectiveTimeout,
                    )

                    try:
                        event = el.admit(candidate, step)
                    except CollectiveTimeout as e:
                        # the returning hosts vanished inside the warm-up
                        # window: abort THIS admission, keep training
                        # shrunk — the pool is still healthy, and the
                        # slice re-qualifies via a fresh probation window
                        logger.warning(
                            "re-admission of slice %d aborted at step %d: "
                            "warm-up barrier timed out (%s); continuing "
                            "on the shrunk mesh", candidate, step, e)
                    else:
                        raise event
        # Close the elastic replay window: once the run has re-reached the
        # step it died at, the re-trained steps stop counting as goodput
        # loss (timer opened by the recovery loop).
        if (getattr(self, "_replay_until", None) is not None
                and step >= self._replay_until):
            self.timers("elastic_replay").stop()
            self._replay_until = None
        # Preemption poll FIRST (before the elastic health poll): a signal
        # this host already caught must take the grace-window save path —
        # under a full-pool preemption every slice looks unhealthy and the
        # elastic verdict would otherwise misread it as a slice failure.
        # signals_received is COLLECTIVE, so all hosts must call it on the
        # same steps — single-process polls every step (free); multi-host
        # every 10th (the per-step allgather would serialize async
        # dispatch; preemption grace windows are tens of seconds, so a few
        # steps of latency is fine) and at checkpoint boundaries.
        poll = (jax.process_count() == 1 or step % 10 == 0 or is_ckpt)
        if preempt is not None and poll and preempt.signals_received():
            self.flush_metrics()
            saved = False
            if (self.checkpoint_config.enabled
                    and getattr(self, "_last_ckpt_step", -1) != step):
                # Grace-window save: if it fails (preemption kill
                # landing mid-write, exhausted I/O retries), exit
                # cleanly anyway — the atomic commit protocol means
                # a failed save left only a .tmp dir and the last
                # COMMITTED checkpoint is still what resume finds.
                # Multi-host caveat: a host-local failure leaves the
                # peers blocked at the commit barrier until the
                # preemptor's hard kill — acceptable here because
                # the whole pool is being torn down regardless; the
                # point of the catch is the state guarantee, not
                # saving the doomed processes.  An async save must
                # BLOCK here until committed (join) — dispatching
                # into a background thread the preemptor is about to
                # kill would guarantee a torn .tmp every preemption.
                try:
                    self.save_checkpoint(epoch, step)
                    self.join_pending_save()
                    self._last_ckpt_step = step
                    saved = True
                except Exception:
                    logger.exception(
                        "preemption checkpoint at step %d failed; "
                        "resume will use the last committed "
                        "checkpoint", step)
            else:
                # a routine async save may still be in flight from an
                # earlier boundary: land it inside the grace window too
                try:
                    self.join_pending_save()
                except Exception:
                    # that in-flight save was the one _last_ckpt_step
                    # recorded at dispatch — it never committed, so it
                    # must not count as "saved at this step" below
                    self._last_ckpt_step = -1
                    logger.exception(
                        "in-flight background checkpoint failed during "
                        "preemption handling; resume will use the last "
                        "committed checkpoint")
            self._preempt_saved = (
                saved or getattr(self, "_last_ckpt_step", -1) == step)
            self.preempted = True
            self._stop_trace()  # may stop inside an open window
            return True
        # Elastic slice-health poll (COLLECTIVE like the preemption poll:
        # fixed step cadence so every host calls it together; it runs
        # AFTER the preemption poll so a locally-caught signal takes the
        # grace save, not a slice verdict).  A verdict raises
        # SliceLostError, which unwinds to the recovery loop in
        # run_train_validation_loop.
        el = getattr(self, "_elastic", None)
        if el is not None and step % max(
                self.elastic_config.heartbeat_interval_steps, 1) == 0:
            el.poll(step)
            # Grow-back: a retired slice that heartbeat through its full
            # probation window becomes PENDING here; admission itself is
            # deferred to the next committed-checkpoint boundary (the
            # is_ckpt branch above) so the grow's restore loses no steps.
            ready = el.ready_to_readmit()
            if ready is not None and getattr(self, "_pending_readmit",
                                             None) is None:
                if self.checkpoint_config.enabled:
                    logger.info(
                        "retired slice %d passed probation at step %d; "
                        "re-admitting at the next committed checkpoint "
                        "boundary", ready, step)
                    self._pending_readmit = ready
                elif not getattr(self, "_warned_readmit_no_ckpt", False):
                    self._warned_readmit_no_ckpt = True
                    logger.warning(
                        "retired slice %d is healthy again but "
                        "checkpointing is disabled — grow-back needs a "
                        "committed checkpoint to restore from; the run "
                        "stays at dcn_dp=%d", ready,
                        self.mesh_manager.dcn_dp_size)
        return False

    def _train_epochs(self, sched, is_main, prof, preempt=None):
        # The async input path needs the loader's consumed-state contract
        # (pending_state/commit_state — datasets/prefetch.py); a bare
        # StatefulDataLoader (prefetch_depth: 0) takes the legacy
        # synchronous loop unchanged.
        async_input = hasattr(self.dataloader, "commit_state")
        for epoch in sched.epochs:
            if hasattr(self.dataloader, "set_epoch"):
                self.dataloader.set_epoch(epoch)
            run_epoch = (self._run_epoch_async if async_input
                         else self._run_epoch_sync)
            if run_epoch(sched, epoch, is_main, prof, preempt):
                return
            self._log_step_metrics(self.flush_metrics(), is_main)
            # epoch-end / final checkpoint (reference is_ckpt_step's
            # last-batch clause): the generator sets its exhausted flag only
            # after the loop, so re-check here.
            if (self.checkpoint_config.enabled and sched.is_ckpt_step
                    and getattr(self, "_last_ckpt_step", -1) != sched.step):
                self.save_checkpoint(epoch, sched.step)
                self._last_ckpt_step = sched.step
            if sched.finished:
                break
        self._stop_trace()  # loop may end inside an open trace window
        return self


def main(config_path: Optional[str] = None, argv=None):
    """CLI entry (reference ``train_ft.py:833-847``)."""
    logging.basicConfig(level=logging.INFO)
    cfg = parse_args_and_load_config(argv, default_config=config_path)
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    recipe.run_train_validation_loop()
    return recipe


if __name__ == "__main__":
    main()
