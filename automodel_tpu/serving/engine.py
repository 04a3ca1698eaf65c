"""The decode engine: continuous batching over the block-paged KV cache.

``generation/generate.py`` is a fixed-batch prefill-then-scan loop — every
row starts together, pads to the longest prompt, and the whole batch holds
its HBM until the slowest row finishes.  A serving workload needs the
opposite: requests arrive and finish continuously, and the engine must
keep the chip busy without ever recompiling.  :class:`DecodeEngine` does
that with three static-shape ingredients:

* **step buffers** — every device step is ``[max_num_seqs, W]`` where the
  width ``W`` is 1 (pure decode) or ``prefill_chunk`` (a step carrying any
  prefill work; decode rows ride along with one valid token).  One jitted
  program per width, compiled once — admissions, finishes, preemptions,
  aborts, expiries and rejections only change the *contents* of the
  buffers (the tier-1 suite holds ``assert_compiles_once`` across a
  multi-request run);
* **the paged KV cache** (``serving/kv_cache.py``) — the stacked pools are
  donated to the step, ride the model's layer scan as its carry, and are
  scatter-written and read in place at each layer's index: no instruction
  of the step program copies, slices or reallocates a K or V pool or a
  layer of one (``tests/unit_tests/test_program_spans.py`` compiles the
  step for a v5e and looks).  Block tables are assembled host-side from the
  scheduler's plan;
* **the scheduler** (``serving/scheduler.py``) — WAITING → PREFILL →
  DECODE → FINISHED per request, chunked prefill sharing step slots with
  decode, in-flight admission when blocks free up, and recompute
  preemption under KV pressure (drilled by the ``serve_block_alloc`` fault
  point; mid-flight cancels by ``serve_request_abort``).

The request-lifecycle robustness layer rides entirely HOST-SIDE on top of
those three (the decode step's census stays collective- and
callback-free): per-request deadlines/TTLs and admission control live in
the scheduler (``serving/scheduler.py`` docstring), and the engine adds

* **a watchdog** (``serving.watchdog_s``) — when no slot makes progress
  within the window (a wedged scheduler/host loop; drilled as a stalled
  device step by the ``serve_watchdog_stall`` fault point), the engine
  aborts the in-flight batch, REBUILDS the pools (donated buffers cannot
  be trusted after a failed step), reclaims every block table, and
  replays the admitted requests from their last computed token — pinned,
  so recovery never stacks preemptions on the stall it just absorbed.
  Greedy output through a recovery stays token-identical (recompute
  semantics, tier-1 pinned);
* **graceful drain** (:meth:`DecodeEngine.drain`) — stop admitting,
  finish in-flight work, bounded by a grace deadline (then remaining
  rows EXPIRE with blocks reclaimed).  ``tools/serve.py`` wires it to
  SIGTERM/SIGINT mirroring the trainer's preemption grace window.

Greedy sampling runs on-device inside the step (one ``[B, W]`` token
fetch per step is the engine's only host sync); ``do_sample`` configs
sample host-side from the returned last-token logits.  Greedy output is
token-identical to ``generate()`` on the same model/params — the tier-1
parity oracle (``tests/unit_tests/test_serving.py``).

**Look-ahead of one step**: a greedy engine dispatches step N+1 BEFORE it
fetches step N.  A decode row's input token for N+1 is taken on the device
from N's output (``prev_tok`` / ``take_prev`` of the step program), the
pools already chain from step to step as futures, so the device goes from
program N straight to N+1 while the host fetches N, applies it
(``Scheduler.deliver``) and prepares N+2: the host's whole loop and the
launch + fetch latency are hidden behind the device step.  A token is on
the host one ``step()`` call after the call that dispatched its step.
``do_sample`` and speculation need the newest token ON the host to plan the
next step, so those engines run the same loop at depth 0 (the fetch taken
at once), decided at build.

Speculative decoding (``serving.speculative: ngram``,
``serving/speculative.py``) changes only the pure-decode width: a
host-side prompt-lookup proposer drafts up to ``serving.spec_k`` tokens
per sampling row, the step runs once at width ``spec_k + 1`` (token +
drafts written together, argmax read at every position), and the
scheduler accepts the longest draft prefix matching the greedy chain
plus the bonus token.  Compiled widths become ``{spec_k+1,
prefill_chunk}`` — acceptance churn is data, never a shape — and the
per-step host sync stays ONE fetch, now ``[B, spec_k+1]`` ints.  Greedy
output is token-identical to spec-off by construction (tier-1 pinned,
``tests/unit_tests/test_speculative.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import time
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from automodel_tpu.generation.generate import GenerationConfig, sample_logits
from automodel_tpu.ops.paged_attention import window_span_blocks
from automodel_tpu.serving.kv_cache import (
    DEFAULT_KV_CACHE_DTYPE,
    DEFAULT_PREFIX_CACHING,
    BlockAllocator,
    BlockGroup,
    CacheGroup,
    PagedKVView,
    PrefixIndex,
    StatePlaneView,
    blocks_needed,
    cache_groups,
    cow_copy_blocks,
    init_paged_pools,
    init_state_planes,
    normalize_kv_cache_dtype,
    normalize_prefix_caching,
    pool_bytes,
    sequence_planes,
    slot_for,
    validate_kv_cache_dtype,
    validate_prefix_caching,
)
from automodel_tpu.serving.scheduler import (
    DEFAULT_SCHEDULER_POLICY,
    DEFAULT_SHED_POLICY,
    DEFAULT_SJF_AGING_STEPS,
    Request,
    RequestRejected,
    RequestState,
    Scheduler,
    StepPlan,
    normalize_scheduler_policy,
    normalize_shed_policy,
    validate_scheduler_policy,
    validate_shed_policy,
)
from automodel_tpu.serving.speculative import (
    DEFAULT_SPEC_K,
    DEFAULT_SPECULATIVE,
    build_proposer,
    normalize_speculative,
    validate_speculative,
)
from automodel_tpu.training.timers import Timers
from automodel_tpu.utils.fault_injection import InjectedFault, fault_point

logger = logging.getLogger(__name__)

# drain(grace_s=...) default sentinel: "use serving.drain_grace_s" — an
# explicit None means "unbounded", so None cannot double as the default
_GRACE_FROM_CONFIG = object()


@dataclasses.dataclass
class ServingConfig:
    """The ``serving:`` YAML section (every enum re-validated here so
    programmatic construction fails exactly like a typo'd YAML —
    the L002 contract)."""

    kv_block_size: int = 16
    kv_cache_dtype: Optional[str] = None     # None/"auto" -> compute dtype
    max_num_seqs: int = 8
    max_model_len: int = 1024
    # None -> full residency + null; for a cache of several block groups a
    # mapping {group name: blocks} (a group left out takes full residency)
    num_kv_blocks: Optional[Any] = None
    prefill_chunk: int = 32
    scheduler_policy: Optional[str] = None   # None -> fcfs
    # -- robustness layer (docs/guides/serving.md "Production hardening") --
    max_waiting: Optional[int] = None        # None -> unbounded queue
    shed_policy: Optional[str] = None        # None -> reject_newest
    # -- prefix caching (docs/guides/serving.md "Prefix caching") ----------
    prefix_caching: Optional[str] = None     # None -> off (on/off, bools ok)
    prefix_lru_blocks: Optional[int] = None  # None -> unbounded warm LRU
    max_preemptions: Optional[int] = None    # None -> never pin
    sjf_aging_steps: Optional[int] = None    # None -> default (32)
    watchdog_s: Optional[float] = None       # None -> watchdog disabled
    drain_grace_s: Optional[float] = None    # None -> unbounded drain
    # -- speculative decoding (docs/guides/serving.md "Speculative") -------
    speculative: Optional[str] = None        # None -> off (off/ngram, bools ok)
    spec_k: Optional[int] = None             # None -> default (4) draft tokens
    # -- elastic fleet (docs/guides/serving.md "Elastic fleet") ------------
    replicas: Optional[int] = None           # None -> 1 (single engine)
    router_policy: Optional[str] = None      # None -> round_robin
    fleet_probation_polls: Optional[int] = None   # None -> default (3)
    # -- multi-tenant adapters (docs/guides/serving.md "Multi-tenant") -----
    max_adapters: Optional[int] = None       # None -> multi-LoRA off
    adapter_rank: Optional[int] = None       # None -> default (8)
    tenant_quota: Optional[int] = None       # None -> no per-tenant cap

    def __post_init__(self):
        for field in ("kv_block_size", "max_num_seqs", "max_model_len",
                      "prefill_chunk"):
            v = getattr(self, field)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"serving.{field} must be a positive int, got {v!r}")
        if hasattr(self.num_kv_blocks, "to_dict"):          # ConfigNode
            self.num_kv_blocks = self.num_kv_blocks.to_dict()
        counts = (list(self.num_kv_blocks.values())
                  if isinstance(self.num_kv_blocks, dict)
                  else [] if self.num_kv_blocks is None
                  else [self.num_kv_blocks])
        if any(isinstance(n, bool) or not isinstance(n, int) or n < 2
               for n in counts):
            raise ValueError(
                "serving.num_kv_blocks must be >= 2 (1 null + 1 usable), or "
                "a mapping of block-group name to such a count, got "
                f"{self.num_kv_blocks!r}")
        from automodel_tpu.config.loader import normalize_null_spelling

        for field in ("max_waiting", "max_preemptions", "sjf_aging_steps",
                      "replicas", "fleet_probation_polls",
                      "prefix_lru_blocks", "spec_k", "max_adapters",
                      "adapter_rank", "tenant_quota"):
            v = normalize_null_spelling(getattr(self, field))
            setattr(self, field, v)
            if v is None:
                continue
            if isinstance(v, bool) or not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"serving.{field} must be an integer >= 1 (or null "
                    f"for the default), got {v!r}")
        for field in ("watchdog_s", "drain_grace_s"):
            v = normalize_null_spelling(getattr(self, field))
            setattr(self, field, v)
            if v is None:
                continue
            if isinstance(v, bool) or not isinstance(v, (int, float)) \
                    or v <= 0:
                raise ValueError(
                    f"serving.{field} must be a positive number (or null "
                    f"to disable), got {v!r}")
        self.kv_cache_dtype = validate_kv_cache_dtype(
            normalize_kv_cache_dtype(self.kv_cache_dtype))
        self.prefix_caching = validate_prefix_caching(
            normalize_prefix_caching(self.prefix_caching))
        self.speculative = validate_speculative(
            normalize_speculative(self.speculative))
        self.scheduler_policy = validate_scheduler_policy(
            normalize_scheduler_policy(self.scheduler_policy))
        self.shed_policy = validate_shed_policy(
            normalize_shed_policy(self.shed_policy))
        # lazy: fleet.py imports this module, so its enum validators are
        # pulled in here at validation time only (no import cycle)
        from automodel_tpu.serving.fleet import (
            normalize_router_policy,
            validate_router_policy,
        )

        self.router_policy = validate_router_policy(
            normalize_router_policy(self.router_policy))

    @property
    def blocks_per_seq(self) -> int:
        return blocks_needed(self.max_model_len, self.kv_block_size)

    def resolved_num_blocks(self, group: Optional[CacheGroup] = None) -> int:
        """The pool's blocks (of ``group``, in a cache of several): what
        the config names, else full residency — every row at
        ``max_model_len``, or at what the group's window lets it hold."""
        n = self.num_kv_blocks
        if isinstance(n, dict):
            if group is None or group.name is None:
                raise ValueError(
                    f"serving.num_kv_blocks {n!r} names block groups, but "
                    "this model's cache has one unnamed group: give one count")
            n = n.get(group.name)
        if n is not None:
            return n
        per_seq = self.blocks_per_seq
        if group is not None and group.window is not None:
            per_seq = min(per_seq, window_span_blocks(
                group.window, self.prefill_chunk, self.kv_block_size))
        return self.max_num_seqs * per_seq + 1


def build_serving_config(cfg: Any) -> ServingConfig:
    """``ServingConfig`` from a loaded YAML's ``serving:`` node (or a plain
    dict / None for the defaults)."""
    if cfg is None:
        return ServingConfig()
    if hasattr(cfg, "get") and hasattr(cfg, "to_dict"):   # ConfigNode
        node = cfg.get("serving", cfg)
        data = node.to_dict() if hasattr(node, "to_dict") else dict(node)
    else:
        data = dict(cfg)
    known = {f.name for f in dataclasses.fields(ServingConfig)}
    unknown = sorted(set(data) - known)
    if unknown:
        raise ValueError(
            f"unknown serving config key(s) {unknown}; known: "
            f"{sorted(known)}")
    return ServingConfig(**data)


class LastColumn(NamedTuple):
    """What each row's last valid column produced in one step."""

    logits: Any     # [B, V] float32
    token: Any      # [B] int32: its argmax, the next step's ``prev_tok``


def _paged_step(model, block_size: int, quantized: bool, cow_enabled: bool,
                adapters_enabled: bool, state_planes: bool,
                params, pools,
                input_ids, positions, slot_mapping, block_tables,
                context_lens, last_col, cow_src, cow_dst,
                prev_tok, take_prev,
                adapter_ids=None, adapter_slabs=None):
    """ONE traced program per step width: run any pending copy-on-write
    block forks, write this step's tokens into the paged cache, attend,
    and greedy-pick EVERY column's next token.  Returns ``(greedy [B, W],
    last: LastColumn, pools)`` and, from a model with routed expert
    layers, ``expert_tokens [n_moe_layers, held]``.  ``last`` is what each
    row's last valid column produced: its ``logits [B, V]`` (host-side
    sampling reads them) and its greedy ``token [B]`` — the program feeds
    itself with it: the NEXT step hands it back as ``prev_tok [B]`` (still
    a device future, never fetched for this) with ``take_prev [B]`` bool,
    and a row whose flag is set reads column 0 of its ``input_ids`` from
    there.  Both are ``[B]`` at either width, so the two programs chain in
    any order.  The pools are donated
    and come back as the layer scan's carry (``models/layer_scan.py::scan_layers``), so the
    cache updates in place.  Plain decode reads its one token at its last
    valid column of ``greedy``; the speculative verify reads the argmax at
    each draft position from the same array — the per-column argmax IS the
    verify, so acceptance costs no extra device work and no extra fetch.

    ``cow_src``/``cow_dst`` are fixed ``[B]`` block-id pairs: rows with a
    prefix-cache fork copy their shared last block into a private one
    BEFORE this step's writes land; rows without carry ``(0, 0)`` — the
    null page copied onto itself, a content no-op — so hit/miss/fork
    steps share this one compiled program (no new shapes).
    ``cow_enabled`` is a TRACE-TIME constant: with the prefix cache off
    no fork can ever be scheduled, so the step compiles without the
    per-step block copy (the cache-off path pays nothing; the args stay
    in the signature so both modes keep one census).

    ``adapters_enabled`` is likewise a trace-time constant: a multi-tenant
    engine appends ``adapter_ids [B]`` int32 (0 = base) and the device
    adapter slabs to every step, and the forward routes each row's rank-r
    delta through the grouped GEMM (``ops/lora_gmm.py``).  A base-only
    engine passes NEITHER — its traced program is the pre-multi-tenant
    one, byte-identical.  Swapping a slot only changes slab CONTENTS, so
    hot-swap never adds a program shape.

    ``state_planes`` (trace-time too): the model keeps per-SEQUENCE state
    (``kv_cache.StatePlaneView``: power retention).  ``pools`` are then the
    state planes, a row per step-buffer row; ``block_tables`` is ``[B, 1]``
    and only says which rows hold a request, ``slot_mapping`` and
    ``context_lens`` address nothing."""
    input_ids = input_ids.at[:, 0].set(
        jnp.where(take_prev, prev_tok, input_ids[:, 0]))
    if cow_enabled:
        with jax.named_scope("cow_copy"):
            pools = cow_copy_blocks(pools, cow_src, cow_dst)
    if state_planes:
        view = StatePlaneView(pools, block_tables, positions)
    else:
        view = PagedKVView(
            pools, block_tables, slot_mapping, context_lens, positions,
            block_size=block_size, quantized=quantized)
    if adapters_enabled:
        out = model(params, input_ids, position_ids=positions,
                    kv_cache=view, adapters=adapter_slabs,
                    adapter_ids=adapter_ids)
    else:
        out = model(params, input_ids, position_ids=positions,
                    kv_cache=view)
    with jax.named_scope("sample"):
        logits = out["logits"].astype(jnp.float32)            # [B, W, V]
        last = jnp.take_along_axis(
            logits, last_col[:, None, None], axis=1)[:, 0]    # [B, V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, W]
        last = LastColumn(logits=last, token=jnp.take_along_axis(
            greedy, last_col[:, None], axis=1)[:, 0])         # [B]
    if "expert_tokens" in out:
        # [n_moe_layers, held] int32: tokens each held expert got this step
        return greedy, last, out["kv_cache"], out["expert_tokens"]
    return greedy, last, out["kv_cache"]


@dataclasses.dataclass
class _Flight:
    """One dispatched step whose tokens are not on the host yet: the plan,
    the step program's outputs (device futures) and what the dispatch
    already knew of it."""

    plan: StepPlan
    step: int                  # ``steps_run`` at dispatch (the span's stat)
    greedy: Any
    logits: Any                # ``last.logits``, kept for ``do_sample`` only
    routed: list


class DecodeEngine:
    """Continuous-batching paged-KV decode over one model + params."""

    def __init__(self, model, params, config: Optional[ServingConfig] = None,
                 generation: Optional[GenerationConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 timers=None, param_sharding=None, sample_seed: int = 0):
        self.model = model
        # Decode-plan placement (the weight-handoff contract, see
        # :meth:`update_params`): a pytree of shardings pins where the
        # engine's OWN COPIES of the params live; None adopts arrays as
        # handed (no training loop in play — tests, tools/serve.py).
        self.param_sharding = param_sharding
        self._sync_copy = None
        if param_sharding is not None:
            params = self._copy_into_decode_plan(params)
        self.params = params
        self._param_structs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        self.config = config or ServingConfig()
        self.generation = generation or GenerationConfig()
        self.clock = clock
        # host clocks per phase of step() (``tools/serve.py``'s timers_ms)
        # and, under a profiler session, the same phases as trace spans
        self.timers = timers if timers is not None else Timers()
        mcfg = model.config
        dtype = self.config.kv_cache_dtype or DEFAULT_KV_CACHE_DTYPE
        self.quantized = dtype == "int8"
        cache_dtype = jnp.int8 if self.quantized else model.compute_dtype
        # the planes of the cache are the MODEL's to say: per-head k and v,
        # MLA's one latent plane (refused with int8, loudly, there), or
        # per-sequence state (power retention), which takes no block pool
        planes = model.paged_cache_planes()
        # the cache's block groups as the model declares them: one unnamed
        # group for a flat declaration, whose pools, tables and slots the
        # engine then holds bare and not under a name
        self.cache_groups: List[CacheGroup] = cache_groups(
            planes, mcfg.num_hidden_layers)
        self.grouped = self.cache_groups[0].name is not None
        self.state_planes = (not self.grouped) and sequence_planes(planes)
        if self.grouped:
            self._refuse_for_block_groups()
        if self.state_planes:
            self._refuse_for_state_planes(planes)
            # a row per step-buffer row; the allocator's blocks are
            # bookkeeping that never binds (its minimum: null + 1)
            num_blocks, self.max_blocks_per_seq = [2], 1
            self._new_pools = functools.partial(
                init_state_planes, num_layers=mcfg.num_hidden_layers,
                rows=self.config.max_num_seqs, planes=planes)
        else:
            num_blocks = [self.config.resolved_num_blocks(g)
                          for g in self.cache_groups]
            self.max_blocks_per_seq = self.config.blocks_per_seq
            makers = [functools.partial(
                init_paged_pools, num_layers=g.layers, planes=g.planes,
                num_blocks=n, block_size=self.config.kv_block_size,
                cache_dtype=cache_dtype, quantized=self.quantized)
                for g, n in zip(self.cache_groups, num_blocks)]
            self._new_pools = (
                (lambda: {g.name: make() for g, make in
                          zip(self.cache_groups, makers)})
                if self.grouped else makers[0])
        self.pools = self._new_pools()
        self.block_groups = [
            BlockGroup(g.name, BlockAllocator(n), g.window)
            for g, n in zip(self.cache_groups, num_blocks)]
        self.allocator = self.block_groups[0].allocator
        self.prefix_index: Optional[PrefixIndex] = None
        if (self.config.prefix_caching
                or DEFAULT_PREFIX_CACHING) == "on":
            self.prefix_index = PrefixIndex(
                self.allocator, block_size=self.config.kv_block_size,
                lru_blocks=self.config.prefix_lru_blocks)
        # -- multi-tenant adapter slots (serving/adapters.py) --------------
        self.adapter_slots = None
        if self.config.max_adapters:
            from automodel_tpu.serving.adapters import (
                DEFAULT_ADAPTER_RANK,
                AdapterSlots,
            )

            self.adapter_slots = AdapterSlots(
                model, max_adapters=self.config.max_adapters,
                rank=self.config.adapter_rank or DEFAULT_ADAPTER_RANK)
        # -- speculative decoding (serving/speculative.py) -----------------
        spec_mode = self.config.speculative or DEFAULT_SPECULATIVE
        self.spec_k = self.config.spec_k or DEFAULT_SPEC_K
        if spec_mode != "off" and self.generation.do_sample:
            # acceptance verifies the GREEDY chain; a host-sampled token
            # has no draft to verify against, so speculation is a no-op
            # under do_sample — disable it loudly rather than silently
            # paying the wide verify step for nothing
            logger.warning(
                "serving.speculative=%s disabled: generation.do_sample is "
                "set and speculative verification is greedy-only", spec_mode)
            spec_mode = "off"
        self.spec_mode = spec_mode
        # Steps dispatched ahead of the fetch: 1 where the next step can be
        # planned without the newest token (greedy: the device feeds it),
        # 0 where the host needs it first (it samples it, or the n-gram
        # proposer reads it and the verify accepts a variable number)
        self.lookahead = (0 if self.generation.do_sample
                          or spec_mode != "off" else 1)
        self._in_flight: Deque[_Flight] = collections.deque()
        self._done_between: List[Request] = []
        # the newest step's ``last.token``, a device future: what a fed row
        # of the next step reads (zeros before the first step: no row is fed)
        self._prev_tok = jnp.zeros((self.config.max_num_seqs,), jnp.int32)
        self.scheduler = Scheduler(
            self.allocator, max_num_seqs=self.config.max_num_seqs,
            prefill_chunk=self.config.prefill_chunk,
            block_size=(None if self.state_planes
                        else self.config.kv_block_size),
            max_model_len=self.config.max_model_len,
            policy=self.config.scheduler_policy
            or DEFAULT_SCHEDULER_POLICY,
            max_waiting=self.config.max_waiting,
            shed_policy=self.config.shed_policy or DEFAULT_SHED_POLICY,
            max_preemptions=self.config.max_preemptions,
            sjf_aging_steps=self.config.sjf_aging_steps
            or DEFAULT_SJF_AGING_STEPS,
            prefix_index=self.prefix_index,
            spec_proposer=build_proposer(spec_mode),
            spec_k=self.spec_k,
            tenant_quota=self.config.tenant_quota,
            multi_tenant=self.adapter_slots is not None,
            clock=clock, event=self.timers.event, groups=self.block_groups)
        self.requests: Dict[int, Request] = {}
        self.rejections: List[RequestRejected] = []
        self._rids = itertools.count()
        self._steps: Dict[int, Any] = {}       # width -> jitted step
        self._sample_key = jax.random.key(sample_seed)
        self.weight_syncs = 0
        self.steps_run = 0
        # step fill over the engine's life: active rows, real positions
        # written, and positions computed (max_num_seqs x width) per step
        self.rows_sum = 0
        self.positions_sum = 0
        self.slots_sum = 0
        self.decode_steps = 0
        self.mixed_steps = 0
        self.aborts = 0
        self.tokens_generated = 0
        # steps dispatched while the one before was still unfetched
        self.ahead_steps = 0
        # routed-expert counters, summed over the steps of a model whose
        # step returns ``expert_tokens`` (None for a model without routed
        # layers in its serving step)
        self.expert_assignments_sum = None
        self.experts_hit_sum = None
        # state-plane counters, summed over the steps of a model with
        # per-sequence state: rows whose state a step read and wrote, and
        # rows it started from zero (None for a per-token cache)
        self.state_rows_sum = 0 if self.state_planes else None
        self.state_resets_sum = 0 if self.state_planes else None
        self.watchdog_recoveries = 0
        # clock stamp of the FIRST of the current run of no-progress steps
        # (None while the engine is productive or idle)
        self._no_progress_since: Optional[float] = None

    def _refuse_for_block_groups(self) -> None:
        """What is not wired for a cache of several block groups is refused
        at build, each with what is missing."""
        cfg = self.config
        names = [g.name for g in self.cache_groups]
        if (cfg.prefix_caching or DEFAULT_PREFIX_CACHING) != "off":
            raise NotImplementedError(
                f"serving.prefix_caching: on is not wired for the block "
                f"groups {names}: the index keys ONE table's blocks, and a "
                "block that a window group released while its request ran "
                "is gone for whoever shares the prefix later (a hit would "
                "need the window's blocks recomputed); serve this family "
                "with prefix_caching: off")
        if (cfg.speculative or DEFAULT_SPECULATIVE) != "off":
            raise NotImplementedError(
                f"serving.speculative: {cfg.speculative} is not wired for "
                f"the block groups {names}: a window group releases blocks "
                "by the row's first query position, and no test holds that "
                "against a verify step that is rolled back; serve this "
                "family with speculative: off")
        if self.quantized:
            raise NotImplementedError(
                f"serving.kv_cache_dtype: int8 is not wired for the block "
                f"groups {names}: the scale planes would need a pool a group "
                "too and no test holds them; serve it in the compute dtype")

    @property
    def all_free(self) -> bool:
        """The leak oracle: every block of every group is back."""
        return self.scheduler.all_free

    def _refuse_for_state_planes(self, planes) -> None:
        """A per-sequence state plane has no blocks to share, no way back
        from a rejected draft and no scale: the three options that assume a
        per-token cache are refused at build, each with what is missing."""
        cfg, names = self.config, sorted(planes)
        if (cfg.prefix_caching or DEFAULT_PREFIX_CACHING) != "off":
            raise NotImplementedError(
                f"serving.prefix_caching: on is not wired for the "
                f"per-sequence state planes {names}: a shared prefix would "
                "need a SNAPSHOT of the state at block boundaries to start "
                "from (the state is one running sum, not blocks that can be "
                "shared); serve this family with prefix_caching: off")
        if (cfg.speculative or DEFAULT_SPECULATIVE) != "off":
            raise NotImplementedError(
                f"serving.speculative: {cfg.speculative} is not wired for "
                f"the per-sequence state planes {names}: a verify step "
                "advances the state past every draft token and nothing "
                "rolls it BACK to the last accepted one; serve this family "
                "with speculative: off")
        if self.quantized:
            raise NotImplementedError(
                f"serving.kv_cache_dtype: int8 is not wired for the "
                f"per-sequence state planes {names}: the float32 state has "
                "no scale plane (and a rounding error in it is carried "
                "into every later token); serve it as it is")

    # -- compiled step per width (the "compiles once per bucket" seam) -----
    def step_fn(self, width: int):
        fn = self._steps.get(width)
        if fn is None:
            step = functools.partial(_paged_step, self.model,
                                     self.config.kv_block_size,
                                     self.quantized,
                                     self.prefix_index is not None,
                                     self.adapter_slots is not None,
                                     self.state_planes)
            # a jitted partial is "jit__unknown" in a trace: name the program
            # by its width, which is already the key of ``_steps``
            step.__name__ = f"paged_step_w{width}"
            fn = jax.jit(step, donate_argnums=(1,))
            self._steps[width] = fn
        return fn

    # -- weight handoff (post-training rollouts on one mesh) ---------------
    def _copy_into_decode_plan(self, params):
        """A genuine device-side COPY of ``params`` at the decode plan's
        shardings.  A plain ``device_put`` into an already-matching
        sharding is a no-op ALIAS — and the post-training optimizer steps
        DONATE the live tree, so an aliased engine would hold deleted
        buffers the moment training stepped.  The jitted copy (compiled
        once) keeps the transfer on-fabric — no host round-trip — while
        giving the engine buffers it owns outright."""
        if self._sync_copy is None:
            self._sync_copy = jax.jit(
                lambda t: jax.tree.map(jnp.copy, t),
                out_shardings=self.param_sharding)
        return self._sync_copy(params)

    def update_params(self, params=None, *, adapter_slot: Optional[int] = None,
                      adapters=None, adapter_name: Optional[str] = None,
                      adapter_scale: float = 1.0) -> None:
        """Adopt LIVE training params — the explicit weight-handoff API
        the post-training rollout layer drives (``post_training/
        rollout.py``; ``docs/guides/post_training.md`` "The weight-handoff
        contract").

        **Per-slot adapter hot-swap arm** (multi-tenant serving): pass
        ``adapter_slot``/``adapters`` (and nothing, or additionally the
        base ``params``) to load or swap ONE tenant's LoRA tree into a
        slot with zero downtime — digest-verified through the replication
        shard protocol, committed atomically (``serving/adapters.py``),
        and compile-stable: slab shapes never change, so no decode step
        recompiles and rows on other slots are never perturbed.

        * **Device-to-device**: when the engine was built with a
          ``param_sharding`` pytree (its decode plan), the incoming tree —
          typically sharded per the TRAIN plan — is COPIED into it by a
          jitted device-side copy: an async on-fabric transfer, never a
          host round-trip, and the engine owns the result (the training
          loop donates its params every optimizer step, so the engine can
          never alias them).  With no decode plan the arrays are adopted
          as handed — correct only when nothing donates them.
        * **Compile-stable**: the pytree structure and every leaf's
          shape/dtype must match what the engine was built with —
          anything else would silently invalidate the compiled step
          entries, so it raises instead.
        * The handoff itself never touches request state: in-flight
          sequences keep decoding under the NEW weights (recompute-style
          preemption semantics already tolerate that; rollout drivers
          sync only between generations).
        """
        if adapter_slot is not None:
            self.load_adapter(adapter_slot, adapters, name=adapter_name,
                              scale=adapter_scale)
            if params is None:
                return
        if params is None:
            raise ValueError(
                "update_params: pass base params, an adapter_slot swap, "
                "or both")
        structs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        try:
            match = jax.tree.all(jax.tree.map(
                lambda a, b: a == b, structs, self._param_structs))
        except ValueError as e:
            raise ValueError(
                "update_params: incoming pytree structure does not match "
                f"the engine's params ({e})") from None
        if not match:
            raise ValueError(
                "update_params: incoming leaf shapes/dtypes do not match "
                "the engine's params — the compiled decode steps would be "
                "invalid; build a new engine for a different model")
        if self.param_sharding is not None:
            params = self._copy_into_decode_plan(params)
        # the step in flight ran under the old weights; its tokens are
        # delivered before the first step under the new ones is planned
        self._flush()
        self.params = params
        self.weight_syncs += 1

    # -- multi-tenant adapter slots (serving/adapters.py) -------------------
    def _require_adapters(self):
        if self.adapter_slots is None:
            raise ValueError(
                "this engine serves the base model only — set "
                "serving.max_adapters to enable multi-tenant adapters")
        return self.adapter_slots

    def load_adapter(self, slot: int, adapters, *,
                     name: Optional[str] = None,
                     scale: float = 1.0) -> Dict[str, Any]:
        """Load or hot-swap one tenant's LoRA tree into ``slot`` (1-based;
        0 is the base model).  Raises ``AdapterLoadError`` on any
        verification failure with the slot still serving its previous
        adapter.  In-flight requests never notice: the next step simply
        reads the new slab contents, same compiled program."""
        return self._require_adapters().load(slot, adapters, name=name,
                                             scale=scale)

    def remove_adapter(self, slot: int) -> None:
        """Unload ``slot``; later submits naming it are rejected."""
        self._require_adapters().remove(slot)

    # -- request intake ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = "default",
               deadline_s: Optional[float] = None,
               max_queue_s: Optional[float] = None,
               adapter_id: int = 0) -> int:
        """Queue one request; returns its id.  ``eos_token_id`` defaults to
        the engine's :class:`GenerationConfig` (pass None to disable).

        ``deadline_s`` is an end-to-end wall budget from this call;
        ``max_queue_s`` bounds WAITING time (both None -> unbounded).  A
        request admission control drops is NOT an exception: its state is
        ``REJECTED`` and the typed :class:`RequestRejected` outcome is
        appended to ``self.rejections`` — check ``engine.requests[rid]``
        or the return of :meth:`outcome_counts`."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("cannot serve an empty prompt")
        if eos_token_id == "default":
            eos_token_id = self.generation.eos_token_id
        if adapter_id != 0:
            if not self._require_adapters().is_loaded(adapter_id):
                raise ValueError(
                    f"adapter_id={adapter_id} names an empty slot — load "
                    "it first (engine.load_adapter)")
        rid = next(self._rids)
        req = Request(
            rid=rid, prompt=prompt,
            max_new_tokens=(self.generation.max_new_tokens
                            if max_new_tokens is None else max_new_tokens),
            eos_token_id=eos_token_id,
            deadline_s=deadline_s, max_queue_s=max_queue_s,
            adapter_id=int(adapter_id))
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.submit_request(req)
        return rid

    def submit_request(self, req) -> list:
        """Admit an externally-built :class:`Request` (the fleet router
        owns the rid space and builds requests itself — see
        ``serving/fleet.py``).  Same admission path as :meth:`submit`:
        the scheduler may shed it (typed, recorded in ``rejections``),
        never raise.  Returns the :class:`RequestRejected` outcomes this
        admission produced (possibly shedding OTHER queued rows)."""
        rejected = self.scheduler.add(req)   # ValueError = caller bug only
        self.requests[req.rid] = req
        self.rejections.extend(rejected)
        return rejected

    def adopt_for_replay(self, req) -> None:
        """Adopt an admitted request harvested from a LOST fleet replica:
        parks it pinned/WAITING with ``num_computed`` reset, so the
        recompute replay re-prefills prompt + generated-so-far here and
        greedy output stays token-identical across the engine move."""
        self.scheduler.adopt_replay(req)
        self.requests[req.rid] = req

    def harvest_for_replay(self) -> list:
        """Strip every unfinished request off this engine for replay
        elsewhere (this engine's slice was declared lost).  Each row's
        slot/blocks are released — the allocator ends ``all_free`` once
        finished rows are accounted — and ``num_computed`` resets so the
        adopting engine replays from scratch.  Rows keep their terminal
        flags (``was_admitted``, pinned, tokens-so-far) and leave
        ``self.requests`` entirely: the fleet decides where they land."""
        harvested = []
        # a lost slice's step never comes back: what is in flight is dropped
        self._in_flight.clear()
        for req in list(self.scheduler.active) + list(self.scheduler.waiting):
            if req.finished:
                continue
            self.scheduler._release(req)
            req.num_computed = 0
            req.in_flight = 0
            req.state = RequestState.WAITING
            harvested.append(req)
            self.requests.pop(req.rid, None)
        return harvested

    def abort(self, rid: int) -> None:
        """Cancel a request anywhere in its lifecycle; its block table is
        freed immediately (the ``serve_request_abort`` contract)."""
        req = self.requests.get(rid)
        if req is None or req.finished:
            return
        # what the device already computed for it is delivered first: the
        # request ends with the tokens of every step dispatched before the
        # cancel, as it does at depth 0 (and may have FINISHED on them)
        self._flush()
        if req.finished:
            return
        self.scheduler.abort(req)
        self.aborts += 1

    # -- the engine loop ---------------------------------------------------
    def _assemble(self, plan: StepPlan):
        cfg = self.config
        B, W, MB = cfg.max_num_seqs, plan.step_width, self.max_blocks_per_seq
        bs = cfg.kv_block_size
        ids = np.zeros((B, W), np.int32)
        pos = np.zeros((B, W), np.int32)
        # pad/idle tokens write into the null page (block 0), slot col % bs
        # (one table and one slot mapping a block group, by name; a cache
        # of one group holds them bare)
        names = [g.name for g in self.cache_groups]
        slots = {n: np.tile(np.arange(W, dtype=np.int32) % bs, (B, 1))
                 for n in names}
        tables = {n: np.zeros((B, MB), np.int32) for n in names}
        ctx = np.ones((B,), np.int32)       # idle rows: 1 (null-page key 0)
        last = np.zeros((B,), np.int32)
        # COW fork pairs: (0, 0) = null page onto itself = content no-op
        cow_src = np.zeros((B,), np.int32)
        cow_dst = np.zeros((B,), np.int32)
        # rows whose one token is the previous step's sample, on the device
        take_prev = np.zeros((B,), np.bool_)
        for work in plan.active:
            b = work.req.slot
            take_prev[b] = work.fed
            # draft tokens are ordinary written tokens to the device step:
            # (adapter routing is assembled separately — see
            # ``_assemble_adapter_ids`` — so this tuple, and every
            # base-only caller that splats it into the step, is unchanged)
            # same ids/pos/slot treatment, context covers them, and the
            # per-column argmax at their positions is the verify readout.
            # Only the HOST distinguishes pending from draft (acceptance
            # advances num_computed past accepted drafts only).
            toks = list(work.tokens) + list(work.draft)
            t = len(toks)
            start = work.start_pos
            ids[b, :t] = toks
            pos[b, :t] = np.arange(start, start + t)
            pos[b, t:] = start + t - 1      # pads clamp to the last valid
            if self.state_planes:
                tables[None][b, 0] = b + 1  # the row holds a request
            else:
                for i, name in enumerate(names):
                    blocks = (work.req.blocks if i == 0
                              else work.req.group_blocks[name])
                    tables[name][b, :len(blocks)] = blocks
                    slots[name][b, :t] = [slot_for(blocks, p, bs)
                                          for p in range(start, start + t)]
            ctx[b] = start + t
            last[b] = t - 1
            if work.cow is not None:
                cow_src[b], cow_dst[b] = work.cow
        if not self.grouped:
            slots, tables = slots[None], tables[None]
        return (ids, pos, slots, tables, ctx, last, cow_src, cow_dst,
                self._prev_tok, take_prev)

    def _assemble_adapter_ids(self, plan: StepPlan) -> np.ndarray:
        """``[B]`` int32 slot routing for a multi-tenant step — idle rows
        carry 0 (the base/zero adapter, a content no-op like the null
        page), so adapter churn is data, never a shape."""
        aids = np.zeros((self.config.max_num_seqs,), np.int32)
        for work in plan.active:
            aids[work.req.slot] = work.req.adapter_id
        return aids

    def _sample(self, step: int, row: int, last_logits) -> int:
        # host-side sampling path (do_sample only — greedy rows read the
        # in-step argmax): one extra [V] fetch per sampled row
        key = jax.random.fold_in(self._sample_key, step * 4096 + row)
        return int(np.asarray(sample_logits(
            jnp.asarray(last_logits[row])[None], self.generation, key))[0])

    # -- the watchdog (host-side, never a trace event) ---------------------
    def _watchdog_due(self, now: float) -> bool:
        """True when CONSECUTIVE no-progress steps have spanned more than
        ``watchdog_s``.  The marker only starts at a step() that produced
        nothing while work was pending — a healthy engine whose caller
        merely pauses between steps never trips it (every productive step
        clears the marker)."""
        w = self.config.watchdog_s
        return (w is not None and self._no_progress_since is not None
                and self.scheduler.has_work()
                and now - self._no_progress_since > w)

    def _watchdog_recover(self, reason: str) -> None:
        """Abort the in-flight batch and replay every admitted request.

        Donated pool buffers cannot be trusted after a failed/abandoned
        step, so the pools are REBUILT (same shapes/dtypes — the compiled
        step entries stay valid); every active request's block table is
        reclaimed and the request parks back to WAITING, pinned, with
        ``num_computed`` reset — the recompute replay regenerates prompt +
        tokens-so-far, so greedy output stays token-identical."""
        logger.warning(
            "serving watchdog: %s — aborting the in-flight batch and "
            "replaying %d admitted request(s) from their last computed "
            "token", reason, len(self.scheduler.active))
        with self.timers.record("serve_recovery"):
            for req in list(self.scheduler.active):
                self.scheduler.requeue_for_replay(req)
            # every table is back on the free list; zero pools replace the
            # untrusted donated buffers (cheap relative to the stall
            # absorbed); a step still in flight is abandoned with them (the
            # replay regenerates its tokens)
            self._in_flight.clear()
            self.pools = self._new_pools()
            if self.prefix_index is not None:
                # rebuilt pools zero the cached contents — a stale prefix
                # hit would read garbage, so the index forgets everything
                self.prefix_index.flush()
        self.watchdog_recoveries += 1
        self._no_progress_since = None

    def step(self) -> List[Request]:
        """One scheduler + device step; returns the requests whose LAST
        token was delivered in this call.  No-op (empty list) when idle.

        The order inside one call is: plan step N+1 -> assemble -> dispatch
        N+1 -> fetch step N -> deliver N (``self.lookahead`` is 1: greedy
        engines), so the device runs N+1 while the host fetches N, applies
        it and comes back to plan N+2.  A caller that reads
        ``req.out_tokens`` between calls therefore sees step N's token
        after the call that DISPATCHED N+1: one call later than the call
        that dispatched N.  A request leaves ``scheduler.active`` only when
        its last token is delivered, so ``scheduler.has_work()`` stays true
        while a step is in flight and a loop on it ends with none.  With
        ``lookahead`` 0 (``do_sample``, speculation) the fetch is taken at
        once and a call delivers the step it dispatched.

        Never raises for load or stall reasons: exhaustion preempts,
        deadlines expire, a full queue sheds, and a detected wedge recovers
        — the engine loop under fire keeps stepping.  A REAL runtime
        failure out of the device step (not the drilled fault) still
        propagates — but only after the same recovery ran, so the engine's
        state (tables reclaimed, pools rebuilt) stays consistent and a
        caller that catches it may keep stepping."""
        with self.timers.record("serve_step"):
            return self._step()

    def _step(self) -> List[Request]:
        # The drilled mid-decode cancel: an armed ``serve_request_abort``
        # models a client disconnect — the oldest active request is aborted
        # and its block table freed before the step runs.
        try:
            fault_point("serve_request_abort")
        except InjectedFault:
            active = self.scheduler.active
            if active:
                self.abort(min(active, key=lambda r: r.arrival).rid)
        timers = self.timers
        t0 = self.clock()
        with timers.record("serve_schedule"):
            if self._watchdog_due(t0):
                self._watchdog_recover(
                    f"no slot progress across consecutive steps spanning > "
                    f"serving.watchdog_s={self.config.watchdog_s}")
            plan = self.scheduler.schedule(now=t0)
        # requests that finished in a flush between two calls (an abort, a
        # weight handoff) are this call's to return
        done, self._done_between = self._done_between, []
        try:
            if plan is not None:
                self._dispatch(plan)
            # fetch what is due: every step beyond the look-ahead — and
            # with nothing new to run behind it, the one in flight too
            keep = self.lookahead if plan is not None else 0
            delivered = len(self._in_flight) > keep
            while len(self._in_flight) > keep:
                done.extend(self._deliver(self._in_flight.popleft()))
            if delivered:
                self.scheduler.note_step_time(self.clock() - t0)
        except InjectedFault:
            self._watchdog_recover("injected stall (serve_watchdog_stall)")
            return done
        except Exception as e:
            # a genuine runtime failure mid-dispatch: the donated pools
            # cannot be trusted — recover FIRST (tables reclaimed, pools
            # rebuilt, requests replay), then let the error surface so a
            # real bug stays loud
            self._watchdog_recover(f"device step failed: {e!r}")
            raise
        if plan is not None or delivered:
            self._no_progress_since = None           # this step progressed
        elif self.scheduler.has_work():
            # work pending but nothing schedulable: the no-progress
            # window starts (or continues) here
            if self._no_progress_since is None:
                self._no_progress_since = t0
        else:
            self._no_progress_since = None           # idle is not a wedge
        return done

    def _dispatch(self, plan: StepPlan) -> None:
        """Assemble ``plan``'s buffers, call the step program (the call
        returns at once: its outputs are futures) and run the half of the
        scheduler's step that needs no token."""
        timers = self.timers
        with timers.record("serve_assemble"):
            args = self._assemble(plan)
            # multi-tenant engines append the row->slot routing + the live
            # slabs; base-only engines call with exactly the twelve args
            # (their traced program has no adapter input)
            extra = (() if self.adapter_slots is None
                     else (self._assemble_adapter_ids(plan),
                           self.adapter_slots.slabs))
        # How full this step is, known once the plan is: it rides on the
        # dispatch span and sums into stats().  ``positions`` are pending
        # tokens written (drafts are a guess, not counted); ``slots`` the
        # positions the dense [max_num_seqs, width] program computes;
        # ``ahead`` whether the step before is still unfetched, ``fed_rows``
        # the rows whose token comes from it on the device.
        active, width = plan.active, plan.step_width
        positions = sum(len(w.tokens) for w in active)
        prefill_rows = sum(1 for w in active if len(w.tokens) > 1)
        n_slots = self.config.max_num_seqs * width
        ahead = int(bool(self._in_flight))
        # The drilled wedged-step site: an armed ``serve_watchdog_stall``
        # stands in for a device step that never completed (the runtime
        # surfacing a timeout/cancellation) — the watchdog recovery
        # path must absorb it without crashing the engine loop.
        fault_point("serve_watchdog_stall")
        step = self.steps_run
        with timers.record(
                "serve_dispatch", step=step, width=width,
                rows=len(active), positions=positions, slots=n_slots,
                prefill_rows=prefill_rows,
                sampled=sum(1 for w in active if w.samples_next),
                ahead=ahead, fed_rows=sum(1 for w in active if w.fed)):
            greedy, last, self.pools, *routed = self.step_fn(width)(
                self.params, self.pools, *args, *extra)
        self._prev_tok = last.token
        # a greedy engine lets go of the [B, V] logits here, so two steps'
        # worth are never alive at once
        self._in_flight.append(_Flight(
            plan, step, greedy,
            last.logits if self.generation.do_sample else None, routed))
        self.scheduler.advance(plan)
        if self.grouped:
            # the keys one layer of each block group had to read this step
            # (the context, or what of it the group's window still shows)
            timers.event("serve_kv_read", step=step, rows=len(active),
                         positions=positions,
                         **{f"{name}_keys": n for name, n in
                            self.scheduler.keys_read(plan).items()})
        if self.state_planes:
            resets = sum(1 for w in active if w.start_pos == 0)
            timers.event("serve_state", step=step, rows=len(active),
                         resets=resets, chunk_rows=prefill_rows)
            self.state_rows_sum += len(active)
            self.state_resets_sum += resets
        self.steps_run += 1
        self.ahead_steps += ahead
        self.rows_sum += len(active)
        self.positions_sum += positions
        self.slots_sum += n_slots
        # a decode step carries no prefill work — under speculation its
        # width is spec_k+1, so classify by the rows, not the width
        if not prefill_rows:
            self.decode_steps += 1
        else:
            self.mixed_steps += 1

    def _deliver(self, flight: _Flight) -> List[Request]:
        """Fetch one dispatched step's tokens and run the half of the
        scheduler's step that needs them; returns the requests it
        finished."""
        timers = self.timers
        # the engine's one host sync a step, one step behind the dispatch
        # where it looks ahead: the [B, W] per-column argmax drives the
        # host-side request state machine — plain decode reads one column,
        # the speculative verify reads k+1, SAME fetch either way
        with timers.record("serve_fetch"):
            greedy, *routed = (np.asarray(a) for a in jax.device_get((flight.greedy, *flight.routed)))  # lint: disable=L004 (continuous batching IS a per-step host decision loop: one [B, W]-int fetch per step — the speculative verify rides it too — taken one step BEHIND the dispatch on a greedy engine, so the device runs the next step while this one's tokens come back; the logits stay on device unless do_sample)
        if routed:
            # a span's stats are fixed when it opens, so this is an event
            assignments, hit = int(routed[0].sum()), int((routed[0] > 0).sum())
            timers.event("serve_experts", step=flight.step,
                         assignments=assignments, hit=hit)
            self.expert_assignments_sum = (self.expert_assignments_sum
                                           or 0) + assignments
            self.experts_hit_sum = (self.experts_hit_sum or 0) + hit
        with timers.record("serve_finish"):
            # slot -> this row's greedy/sampled CHAIN: column t-1 is the
            # plain next token, columns t..t+d-1 are the argmax at the d
            # draft positions (the verify read — deliver accepts the
            # longest matching prefix).  do_sample rows (never drafted)
            # sample host-side.
            sampled = {}
            for b, w in enumerate(flight.plan.rows):
                # a row of the plan IS its slot when the plan was made, and
                # a request the delivery applies to still holds it
                if w is None or not w.samples_next:
                    continue
                t = len(w.tokens)
                if self.generation.do_sample:
                    sampled[b] = [self._sample(flight.step, b,
                                               flight.logits)]
                else:
                    sampled[b] = greedy[b, t - 1:t + len(w.draft)].tolist()
            appended0 = self.scheduler.tokens_appended
            done = self.scheduler.deliver(flight.plan, sampled)
            self.tokens_generated += (self.scheduler.tokens_appended
                                      - appended0)
        return done

    def _flush(self) -> None:
        """Deliver every step in flight now (before an abort or a weight
        handoff, after a drain's deadline): the caller goes on with nothing
        in flight.  What finishes here is returned by the next ``step()``."""
        while self._in_flight:
            self._done_between.extend(
                self._deliver(self._in_flight.popleft()))

    def run(self, max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Drive until every submitted request reaches a terminal state;
        returns rid -> generated tokens.  ``max_steps`` (default: a
        generous work bound) turns a scheduler bug into a loud error
        instead of a hang."""
        if max_steps is None:
            budget = sum(
                blocks_needed(len(r.prompt), self.config.prefill_chunk)
                + r.max_new_tokens + 1
                for r in self.requests.values() if not r.finished)
            max_steps = 64 + 8 * budget
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"engine made no progress within {max_steps} steps — "
                    "scheduler stall (file a bug with the request trace)")
        return {rid: list(r.out_tokens) for rid, r in self.requests.items()}

    # -- graceful drain (SIGTERM/SIGINT path in tools/serve.py) ------------
    def drain(self, grace_s=_GRACE_FROM_CONFIG) -> Dict[str, int]:
        """Stop admitting and finish in-flight work, bounded by a grace
        deadline.

        New submissions reject immediately (typed, reason ``draining``);
        NEVER-ADMITTED rows still waiting when the drain starts reject
        too — a restarting client should resubmit elsewhere.  ADMITTED
        requests keep stepping — including preempted/watchdog-replayed
        rows parked in the waiting list: they are in-flight work and
        re-admit with their generated tokens intact — until done or until
        ``grace_s`` runs out, at which point the stragglers EXPIRE with
        their blocks reclaimed.  Returns the per-terminal-state counts
        (:meth:`outcome_counts`)."""
        if grace_s is _GRACE_FROM_CONFIG:
            grace_s = self.config.drain_grace_s
        self.scheduler.draining = True
        for req in list(self.scheduler.waiting):
            if req.was_admitted:
                continue     # parked in-flight work re-admits and finishes
            self.rejections.append(
                self.scheduler._reject(req, "draining"))
        deadline = None if grace_s is None else self.clock() + grace_s
        with self.timers.record("serve_drain"):
            while self.scheduler.has_work():
                if deadline is not None and self.clock() >= deadline:
                    for req in (list(self.scheduler.active)
                                + list(self.scheduler.waiting)):
                        self.scheduler.expire(req, reason="drain_deadline")
                    self._flush()    # rows of expired requests: dropped
                    break
                self.step()
        return self.outcome_counts()

    # -- the generate()-shaped oracle entry --------------------------------
    def generate(self, input_ids, prompt_lens=None,
                 config: Optional[GenerationConfig] = None) -> np.ndarray:
        """Drop-in for :func:`automodel_tpu.generation.generate`:
        right-padded ``[B, S]`` prompts -> ``[B, max_new_tokens]`` int32
        with ``pad_token_id`` after eos — the tier-1 parity oracle drives
        both paths with this exact contract."""
        cfg = config or self.generation
        ids = np.asarray(input_ids)
        B, S = ids.shape
        lens = (np.full((B,), S, np.int64) if prompt_lens is None
                else np.asarray(prompt_lens))
        rids = [self.submit(ids[b, :int(lens[b])],
                            max_new_tokens=cfg.max_new_tokens,
                            eos_token_id=cfg.eos_token_id)
                for b in range(B)]
        self.run()
        # the ORACLE contract: every row must have genuinely finished — a
        # row the robustness layer rejected/expired (e.g. a max_waiting
        # bound on an eval engine) padded silently would corrupt scores
        not_finished = {rid: self.requests[rid].state.value
                        for rid in rids
                        if self.requests[rid].state
                        is not RequestState.FINISHED}
        if not_finished:
            raise RuntimeError(
                f"engine.generate(): {len(not_finished)} of {B} rows did "
                f"not finish ({not_finished}) — generate() is the parity "
                "oracle and cannot pad shed/expired rows; drive lossy "
                "traffic through submit()/step() and read outcome_counts()")
        out = np.full((B, cfg.max_new_tokens), cfg.pad_token_id, np.int32)
        for b, rid in enumerate(rids):
            toks = self.requests[rid].out_tokens
            out[b, :len(toks)] = toks
        return out

    # -- telemetry ---------------------------------------------------------
    def outcome_counts(self) -> Dict[str, int]:
        """Requests per lifecycle state (terminal AND in-flight) — the
        per-terminal-state summary ``tools/serve.py`` prints and exits
        nonzero on when anything is not ``finished``."""
        counts: Dict[str, int] = {}
        for req in self.requests.values():
            counts[req.state.value] = counts.get(req.state.value, 0) + 1
        return counts

    def completed_in_deadline(self) -> int:
        """FINISHED requests whose completion stamp met their deadline (no
        deadline counts as met) — the numerator of the goodput fraction.
        The step-boundary sweep expires over-deadline rows, but a request
        can still finish DURING the step that crossed its deadline — those
        count as misses here even though they produced tokens."""
        n = 0
        for req in self.requests.values():
            if req.state is not RequestState.FINISHED:
                continue
            if (req.deadline_s is None or req.finish_time is None
                    or req.finish_time - req.submit_time <= req.deadline_s):
                n += 1
        return n

    def stats(self) -> Dict[str, Any]:
        idx = self.prefix_index
        sched = self.scheduler

        def per_group(read):
            if not self.grouped:
                return read(self.allocator)
            return {g.name: read(g.allocator) for g in self.block_groups}

        prefix = {
            "enabled": idx is not None,
            "lookups": idx.lookups if idx else 0,
            "hits": idx.hits if idx else 0,
            "misses": idx.misses if idx else 0,
            "insertions": idx.insertions if idx else 0,
            "evictions": idx.evictions if idx else 0,
            "cached_blocks": idx.cached_blocks if idx else 0,
            "cow_forks": sched.cow_forks,
            "cow_fork_failures": sched.cow_fork_failures,
            "deferrals": sched.prefix_deferrals,
        }
        spec = {
            "enabled": self.spec_mode != "off",
            "mode": self.spec_mode,
            "spec_k": self.spec_k,
            "tokens_proposed": sched.spec_tokens_proposed,
            "tokens_accepted": sched.spec_tokens_accepted,
            "draft_faults": sched.spec_draft_faults,
            "verify_failures": sched.spec_verify_failures,
        }
        slots = self.adapter_slots
        multi_tenant = {
            "enabled": slots is not None,
            "per_tenant": {k: dict(v)
                           for k, v in sorted(sched.per_tenant.items())},
        }
        if slots is not None:
            multi_tenant["adapters"] = slots.stats()
            multi_tenant["tenant_quota"] = self.config.tenant_quota
            multi_tenant["quota_deferrals"] = sched.tenant_quota_deferrals
        return {
            "prefill_tokens_saved": sched.prefix_tokens_reused,
            "cache_hit_rate": (idx.hits / max(1, idx.lookups)
                               if idx else 0.0),
            "prefix_cache": prefix,
            "spec_tokens_accepted": sched.spec_tokens_accepted,
            "accept_rate": (sched.spec_tokens_accepted
                            / max(1, sched.spec_tokens_proposed)),
            "tokens_per_step": (self.tokens_generated
                                / max(1, self.steps_run)),
            "speculative": spec,
            "multi_tenant": multi_tenant,
            "steps": self.steps_run,
            # the look-ahead: steps dispatched before the one before was
            # fetched, and rows a step computed for a request that had
            # finished or been preempted by the time it was delivered
            "lookahead": self.lookahead,
            "ahead_steps": self.ahead_steps,
            "discarded_rows": self.scheduler.discarded_rows,
            # step fill: positions_sum / slots_sum is the share of computed
            # positions that held a pending token (rows_sum: active rows)
            "rows_sum": self.rows_sum,
            "positions_sum": self.positions_sum,
            "slots_sum": self.slots_sum,
            "decode_steps": self.decode_steps,
            "mixed_steps": self.mixed_steps,
            "tokens_generated": self.tokens_generated,
            # None unless the model's step has routed expert layers
            "expert_assignments_sum": self.expert_assignments_sum,
            "experts_hit_sum": self.experts_hit_sum,
            # None unless the model keeps per-sequence state planes
            "state_rows_sum": self.state_rows_sum,
            "state_resets_sum": self.state_resets_sum,
            "state_plane_bytes": (pool_bytes(self.pools)
                                  if self.state_planes else None),
            "preemptions": self.scheduler.preemptions,
            "admissions": self.scheduler.admissions,
            "aborts": self.aborts,
            "expired": self.scheduler.expired,
            "rejected": self.scheduler.rejected,
            "pinned": self.scheduler.pins,
            "watchdog_recoveries": self.watchdog_recoveries,
            "weight_syncs": self.weight_syncs,
            "kv_pool_bytes": pool_bytes(self.pools),
            # a cache of several block groups: by group name
            "kv_blocks_peak": per_group(lambda a: a.peak_used),
            "kv_blocks_free": per_group(lambda a: a.free_blocks),
            "failed_allocs": sum(g.allocator.failed_allocs
                                 for g in self.block_groups),
            # blocks a window group released while their request ran
            "window_blocks_released": dict(self.scheduler.blocks_released),
            "compiled_widths": sorted(self._steps),
            "outcomes": self.outcome_counts(),
        }
