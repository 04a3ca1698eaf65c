"""Block-paged KV cache: static pools, a host-side block allocator, and
the pytree view the model's attention core consumes.

The dense decode cache (``generation/dense_kv.DenseKVView``, the other
implementation of the cache protocol in ``models/layer_scan.py``) reserves
``[B, S_max]`` rows per request — at serving batch sizes that is almost entirely dead HBM
(most requests are far shorter than the max).  The paged cache instead
keeps ONE static pool of fixed-size blocks per layer, stacked over the
layers into one buffer the step donates and updates in place,

    ``k/v: [L, num_blocks, block_size, Hk, D]``  (position-major),

(``[L, num_blocks, block_size, Hk * D]``, the same bytes as ROWS, where the
kv heads are fewer than the dtype's sublane packing: the paged kernel then
reads a page as dense ``(block_size, Hk * D)`` tiles; :func:`pool_layout`)
and a per-request *block table* mapping position ``p`` to slot ``p %
block_size`` of block ``table[p // block_size]``.  Blocks are recycled
through a free list as requests finish, so the pool sizes to the TOTAL
live tokens, not ``max_num_seqs * max_model_len``.  Everything the jitted
step touches is static-shape: pools, ``[B, MB]`` block tables, ``[B, S]``
slot mappings — allocation is pure host bookkeeping
(:class:`BlockAllocator`), never a trace event.

A model with a LATENT cache (MLA: DeepSeek-V3, Kimi-K2) says so through
``model.paged_cache_planes()`` and gets ONE plane ``kv: [L, num_blocks,
block_size, kv_lora_rank + qk_rope_head_dim]`` whose leading values are key
and value at once (:meth:`PagedKVView.write_latent` /
:meth:`PagedKVView.attend_latent`); allocator, block tables, scheduler and
:func:`cow_copy_blocks` see block ids only and do not change.

A model with per-SEQUENCE state (power retention: Brumby) declares planes
of kind ``"sequence"`` and gets NO block pool at all: ``{name: [L,
max_num_seqs, *per-row shape]}`` float32, one row per step-buffer row
(``Request.slot``), read, advanced and written in place through the ONE
method of :class:`StatePlaneView`, ``retain``.  A request's memory does not
grow with its context, so the allocator's blocks never bind for such a
family (the scheduler is told a token costs nothing) and ``max_model_len``
costs no HBM.

A model whose layers are of two kinds, some keeping every key and some
only a WINDOW of them, declares its cache PER GROUP of layers
(``{"full": {"planes": {...}, "layers": n}, "window": {"planes": {...},
"layers": m, "window": W}}``, :func:`cache_groups`) and gets a cache of
BLOCK GROUPS: each group its own pools ``{name: {"k"|"v": [L_group,
NB_group, BS, Hk, D]}}``, its own :class:`BlockAllocator` and its own block
table per request (:class:`BlockGroup`); the view addresses the group of
the layer it stands at (``at_layer(state, layer, group=(name, index))``).
A window group's table is indexed by position like any other, but the
scheduler RELEASES the blocks that lie wholly behind the window while the
request runs and leaves the null page in their place, so a row never holds
more than :func:`~automodel_tpu.ops.paged_attention.window_span_blocks` of
them, whatever its context; the kernel starts its walk at the first block
the window touches and never visits the released ones.  A cache declared
flat (every family before) is a cache of ONE group and goes through the
same code: its pools, tables and slot mappings are held bare instead of
under a group's name.

Block 0 is the reserved **null page**: pad tokens write into it and pad
block-table entries point at it, so scatter/gather shapes stay static and
garbage is never read (context-length masks exclude it).

``serving.kv_cache_dtype: int8`` stores the pools quantized with per-slot
per-kv-head scale planes ``[L, num_blocks, block_size, Hk]`` — the scale
rides the same block layout as the data, so one block table addresses
both.  Quantize/rescale reuses PR-10's machinery (``ops/quant.quant_cast``
at write, broadcast rescale at read — in-VMEM inside the Pallas decode
rung, XLA-fused in the gather fallback).

**Prefix caching** (``serving.prefix_caching: on``) makes committed
blocks shareable across requests: :class:`BlockAllocator` reference-counts
every live block (``free`` is a decref; the pool reclaims at zero) and
:class:`PrefixIndex` keys each FULL committed block by the hash chain
``key = sha256(parent_key, block's token ids)`` — SGLang's RadixAttention
design on the vLLM block substrate.  Lookup walks a request's tokens
block-by-block and returns the longest cached chain; a refcount-zero
indexed block parks in a warm LRU (still ON the free ledger, so
``all_free`` stays the leak oracle) and is evicted only when the
allocator genuinely needs it back — never from a live table.  The last,
partially-covered block of a fully-cached sequence is COPY-ON-WRITE:
the writer takes a private block and the jitted step runs
:func:`cow_copy_blocks` (a fixed whole-block copy riding the existing
step buffers — no new program shapes; int8 scale planes ride the same
block ids, so sharing a block shares its scales).
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ``serving.kv_cache_dtype`` config domain (enum-validated at config load
# like cp_layout / moe.dispatch — see loader._enum_fields).  ``auto``
# stores the model's compute dtype.
KV_CACHE_DTYPES = ("auto", "int8")
DEFAULT_KV_CACHE_DTYPE = "auto"


def normalize_kv_cache_dtype(v):
    from automodel_tpu.config.loader import normalize_null_spelling

    return normalize_null_spelling(v)


def validate_kv_cache_dtype(v: Optional[str]) -> Optional[str]:
    if v is None:
        return None
    if v not in KV_CACHE_DTYPES:
        raise ValueError(
            f"serving.kv_cache_dtype must be one of {list(KV_CACHE_DTYPES)} "
            f"(or null for the default), got {v!r}")
    return v


# ``serving.prefix_caching`` config domain.  YAML ``on``/``off`` are 1.1
# bool literals, so the normalizer maps real bools back onto the mode
# names before the membership check — the ``kernels.autotune`` pattern.
PREFIX_CACHING_MODES = ("off", "on")
DEFAULT_PREFIX_CACHING = "off"


def normalize_prefix_caching(v):
    from automodel_tpu.config.loader import normalize_null_spelling

    v = normalize_null_spelling(v)
    if isinstance(v, bool):
        return "on" if v else "off"
    return v


def validate_prefix_caching(v: Optional[str]) -> Optional[str]:
    if v is None:
        return None
    if v not in PREFIX_CACHING_MODES:
        raise ValueError(
            f"serving.prefix_caching must be one of "
            f"{list(PREFIX_CACHING_MODES)} (YAML on/off/true/false, or "
            f"null for the default), got {v!r}")
    return v


class OutOfBlocks(RuntimeError):
    """KV pool exhausted — the scheduler converts this into a preemption
    (a request parked back to WAITING with its blocks freed), never a
    crash."""


class BlockAllocator:
    """Host-side free-list allocator over the pool's block ids, with
    per-block REFERENCE COUNTS so committed blocks can be shared across
    requests (prefix caching).

    Block 0 is reserved as the null page (never handed out); allocation
    and free are O(1)-per-block ops on python ints — deterministic, no
    device traffic.  ``allocate`` hands out blocks at refcount 1;
    :meth:`incref` adds a holder (a prefix hit sharing the block);
    :meth:`free` is a DECREF — the block returns to the free ledger only
    when its last holder lets go, so preemption/abort/expiry/watchdog
    reclaim and the fleet's ``harvest_for_replay`` all route through one
    path and a shared block survives any one holder's death.

    The set mirror of the free ledger keeps double-free detection O(1)
    and extends unchanged to shared blocks: decref of a live block is
    legal per holder, but freeing a block that already reached zero is
    still the loud ``double free`` ValueError.  ``peak_used`` /
    ``failed_allocs`` feed the engine's stats; :attr:`all_free` is the
    leak oracle the overload/fault drills pin after every terminal state
    — refcount-zero blocks a :class:`PrefixIndex` keeps warm count as
    free (they are reclaimable on demand, just not yet recycled).
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 KV blocks (1 null + 1 usable), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._free_set = set(self._free)
        self._refs: Dict[int, int] = {}      # live block -> holder count
        self.prefix_index: Optional["PrefixIndex"] = None
        self.peak_used = 0
        self.failed_allocs = 0

    @property
    def free_blocks(self) -> int:
        # the full free ledger: the plain free list PLUS index-warmed
        # refcount-zero blocks (evictable on demand)
        return len(self._free_set)

    @property
    def used_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free_set)

    @property
    def all_free(self) -> bool:
        """True when every allocable block is back on the free ledger — the
        no-leak invariant every request's terminal transition (FINISHED,
        ABORTED, EXPIRED, REJECTED, preempted, watchdog-replayed) must
        restore once no request holds a table.  Blocks the prefix index
        keeps warm at refcount zero ARE free: cached, not leaked."""
        return len(self._free_set) == self.num_blocks - 1

    def ref_count(self, block: int) -> int:
        """Current holder count of ``block`` (0 when free/cached-free)."""
        return self._refs.get(block, 0)

    def allocate(self, n: int) -> List[int]:
        """``n`` block ids at refcount 1, or :class:`OutOfBlocks` (nothing
        handed out — all-or-nothing, so a failed grab never leaks).
        Uncached free blocks are preferred; only when those run out does
        the prefix index evict (LRU) from its warm refcount-zero pool —
        never from a live table."""
        if n > len(self._free_set):
            self.failed_allocs += 1
            raise OutOfBlocks(
                f"KV pool exhausted: requested {n} blocks, "
                f"{len(self._free_set)} free of {self.num_blocks - 1}")
        out = []
        for _ in range(n):
            b = (self._free.pop() if self._free
                 else self.prefix_index.evict_lru())
            self._refs[b] = 1
            out.append(b)
        self._free_set.difference_update(out)
        self.peak_used = max(self.peak_used, self.used_blocks)
        return out

    def incref(self, blocks: List[int]) -> None:
        """Add one holder to each LIVE block (a prefix hit sharing it)."""
        for b in blocks:
            if b not in self._refs:
                raise ValueError(f"incref of non-live block {b}")
            self._refs[b] += 1

    def revive(self, block: int) -> None:
        """A prefix hit on an index-warmed refcount-zero block: pull it
        back off the free ledger at refcount 1 (the PrefixIndex removes it
        from its own LRU before calling)."""
        if block not in self._free_set:
            raise ValueError(f"revive of non-free block {block}")
        self._free_set.discard(block)
        self._refs[block] = 1
        self.peak_used = max(self.peak_used, self.used_blocks)

    def free(self, blocks: List[int]) -> None:
        """DECREF each block; a block whose last holder released returns
        to the free ledger (parked warm when the prefix index still maps
        it, else straight onto the free list)."""
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate block ids in free(): {blocks}")
        for b in blocks:
            if not 1 <= b < self.num_blocks:
                raise ValueError(f"freeing unknown block id {b}")
            if b in self._free_set:
                raise ValueError(f"double free of block {b}")
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] > 0:
                continue                 # another holder keeps it live
            del self._refs[b]
            self._free_set.add(b)
            if not (self.prefix_index is not None
                    and self.prefix_index.retain_freed(b)):
                self._free.append(b)


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """One group of layers that share pools, an allocator and a block table
    a request, as the model declares it.  ``name`` None: the cache's one
    group, declared flat.  ``window``: keys behind a query that the
    group's layers may still see (None: all of them)."""

    name: Optional[str]
    planes: Dict[str, Tuple]
    layers: int
    window: Optional[int] = None


def cache_groups(planes: Dict[str, Any], num_layers: int) -> List[CacheGroup]:
    """``model.paged_cache_planes()`` as a list of groups: a flat
    declaration (``{plane: per-slot shape}``) is one unnamed group over all
    layers; a grouped one is ``{name: {"planes", "layers", "window"}}``."""
    if not all(isinstance(v, dict) for v in planes.values()):
        return [CacheGroup(None, dict(planes), num_layers)]
    groups = [CacheGroup(name, dict(g["planes"]), int(g["layers"]),
                         g.get("window")) for name, g in planes.items()]
    if sum(g.layers for g in groups) != num_layers:
        raise ValueError(
            f"cache groups {[(g.name, g.layers) for g in groups]} do not "
            f"cover the model's {num_layers} layers")
    return groups


@dataclasses.dataclass
class BlockGroup:
    """The host's side of one cache group: its allocator and its window."""

    name: Optional[str]
    allocator: BlockAllocator
    window: Optional[int] = None


class PrefixIndex:
    """Content-hash index over FULL committed KV blocks — the sharing
    substrate of ``serving.prefix_caching``.

    Each entry keys one block by its hash chain::

        key = sha256(parent_key || block's token ids)

    so two sequences share exactly their common block-aligned prefix and
    a lookup needs no token comparison — walking the chain key-by-key
    finds the longest cached run of full blocks.  Eviction rules:

    * a LIVE block (refcount >= 1) is never evicted — its entry simply
      rides along while requests share it;
    * at refcount zero the block parks in the warm LRU (``lru_blocks``
      bounds it; ``None`` keeps every free block warm) — still on the
      allocator's free ledger, so ``all_free`` is unchanged;
    * the allocator evicts warm blocks LRU-last only when its plain free
      list runs dry, and :meth:`flush` (watchdog pool rebuild) forgets
      everything at once — rebuilt pools zero the contents, so a stale
      hit would read garbage.
    """

    def __init__(self, allocator: BlockAllocator, *, block_size: int,
                 lru_blocks: Optional[int] = None):
        self.allocator = allocator
        allocator.prefix_index = self
        self.block_size = block_size
        self.lru_blocks = lru_blocks
        self._by_key: Dict[str, int] = {}
        self._by_block: Dict[int, str] = {}
        self._cached_free: "OrderedDict[int, None]" = OrderedDict()
        self.lookups = 0
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0

    @staticmethod
    def chain_key(parent_key: Optional[str], tokens) -> str:
        h = hashlib.sha256()
        h.update((parent_key or "").encode("ascii"))
        h.update(np.asarray(list(tokens), dtype=np.int64).tobytes())
        return h.hexdigest()

    @staticmethod
    def root_key(adapter_id: int = 0) -> Optional[str]:
        """Chain root for a tenant.  LoRA on q/k/v changes KV content, so
        chains must namespace by adapter: a shared-prefix hit across
        tenants would be a cross-tenant KV leak.  Base-model traffic
        (adapter 0) roots at ``None`` — its keys, and therefore its warm
        index, are byte-identical to a pre-multi-tenant engine."""
        return None if adapter_id == 0 else "adapter:%d" % int(adapter_id)

    def chain_keys(self, tokens, adapter_id: int = 0) -> List[str]:
        """The hash-chain keys of every FULL block of ``tokens``, rooted
        in ``adapter_id``'s namespace."""
        bs = self.block_size
        keys: List[str] = []
        parent: Optional[str] = self.root_key(adapter_id)
        for i in range(len(tokens) // bs):
            parent = self.chain_key(parent, tokens[i * bs:(i + 1) * bs])
            keys.append(parent)
        return keys

    def has_key(self, key: str) -> bool:
        return key in self._by_key

    def peek(self, keys: List[str]) -> int:
        """Length of the cached leading chain — no refs taken (the
        admission-guard / deferral probe)."""
        n = 0
        for k in keys:
            if k not in self._by_key:
                break
            n += 1
        return n

    def acquire(self, keys: List[str]) -> List[int]:
        """Take one reference on each block of the longest cached leading
        chain and return their ids (warm refcount-zero blocks are revived,
        live ones increfed)."""
        self.lookups += 1
        chain: List[int] = []
        for k in keys:
            b = self._by_key.get(k)
            if b is None:
                break
            if b in self._cached_free:
                del self._cached_free[b]
                self.allocator.revive(b)
            else:
                self.allocator.incref([b])
            chain.append(b)
        if chain:
            self.hits += 1
        else:
            self.misses += 1
        return chain

    def commit(self, parent_key: Optional[str], tokens, block_id: int) -> str:
        """Register one FULL committed block under its chain key.  First
        writer wins: when the content is already indexed (a concurrent
        twin, or a COW fork recomputing a cached block) the existing entry
        is kept and ``block_id`` stays private.  Returns the key either
        way — the caller's chain parent for the next block."""
        key = self.chain_key(parent_key, tokens)
        if key in self._by_key or block_id in self._by_block:
            return key
        self._by_key[key] = block_id
        self._by_block[block_id] = key
        self.insertions += 1
        return key

    def retain_freed(self, block: int) -> bool:
        """Allocator hook at refcount zero: park an indexed block in the
        warm LRU (True) or decline (False -> the plain free list).  An
        over-bound LRU evicts its coldest entries back to the free list."""
        if block not in self._by_block:
            return False
        self._cached_free[block] = None
        if self.lru_blocks is not None:
            while len(self._cached_free) > self.lru_blocks:
                self.allocator._free.append(self.evict_lru())
        return True

    def evict_lru(self) -> int:
        """Drop the least-recently-parked refcount-zero entry and return
        its block id (the caller decides the destination: the allocator
        hands it out, ``retain_freed`` returns it to the free list)."""
        b, _ = self._cached_free.popitem(last=False)
        del self._by_key[self._by_block.pop(b)]
        self.evictions += 1
        return b

    @property
    def cached_blocks(self) -> int:
        return len(self._by_key)

    def flush(self) -> None:
        """Forget every entry (the watchdog's pool rebuild zeroes cached
        contents); warm blocks rejoin the plain free list."""
        self.allocator._free.extend(self._cached_free)
        self._cached_free.clear()
        self._by_key.clear()
        self._by_block.clear()


def cow_copy_blocks(pools: Dict[str, jnp.ndarray], src: jnp.ndarray,
                    dst: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """The jitted copy-on-write fork: whole-block copy ``src[b] -> dst[b]``
    per step row across EVERY pool plane (int8 scale planes ride the same
    block ids, so a forked block carries its scales).  Fixed ``[B]``-pair
    shapes ride the existing step buffers — rows without a fork carry
    ``(0, 0)``, copying the null page onto itself (a content no-op) — so
    hit/miss/fork steps all share one compiled program per width."""
    return {name: pool.at[:, dst].set(pool[:, src])
            for name, pool in pools.items()}


def latent_plane_width(r: int) -> int:
    """A latent plane's stored width: ``r`` rounded up to the 128-lane tile.
    The TPU pads the minor dimension of an HBM array to the tile anyway (a
    576-wide bf16 plane IS ``memref<..x640xbf16>`` to Mosaic, which then
    refuses a 576-wide page as a DMA source), so the padding costs no byte
    that was not already there; the pad columns are written as zeros and
    meet zeros in the query."""
    return -(-int(r) // 128) * 128


def init_paged_pools(*, num_layers: int, num_blocks: int, block_size: int,
                     cache_dtype, quantized: bool,
                     planes: Dict[str, Tuple[int, ...]],
                     ) -> Dict[str, jnp.ndarray]:
    """The static per-layer-stacked pools, one per plane of the model's
    cache: ``{name: [L, NB, BS, *per-slot shape]}``.  ``planes`` is what
    the model says it caches per token (``model.paged_cache_planes()``):
    ``{"k"|"v": (Hk, D)}`` for a per-head cache, ``{"kv": (R,)}`` for a
    latent cache (MLA: ONE plane whose leading values are key AND value).
    A per-head plane whose heads are fewer than the dtype's sublane
    packing is stored as rows, ``[L, NB, BS, Hk * D]``
    (``ops/paged_attention.stores_rows``): the shape of the plane decides,
    never a knob.  A quantized per-head cache adds ``{"k_scale"|"v_scale":
    [L, NB, BS, Hk]}``; a latent plane has no per-head scale to carry and
    is refused."""
    from automodel_tpu.ops.paged_attention import stores_rows

    if sequence_planes(planes):
        raise ValueError(
            "per-sequence state planes are allocated by init_state_planes "
            "(a row per step-buffer row), not as block pools")
    dtype = jnp.int8 if quantized else jnp.dtype(cache_dtype)
    pools = {}
    for name, per_slot in planes.items():
        if len(per_slot) == 1:
            per_slot = (latent_plane_width(per_slot[0]),)
        elif stores_rows(per_slot[0], dtype, quantized):
            per_slot = (per_slot[0] * per_slot[1],)
        shape = (num_layers, num_blocks, block_size, *per_slot)
        pools[name] = jnp.zeros(shape, dtype)
        if quantized:
            if len(per_slot) != 2:
                raise NotImplementedError(
                    f"serving.kv_cache_dtype: int8 is not wired for the "
                    f"latent cache plane {name!r} {tuple(per_slot)}: it has "
                    "no per-head scale plane; serve it in the compute dtype")
            # distinct buffers: the step donates the pools, and XLA
            # rejects donating one buffer twice
            pools[name + "_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return pools


def sequence_planes(planes: Dict[str, Tuple]) -> bool:
    """True where the model's planes hold per-SEQUENCE state (``("sequence",
    *per-row shape)``: a retention layer's ``S`` and ``z``) and not
    per-token rows.  A cache is of one kind: a mix is refused."""
    kinds = {bool(p) and p[0] == "sequence" for p in planes.values()}
    if len(kinds) != 1:
        raise NotImplementedError(
            f"cache planes {planes} mix per-sequence state with per-token "
            "planes: a hybrid stack needs both kinds of view in one step "
            "(ROADMAP Reach A4)")
    return kinds.pop()


def init_state_planes(*, num_layers: int, rows: int,
                      planes: Dict[str, Tuple]) -> Dict[str, jnp.ndarray]:
    """The static per-layer-stacked state planes of a family with
    per-sequence state: ``{name: [L, rows, *per-row shape]}`` float32
    zeros, ``rows`` = ``max_num_seqs``.  Row ``b`` belongs to step-buffer
    row ``b`` for good: an idle row keeps (and never reads) what its last
    request left, and a request's first chunk starts it from zero, so there
    is no null row."""
    return {name: jnp.zeros((num_layers, rows, *per_row[1:]), jnp.float32)
            for name, per_row in planes.items()}


def pool_bytes(pools: Dict[str, Any]) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(pools))


def pool_layout(pools: Dict[str, Any]) -> Optional[str]:
    """How one group's pools hold a token: ``"rows"`` (``k``/``v`` as
    ``[.., BS, Hk * D]``), ``"heads"`` (``[.., BS, Hk, D]``), ``"latent"``
    (one MLA plane), None (per-sequence state planes)."""
    if "k" in pools:
        return "rows" if pools["k"].ndim == 4 else "heads"
    return "latent" if "kv" in pools else None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVView:
    """The paged cache as one model forward sees it — a pytree whose array
    leaves are the STACKED pools ``[L, NB, BS, Hk, D]`` (or rows ``[L, NB,
    BS, Hk * D]``), the per-step addressing arrays and the layer the view
    stands at, with the layout facts (block size, quantization) as static
    aux data.

    ``models/layer_scan.scan_layers`` carries the pools through every
    layer scan (the loop's CARRY, never its ``xs``/``ys``: a scan slices its ``xs`` and
    stacks its ``ys`` into a new buffer, one layer of pool out and one in
    per layer) and closes over the addressing arrays, which every layer
    shares; inside the body :meth:`at_layer` rewraps the carried pools
    with the layer's index.  :meth:`write` and :meth:`attend` then address
    the stacked pools AT that layer, so the donated buffers are updated
    and read in place.

    A cache of several block groups holds ``pools``, ``block_tables`` and
    ``slot_mapping`` as dicts by group name and stands at a layer OF A
    GROUP (``group``, static); a cache of one group holds them bare.
    """

    pools: Dict[str, Any]
    block_tables: Any             # [B, MB] int32 (by group name: a dict)
    slot_mapping: Any             # [B, S] int32 flat slot per written token
    context_lens: jnp.ndarray     # [B] int32, INCLUDING this step's writes
    positions: jnp.ndarray        # [B, S] int32 absolute query positions
    layer: Any = None             # int32 scalar (traced in the layer scan)
    block_size: int = 16
    quantized: bool = False
    group: Optional[str] = None   # the group the view stands in

    def tree_flatten(self):
        children = (self.pools, self.block_tables, self.slot_mapping,
                    self.context_lens, self.positions, self.layer)
        return children, (self.block_size, self.quantized, self.group)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, block_size=aux[0], quantized=aux[1],
                   group=aux[2])

    def at_layer(self, pools: Dict[str, Any], layer,
                 group: Optional[Tuple[str, Any]] = None) -> "PagedKVView":
        """The view over ``pools`` (the stacked pools as the layer scan
        carries them) standing at ``layer``; in a cache of block groups,
        at layer ``group[1]`` of the group named ``group[0]``."""
        if group is not None:
            return dataclasses.replace(self, pools=pools, layer=group[1],
                                       group=group[0])
        return dataclasses.replace(self, pools=pools, layer=layer)

    def _mine(self, x):
        """``x`` (pools, tables, slots) of the group the view stands in."""
        return x if self.group is None else x[self.group]

    # -- the model-facing seam (the cache protocol of models/layer_scan.py) --
    def write(self, k: jnp.ndarray, v: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Scatter this step's ``[B, S, Hk, D]`` k/v into the view's layer
        of the stacked pools — flat slot ``layer * NB * BS + slot_mapping``
        (pad tokens land in the layer's null page 0) — and return the
        stacked pools dict.  A pool stored as rows takes ``[B*S, Hk * D]``
        rows, as :meth:`write_latent` does.  int8 pools quantize per written
        slot per kv head (PR-10's ``quant_cast``), storing the scale in the
        matching scale plane — indexed ``[layer, block, slot]`` as it
        stands: a plane's ``[.., BS, Hk]`` tiles are far under a lane tile,
        so the TPU keeps it NB-minor, and a reshape to rows would have
        every layer relay out the whole plane."""
        B, S, Hk, D = k.shape
        pools = dict(self._mine(self.pools))
        NB, BS = pools["k"].shape[1:3]
        layer = jnp.asarray(self.layer, jnp.int32)
        slot = self._mine(self.slot_mapping).reshape(-1)
        slots = layer * (NB * BS) + slot
        for name, x in (("k", k), ("v", v)):
            pool = pools[name]
            slot_shape = pool.shape[3:]             # (Hk, D) or (Hk * D,)
            flat = x.reshape(B * S, Hk, D)
            if self.quantized:
                from automodel_tpu.ops.quant import INT8_MAX, quant_cast

                amax = jnp.max(jnp.abs(flat.astype(jnp.float32)), axis=-1)
                sc = jnp.maximum(amax, 1e-12) / INT8_MAX      # [B*S, Hk]
                flat = quant_cast(flat, sc[..., None], jnp.int8)
                pools[name + "_scale"] = pools[name + "_scale"].at[
                    layer, slot // BS, slot % BS].set(sc)
            else:
                flat = flat.astype(pool.dtype)
            pools[name] = pool.reshape(-1, *slot_shape).at[slots].set(
                flat.reshape(-1, *slot_shape)).reshape(pool.shape)
        if self.group is not None:
            return {**self.pools, self.group: pools}
        return pools

    def attend(self, q: jnp.ndarray, pools: Dict[str, jnp.ndarray], *,
               scale=None, logits_soft_cap=None, local_window_size=None
               ) -> jnp.ndarray:
        """Paged attention of ``q [B, S, Hq, D]`` over the view's layer of
        the (freshly written) stacked pools, through the
        ``attention.paged_decode`` chain."""
        from automodel_tpu.ops.paged_attention import paged_attention

        pools = self._mine(pools)
        return paged_attention(
            q, pools["k"], pools["v"],
            k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
            layer=self.layer,
            block_tables=self._mine(self.block_tables),
            context_lens=self.context_lens,
            positions=self.positions, scale=scale,
            logits_soft_cap=logits_soft_cap,
            local_window_size=local_window_size,
            kernel_name=(None if self.group is None
                         else f"paged_decode_{self.group}"))

    def valid_tokens(self) -> jnp.ndarray:
        """``[B, S]`` bool: the step buffer's columns that hold a token.
        The engine writes a row's tokens at consecutive positions and pads
        by repeating the last one, so a row holds ``last - first + 1``; an
        idle row's table is all null page (block 0 is never a request's)
        and it holds none.  The entry asked is the one of the row's LAST
        position: it is live in every group, a window group's too."""
        pos = self.positions
        held = pos[:, -1:] - pos[:, :1] + 1
        tables = jax.tree.leaves(self.block_tables)[0]    # any group's
        last = (self.context_lens - 1) // self.block_size
        busy = jnp.take_along_axis(tables, last[:, None], axis=1) != 0
        return (jnp.arange(pos.shape[1], dtype=pos.dtype)[None, :] < held) & busy

    # -- the latent plane's pair (deepseek_v3._mla_attention, absorbed form) --
    def write_latent(self, latent: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Scatter this step's ``[B, S, R]`` latent rows (MLA: the normalised
        ``c_kv`` and the rotated rope key, side by side) into the view's
        layer of the ONE stacked plane ``kv [L, NB, BS, R]``, at flat slot
        ``layer * NB * BS + slot_mapping``; returns the stacked pools.  The
        plane is :func:`latent_plane_width` wide: the rows are zero-padded
        to it."""
        pools = dict(self.pools)
        pool = pools["kv"]
        NB, BS, R = pool.shape[1:]
        latent = jnp.pad(latent, ((0, 0), (0, 0),
                                  (0, R - latent.shape[-1])))
        slots = (jnp.asarray(self.layer, jnp.int32) * (NB * BS)
                 + self.slot_mapping.reshape(-1))
        pools["kv"] = pool.reshape(-1, R).at[slots].set(
            latent.reshape(-1, R).astype(pool.dtype)).reshape(pool.shape)
        return pools

    def attend_latent(self, q: jnp.ndarray, pools: Dict[str, jnp.ndarray],
                      *, value_dim: int, scale: float) -> jnp.ndarray:
        """MQA over the latent: ``q [B, S, Hq, R]`` (absorbed queries beside
        their rope part) against the view's layer of ``kv``, whose first
        ``value_dim`` values are also the value -> ``[B, S, Hq, value_dim]``
        through the ``attention.mla_paged_decode`` chain."""
        from automodel_tpu.ops.mla_paged_attention import mla_paged_attention

        q = jnp.pad(q, ((0, 0),) * 3
                    + ((0, pools["kv"].shape[-1] - q.shape[-1]),))
        return mla_paged_attention(
            q, pools["kv"], layer=self.layer,
            block_tables=self.block_tables, context_lens=self.context_lens,
            positions=self.positions, value_dim=value_dim, scale=scale)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StatePlaneView:
    """A per-sequence state cache as one model forward sees it: the stacked
    planes ``{"state": [L, B, Hk, O, dv, d], "norm": [L, B, Hk, O, d]}`` (the
    layer scan's carry, like the paged pools), the step's ``positions [B,
    S]`` and ``block_tables [B, 1]`` whose one entry says whether the row
    holds a request (the engine writes ``slot + 1``; 0 = idle).  Row ``b``
    of the step buffer owns row ``b`` of the planes: there is nothing to
    look up."""

    pools: Dict[str, jnp.ndarray]
    block_tables: jnp.ndarray     # [B, 1] int32, 0 = idle row
    positions: jnp.ndarray        # [B, S] int32 absolute positions
    layer: Any = None             # int32 scalar (traced in the layer scan)

    def tree_flatten(self):
        return (self.pools, self.block_tables, self.positions,
                self.layer), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def at_layer(self, pools, layer) -> "StatePlaneView":
        return dataclasses.replace(self, pools=pools, layer=layer)

    def valid_counts(self) -> jnp.ndarray:
        """``[B]``: a row's leading columns that hold a token (the engine
        writes consecutive positions and pads by repeating the last one;
        an idle row holds none)."""
        pos = self.positions
        held = pos[:, -1] - pos[:, 0] + 1
        return jnp.where(self.block_tables[:, 0] != 0, held, 0)

    # -- the model-facing seam: ONE call (models/brumby.py) ----------------
    def retain(self, q, k, v, log_g):
        """Power retention of this step's ``q [B, S, Hq, d]``, ``k, v [B, S,
        Hk, d]``, ``log_g [B, S, Hk]`` through the view's layer of the
        planes: a row whose positions start at 0 starts from an empty state
        (a request's first chunk, also after preemption or replay: whatever
        the row's last owner left is dropped), then the state is read,
        advanced by the row's valid tokens and written back in place.
        Returns ``(o [B, S, Hq, d], pools)``."""
        from automodel_tpu.ops.power_retention import retention

        n_valid = self.valid_counts()
        with jax.named_scope("state_reset"):
            reset = (self.positions[:, 0] == 0) & (n_valid > 0)
        o, state, norm = retention(
            q, k, v, log_g, self.pools["state"], self.pools["norm"],
            layer=self.layer, n_valid=n_valid, reset=reset)
        return o, {"state": state, "norm": norm}


def slot_for(block_table: List[int], position: int, block_size: int) -> int:
    """Host-side flat pool slot of ``position`` under a request's block
    table (the addressing rule in one place)."""
    return block_table[position // block_size] * block_size \
        + position % block_size


def blocks_needed(tokens: int, block_size: int) -> int:
    return -(-tokens // block_size)
