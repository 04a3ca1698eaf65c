"""Parallelism auditor: structured collective census + sharding audit of a
jitted step function.

Replaces the brittle stringified-jaxpr pins PR-3/4 left behind
(``"ppermute" in str(jaxpr)``, ``str(jaxpr).count("sharding_constraint")``)
with a real walk of the ClosedJaxpr — recursing into ``pjit`` /
``shard_map`` / ``scan`` / ``cond`` / ``custom_vjp`` sub-jaxprs — plus a
census of the compiled HLO's GSPMD-inserted collectives (the FSDP
all-gathers / grad reduce-scatters that never appear in a jaxpr because XLA
materializes them at partitioning time).

Census keys: collective kind -> mesh-axis key -> count.  Jaxpr-level axes
come straight from the primitive's ``axes``/``axis_name`` params; HLO-level
axes are recovered by matching each op's ``replica_groups`` /
``source_target_pairs`` against the groups every subset of mesh axes would
produce — structured, not substring, in both cases.

Golden censuses for the dryrun flagship legs live in
``tests/data/golden_census/`` (regenerate with ``tools/lint.py
--update-golden``) and are asserted by tier-1: a new collective, a dropped
``sharding_constraint``, a host callback sneaking into the hot path, or a
replicated-param regression all fail as a readable census diff instead of a
0.9x bench run three PRs later.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

# Jaxpr-level collective primitives (the shard_map vocabulary), by the
# names the installed JAX emits.  ``check_vma=True`` shard_maps spell psum /
# all_gather as ``*_invariant``; the census counts both under the base name.
_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "psend", "precv", "pbroadcast",
    "all_gather", "all_to_all", "ragged_all_to_all", "reduce_scatter",
}
_INVARIANT_SUFFIX = "_invariant"
# Host-transfer / callback primitives: none of these belong in a hot-path
# step function.  Every ``*callback*`` primitive counts as well;
# ``jax.debug.print`` lowers to its own ``debug_print``.
_HOST_PRIMS = {"infeed", "outfeed", "debug_print"}

# Matches both sync ops ("= f32[64,64]{1,0} all-gather(...)") and the async
# -start forms XLA:TPU emits by default, whose TUPLE result types contain
# spaces ("= (f32[16,64], f32[64,64]) all-gather-start(...)"); the paired
# -done ops deliberately do NOT match (they would double-count).
_HLO_OP_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_HLO_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_HLO_LIST_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d, ]*\}(?:,\{[\d, ]*\})*)\}")
_HLO_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{\d+,\d+\}(?:,\{\d+,\d+\})*)\}")
_HLO_CALLBACK_RE = re.compile(
    r"custom-call\([^)]*\).*custom_call_target=\"([^\"]*callback[^\"]*)\"")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------
def _jaxpr_types():
    from jax._src.core import ClosedJaxpr, Jaxpr

    return ClosedJaxpr, Jaxpr


def _sub_jaxprs(params: Dict[str, Any]):
    ClosedJaxpr, Jaxpr = _jaxpr_types()
    for v in params.values():
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            yield v
        elif isinstance(v, (tuple, list)):
            for s in v:
                if isinstance(s, (ClosedJaxpr, Jaxpr)):
                    yield s


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All eqns of a (Closed)Jaxpr, recursing into every sub-jaxpr param
    (``pjit``/``shard_map``/``scan``/``cond`` branches/``custom_*`` etc.)."""
    ClosedJaxpr, _ = _jaxpr_types()
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _axis_key(eqn) -> str:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return "?"
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    # shard_map's backward pass emits psums with empty axes (a no-op
    # reduction over no mesh axes); key them "none" rather than "".
    return ",".join(str(a) for a in axes) or "none"


def _aval_bytes(aval) -> int:
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# The census
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CollectiveCensus:
    """Structured parallelism census of one step function.

    ``collectives``/``hlo_collectives``: kind -> mesh-axis key -> count.
    ``allgather_max_bytes``: per-axis-key size of the LARGEST gathered
    output at the jaxpr level — a full-parameter forward all-gather (the
    classic FSDP regression) shows up here as a jump nothing else explains.
    """

    collectives: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    sharding_constraints: int = 0
    host_callbacks: Dict[str, int] = dataclasses.field(default_factory=dict)
    allgather_max_bytes: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    hlo_collectives: Optional[Dict[str, Dict[str, int]]] = None
    # Largest all-gather OUTPUT per axis key in the optimized HLO: the
    # direct detector for a full-parameter forward all-gather, since the
    # FSDP gathers GSPMD inserts are per-layer-sized, not tree-sized.
    hlo_allgather_max_bytes: Optional[Dict[str, int]] = None

    def count(self, kind: str, axis: Optional[str] = None) -> int:
        per_axis = self.collectives.get(kind, {})
        if axis is None:
            return sum(per_axis.values())
        return sum(n for k, n in per_axis.items()
                   if axis in k.split(","))

    def to_json_dict(self) -> dict:
        d = dataclasses.asdict(self)
        for k in ("hlo_collectives", "hlo_allgather_max_bytes"):
            if d[k] is None:
                d.pop(k)
        return d

    @classmethod
    def from_json_dict(cls, d: dict) -> "CollectiveCensus":
        return cls(
            collectives=d.get("collectives", {}),
            sharding_constraints=d.get("sharding_constraints", 0),
            host_callbacks=d.get("host_callbacks", {}),
            allgather_max_bytes=d.get("allgather_max_bytes", {}),
            hlo_collectives=d.get("hlo_collectives"),
            hlo_allgather_max_bytes=d.get("hlo_allgather_max_bytes"),
        )

    def diff(self, golden: "CollectiveCensus") -> List[str]:
        """Human-readable mismatches vs a golden census ([] when equal)."""
        out: List[str] = []

        def cmp_table(name, mine, gold):
            for kind in sorted(set(mine) | set(gold)):
                m, g = mine.get(kind, {}), gold.get(kind, {})
                for axis in sorted(set(m) | set(g)):
                    if m.get(axis, 0) != g.get(axis, 0):
                        out.append(
                            f"{name}[{kind}][{axis}]: got {m.get(axis, 0)}, "
                            f"golden {g.get(axis, 0)}")

        cmp_table("collectives", self.collectives, golden.collectives)
        if self.sharding_constraints != golden.sharding_constraints:
            out.append(f"sharding_constraints: got "
                       f"{self.sharding_constraints}, golden "
                       f"{golden.sharding_constraints}")
        for k in sorted(set(self.host_callbacks) | set(golden.host_callbacks)):
            if self.host_callbacks.get(k, 0) != golden.host_callbacks.get(k, 0):
                out.append(f"host_callbacks[{k}]: got "
                           f"{self.host_callbacks.get(k, 0)}, golden "
                           f"{golden.host_callbacks.get(k, 0)}")
        for k in sorted(set(self.allgather_max_bytes)
                        | set(golden.allgather_max_bytes)):
            if (self.allgather_max_bytes.get(k, 0)
                    != golden.allgather_max_bytes.get(k, 0)):
                out.append(
                    f"allgather_max_bytes[{k}]: got "
                    f"{self.allgather_max_bytes.get(k, 0)}, golden "
                    f"{golden.allgather_max_bytes.get(k, 0)} — a jump here "
                    "usually means a full-parameter forward all-gather")
        for field in ("hlo_collectives", "hlo_allgather_max_bytes"):
            mine, gold = getattr(self, field), getattr(golden, field)
            if (mine is None) != (gold is None):
                # A one-sided HLO census is a PARTIAL comparison, never a
                # silent match: the GSPMD-inserted collectives (the FSDP
                # full-param-gather regression class) live only there.
                out.append(
                    f"{field}: present on one side only (got "
                    f"{'set' if mine is not None else 'None'}, golden "
                    f"{'set' if gold is not None else 'None'}) — census "
                    "with include_hlo=True or regenerate the golden")
            elif mine is not None:
                if field == "hlo_collectives":
                    cmp_table(field, mine, gold)
                else:
                    for k in sorted(set(mine) | set(gold)):
                        if mine.get(k, 0) != gold.get(k, 0):
                            out.append(
                                f"{field}[{k}]: got {mine.get(k, 0)}, "
                                f"golden {gold.get(k, 0)} — a jump here "
                                "usually means a full-parameter forward "
                                "all-gather")
        return out


def jaxpr_census(closed_jaxpr) -> CollectiveCensus:
    """Walk a ClosedJaxpr (recursively) into a :class:`CollectiveCensus`."""
    census = CollectiveCensus()
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name.removesuffix(_INVARIANT_SUFFIX)
        if name in _COLLECTIVE_PRIMS:
            key = _axis_key(eqn)
            table = census.collectives.setdefault(name, {})
            table[key] = table.get(key, 0) + 1
            if name == "all_gather" and eqn.outvars:
                nbytes = _aval_bytes(eqn.outvars[0].aval)
                census.allgather_max_bytes[key] = max(
                    census.allgather_max_bytes.get(key, 0), nbytes)
        elif name == "sharding_constraint":
            census.sharding_constraints += 1
        elif "callback" in name or name in _HOST_PRIMS:
            census.host_callbacks[name] = (
                census.host_callbacks.get(name, 0) + 1)
    return census


# ---------------------------------------------------------------------------
# HLO-level census (GSPMD-inserted collectives)
# ---------------------------------------------------------------------------
def _mesh_subset_groups(mesh) -> List[Tuple[str, frozenset]]:
    """[(axis-key, groups)] for every subset of mesh axes, smallest subsets
    first — the lookup table replica_groups are matched against.  ``groups``
    is a frozenset of frozensets of global device ids.  Size-1 axes alias
    larger subsets to smaller ones; first match (minimal subset) wins, so
    the key names only axes that actually participate."""
    import numpy as np

    names = list(mesh.axis_names)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    table: List[Tuple[str, frozenset]] = []
    order = {n: i for i, n in enumerate(names)}
    subsets = itertools.chain.from_iterable(
        itertools.combinations(names, k) for k in range(len(names) + 1))
    for subset in sorted(subsets, key=lambda s: (len(s),
                                                 [order[n] for n in s])):
        rest = [n for n in names if n not in subset]
        perm = [names.index(n) for n in rest] + [names.index(n)
                                                for n in subset]
        group_size = int(np.prod([mesh.shape[n] for n in subset], dtype=int))
        mat = ids.transpose(perm).reshape(-1, group_size)
        groups = frozenset(frozenset(int(x) for x in row) for row in mat)
        key = ",".join(subset) if subset else "none"
        table.append((key, groups))
    return table


def _parse_replica_groups(line: str) -> Optional[frozenset]:
    import numpy as np

    m = _HLO_IOTA_GROUPS_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        v = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            v = v.transpose([int(x) for x in m.group(4).split(",")])
        mat = v.reshape(n_groups, group_size)
        return frozenset(frozenset(int(x) for x in row) for row in mat)
    m = _HLO_LIST_GROUPS_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([\d, ]*)\}", m.group(1)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            groups.append(frozenset(ids))
        return frozenset(groups)
    return None


def _permute_axis_key(line: str, mesh) -> str:
    """Mesh axes along which a collective-permute's source->target pairs
    move data ("mixed" when pairs cross several axes at once)."""
    import numpy as np

    m = _HLO_PAIRS_RE.search(line)
    if not m:
        return "?"
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    coords = {int(ids[idx]): idx for idx in np.ndindex(ids.shape)}
    axes: set = set()
    for pair in re.findall(r"\{(\d+),(\d+)\}", m.group(0)):
        s, t = coords.get(int(pair[0])), coords.get(int(pair[1]))
        if s is None or t is None:
            return "?"
        moved = [mesh.axis_names[i] for i in range(len(s)) if s[i] != t[i]]
        if len(moved) > 1:
            return "mixed"
        axes.update(moved)
    if not axes:
        return "none"
    if len(axes) > 1:
        return "mixed"
    return axes.pop()


def _result_bytes(type_text: str) -> int:
    """Byte size of an HLO result type.  Async -start ops carry a tuple
    ``(operand_shape, result_shape)``; the gathered RESULT is the largest
    element, so the max over elements is the right size either way."""
    best = 0
    for dtype, dims in _HLO_SHAPE_RE.findall(type_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES.get(dtype, 4))
    return best


def _hlo_scan(hlo_text: str, mesh) -> Tuple[Dict[str, Dict[str, int]],
                                            Dict[str, int]]:
    """(per-kind per-axis counts, per-axis max all-gather output bytes)."""
    table = _mesh_subset_groups(mesh)
    census: Dict[str, Dict[str, int]] = {}
    ag_bytes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        if kind == "collective-permute":
            key = _permute_axis_key(line, mesh)
        else:
            groups = _parse_replica_groups(line)
            key = "?"
            if groups is not None:
                for axis_key, axis_groups in table:
                    if groups == axis_groups:
                        key = axis_key
                        break
        per_axis = census.setdefault(kind, {})
        per_axis[key] = per_axis.get(key, 0) + 1
        if kind == "all-gather":
            ag_bytes[key] = max(ag_bytes.get(key, 0),
                                _result_bytes(m.group(1)))
    return census, ag_bytes


def hlo_collective_census(hlo_text: str, mesh) -> Dict[str, Dict[str, int]]:
    """Count collective ops in optimized HLO, keyed by mesh-axis key.

    Ops whose replica groups match no axis subset (should not happen on a
    mesh-built program) land under ``"?"`` so they are visible rather than
    dropped.
    """
    return _hlo_scan(hlo_text, mesh)[0]


def hlo_host_callbacks(hlo_text: str) -> Dict[str, int]:
    """Host-callback custom-calls in optimized HLO (hot-path scan)."""
    out: Dict[str, int] = {}
    for m in _HLO_CALLBACK_RE.finditer(hlo_text):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------
def census_of(fn, *args, mesh=None, include_hlo: bool = True,
              ) -> CollectiveCensus:
    """Census of a (jitted) step function called with ``args`` (concrete
    arrays or ShapeDtypeStructs carrying shardings).

    The jaxpr walk sees the explicit shard_map collectives and
    ``sharding_constraint``s; with ``include_hlo`` (needs ``mesh``) the
    compiled program's GSPMD-inserted collectives are censused too.
    """
    import warnings

    import jax

    closed = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    census = jaxpr_census(closed)
    if include_hlo:
        if mesh is None:
            raise ValueError("include_hlo=True needs the mesh to map "
                             "replica groups back to axis names")
        with warnings.catch_warnings():
            # Abstract (ShapeDtypeStruct) lowering cannot honor buffer
            # donation; the warning is meaningless at analysis time.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        census.hlo_collectives, census.hlo_allgather_max_bytes = _hlo_scan(
            text, mesh)
        for name, n in hlo_host_callbacks(text).items():
            census.host_callbacks[name] = (
                census.host_callbacks.get(name, 0) + n)
    return census


def load_census(path: str) -> CollectiveCensus:
    with open(path) as f:
        return CollectiveCensus.from_json_dict(json.load(f))


def save_census(census: CollectiveCensus, path: str) -> None:
    with open(path, "w") as f:
        json.dump(census.to_json_dict(), f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# Sharding audit
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardingFinding:
    param: str
    issue: str     # "replicated_by_plan" | "plan_ignored"
    detail: str

    def format(self) -> str:
        return f"{self.param}: [{self.issue}] {self.detail}"


def audit_param_shardings(abs_params: Any, plan: Any,
                          min_bytes: int = 1 << 20) -> List[ShardingFinding]:
    """Large parameters whose RESOLVED sharding contradicts the plan.

    Two failure shapes, both silent OOM-or-slowdown generators at 70B:

    * ``replicated_by_plan`` — a parameter >= ``min_bytes`` whose spec names
      no mesh axis while the mesh has a >1 FSDP/TP axis available: every
      device holds a full copy.
    * ``plan_ignored`` — the spec names a >1 axis but the NamedSharding
      built from it is fully replicated anyway (a spec/mesh mismatch GSPMD
      resolved by replication).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = plan.mesh
    # Only axes that can actually shard PARAMETERS count as "available":
    # under the framework's rules that is FSDP (dp_shard, cp) + TP — a pure
    # dp_replicate (DDP) or pp mesh legitimately replicates every param and
    # must not light up the audit.  Generic meshes (tests, external callers)
    # whose axis names overlap none of the known ones fall back to all axes.
    from automodel_tpu.distributed.mesh import AXIS_TP, FSDP_AXES

    mesh_shape = dict(mesh.shape)
    param_axes = (set(FSDP_AXES) | {AXIS_TP}) & set(mesh_shape)
    if not param_axes:
        param_axes = set(mesh_shape)
    sharded_axes_available = any(mesh_shape[a] > 1 for a in param_axes)
    leaves_p, _ = jax.tree_util.tree_flatten_with_path(abs_params)
    specs = jax.tree_util.tree_leaves(
        plan.param_specs, is_leaf=lambda x: isinstance(x, P))
    shardings = jax.tree_util.tree_leaves(plan.param_sharding)
    findings: List[ShardingFinding] = []
    for (path, leaf), spec, sharding in zip(leaves_p, specs, shardings):
        nbytes = _aval_bytes(leaf)
        if nbytes < min_bytes:
            continue
        name = jax.tree_util.keystr(path)
        spec_axes = [a for part in spec if part
                     for a in ((part,) if isinstance(part, str) else part)]
        if not spec_axes:
            if sharded_axes_available:
                findings.append(ShardingFinding(
                    name, "replicated_by_plan",
                    f"{nbytes} bytes with empty PartitionSpec on a "
                    f"multi-device mesh {dict(mesh.shape)}"))
            continue
        live = [a for a in spec_axes if dict(mesh.shape).get(a, 1) > 1]
        if live and sharding.is_fully_replicated:
            findings.append(ShardingFinding(
                name, "plan_ignored",
                f"spec {spec} names live axes {live} but the resolved "
                "sharding is fully replicated"))
    return findings


# ---------------------------------------------------------------------------
# Recompile guard
# ---------------------------------------------------------------------------
def compile_cache_size(fn) -> Optional[int]:
    """Number of compiled entries behind a ``jax.jit`` wrapper, or None when
    the JAX version does not expose it."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def assert_compiles_once(fn, label: str = "step") -> None:
    """Assert ``fn`` holds exactly ONE compiled entry — i.e. every call
    since its first hit the cache.  Shape/weak-type/layout churn in a hot
    loop shows up here as a second entry, statically, before it costs real
    TPU compile minutes."""
    n = compile_cache_size(fn)
    if n is None:
        return  # cache introspection unavailable on this JAX; not a failure
    if n != 1:
        raise AssertionError(
            f"{label}: expected exactly 1 compiled entry after warmup, "
            f"found {n} — the step function is being retraced "
            "(shape, dtype/weak-type, or static-arg cache-key churn)")
