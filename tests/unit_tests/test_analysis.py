"""The parallelism auditor (``analysis/jaxpr_audit.py``) + the golden
collective censuses of the dryrun flagship legs.

The golden tests are the acceptance surface of ISSUE 7: a new collective on
any mesh axis, a dropped ``sharding_constraint``, a host callback in the
step, a full-parameter forward all-gather, or a replicated-param sharding
regression in the dp2xcp2xtp2 / MoE-EP legs fails HERE as a readable census
diff — not as a 0.9x bench three PRs later.  Regenerate goldens after an
intentional parallelism change with ``python tools/lint.py --update-golden``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from automodel_tpu.analysis.jaxpr_audit import (
    CollectiveCensus,
    assert_compiles_once,
    audit_param_shardings,
    census_of,
    compile_cache_size,
    hlo_collective_census,
    jaxpr_census,
    load_census,
)
from automodel_tpu.analysis.legs import (
    LEG_NAMES,
    TINY_AUDIT_MIN_BYTES,
    build_leg,
    golden_path,
)

shard_map = functools.partial(jax.shard_map, check_vma=False)


def _mesh(shape=(2, 2, 2), names=("dp", "cp", "tp")):
    return Mesh(np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape),
                names)


# ---------------------------------------------------------------------------
# Jaxpr walk: collectives found structurally, through nested sub-jaxprs
# ---------------------------------------------------------------------------
def test_census_sees_collectives_inside_shard_map_and_scan():
    mesh = _mesh()

    def local(x):
        def body(c, _):
            return lax.psum(c, "tp"), None

        y, _ = lax.scan(body, x, None, length=3)
        y = lax.ppermute(y, "cp", [(0, 1), (1, 0)])
        return lax.pmax(y, ("dp", "cp"))

    f = shard_map(local, mesh=mesh, in_specs=(P("dp", None),),
                  out_specs=P(None, None))
    closed = jax.make_jaxpr(f)(jnp.ones((4, 8)))
    census = jaxpr_census(closed)
    assert census.collectives["psum"] == {"tp": 1}  # scan body: ONE eqn
    assert census.collectives["ppermute"] == {"cp": 1}
    assert census.collectives["pmax"] == {"dp,cp": 1}
    assert census.count("psum") == 1
    assert census.count("psum", "tp") == 1
    assert census.count("psum", "cp") == 0


def test_census_recurses_into_pjit_and_cond():
    mesh = _mesh()

    def inner(x):
        return shard_map(lambda v: lax.psum(jnp.sum(v), "tp"), mesh=mesh,
                         in_specs=(P("tp"),), out_specs=P())(x)

    def f(x, flag):
        y = jax.jit(inner)(x)
        return lax.cond(flag, lambda v: v + 1.0, lambda v: inner(x) + v, y)

    census = jaxpr_census(jax.make_jaxpr(f)(jnp.ones((8,)), True))
    # one psum under the pjit, one under the False cond branch
    assert census.count("psum", "tp") == 2


def test_census_counts_sharding_constraints_and_allgather_bytes():
    mesh = _mesh()

    def local(w):
        return lax.all_gather(w, "dp", axis=0, tiled=True)

    def f(x, w):
        x = lax.with_sharding_constraint(x, NamedSharding(mesh, P("dp")))
        wf = shard_map(local, mesh=mesh, in_specs=(P("dp", None),),
                       out_specs=P(None, None))(w)
        return x.sum() + wf.sum()

    census = jaxpr_census(jax.make_jaxpr(f)(
        jnp.ones((8,)), jnp.ones((8, 4), jnp.float32)))
    assert census.sharding_constraints == 1
    assert census.collectives["all_gather"] == {"dp": 1}
    # gathered output is the FULL [8, 4] f32 tensor
    assert census.allgather_max_bytes == {"dp": 8 * 4 * 4}


def test_census_flags_host_callbacks():
    def f(x):
        jax.debug.print("x={}", x)  # lowers to a debug_callback eqn
        return x + 1

    census = jaxpr_census(jax.make_jaxpr(f)(jnp.float32(1.0)))
    assert sum(census.host_callbacks.values()) == 1
    clean = jaxpr_census(jax.make_jaxpr(lambda x: x + 1)(jnp.float32(1.0)))
    assert clean.host_callbacks == {}


# ---------------------------------------------------------------------------
# HLO census: GSPMD-inserted collectives mapped back to mesh axes
# ---------------------------------------------------------------------------
def test_hlo_census_maps_replica_groups_to_mesh_axes():
    mesh = _mesh()
    wsh = NamedSharding(mesh, P(("dp", "cp"), None))  # FSDP-ish weight

    def f(x, w):
        y = x @ w  # GSPMD must all-gather the sharded weight
        return lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None, "tp")))

    jf = jax.jit(f, in_shardings=(NamedSharding(mesh, P()), wsh))
    txt = jf.lower(jnp.ones((8, 16)), jnp.ones((16, 16))).compile().as_text()
    census = hlo_collective_census(txt, mesh)
    gathers = census.get("all-gather", {})
    assert gathers, f"expected GSPMD all-gathers, census={census}"
    # every op's replica groups resolved to a real axis subset, nothing "?"
    for kind, per_axis in census.items():
        assert "?" not in per_axis, (kind, per_axis)
    assert any("dp" in k or "cp" in k for k in gathers)
    # the gathered weight's OUTPUT size is measured (f32[16,16] = 1 KiB):
    # the direct full-param-forward-gather detector
    from automodel_tpu.analysis.jaxpr_audit import _hlo_scan

    _, ag_bytes = _hlo_scan(txt, mesh)
    assert max(ag_bytes.values()) >= 16 * 16 * 4


def test_hlo_census_counts_async_collectives():
    """XLA:TPU emits -start/-done async pairs with TUPLE result types; the
    census must count the -start (bytes = the gathered RESULT element) and
    skip the -done (no double counting)."""
    mesh = _mesh()
    txt = "\n".join([
        "  %ags = (bf16[16,64]{1,0}, bf16[64,64]{1,0}) all-gather-start("
        "bf16[16,64]{1,0} %p), replica_groups={{0,2},{1,3},{4,6},{5,7}},"
        " dimensions={0}",
        "  %agd = bf16[64,64]{1,0} all-gather-done((bf16[16,64]{1,0},"
        " bf16[64,64]{1,0}) %ags)",
        "  %ar = f32[8]{0} all-reduce-start(f32[8]{0} %q),"
        " replica_groups={{0,1},{2,3},{4,5},{6,7}}",
    ])
    from automodel_tpu.analysis.jaxpr_audit import _hlo_scan

    census, ag_bytes = _hlo_scan(txt, mesh)
    assert census["all-gather"] == {"cp": 1}   # -start counted, -done not
    assert census["all-reduce"] == {"tp": 1}
    assert ag_bytes == {"cp": 64 * 64 * 2}     # the gathered bf16 RESULT


# ---------------------------------------------------------------------------
# Census diff
# ---------------------------------------------------------------------------
def test_census_diff_reports_structured_mismatches():
    a = CollectiveCensus(collectives={"ppermute": {"cp": 6}},
                         sharding_constraints=4)
    b = CollectiveCensus(collectives={"ppermute": {"cp": 8},
                                      "all_gather": {"dp_shard": 1}},
                         sharding_constraints=3)
    diff = a.diff(b)
    assert any("ppermute" in d and "got 6" in d and "golden 8" in d
               for d in diff)
    assert any("all_gather" in d for d in diff)
    assert any("sharding_constraints" in d for d in diff)
    assert a.diff(a) == []
    # JSON round trip preserves equality
    assert CollectiveCensus.from_json_dict(a.to_json_dict()).diff(a) == []
    # a jaxpr-only census vs an HLO-bearing golden is a PARTIAL comparison
    # and must say so, never silently match
    c = CollectiveCensus(collectives={"ppermute": {"cp": 6}},
                         sharding_constraints=4,
                         hlo_collectives={"all-reduce": {"tp": 1}},
                         hlo_allgather_max_bytes={"tp": 64})
    partial = a.diff(c)
    assert sum("present on one side only" in d for d in partial) == 2


# ---------------------------------------------------------------------------
# Sharding audit
# ---------------------------------------------------------------------------
def _toy_plan(specs):
    from automodel_tpu.distributed.shardings import ParallelPlan

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp_shard", "tp"))
    return ParallelPlan(
        mesh=mesh, rules={}, param_specs=specs,
        param_sharding=jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)),
        batch_sharding=NamedSharding(mesh, P("dp_shard")))


def test_sharding_audit_flags_large_replicated_param():
    specs = {"big": P("dp_shard", None), "oops": P(), "small": P()}
    abs_params = {
        "big": jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
        "oops": jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
        "small": jax.ShapeDtypeStruct((8,), jnp.float32),
    }
    findings = audit_param_shardings(abs_params, _toy_plan(specs),
                                     min_bytes=1 << 20)
    assert [f.issue for f in findings] == ["replicated_by_plan"]
    assert "oops" in findings[0].param


def test_sharding_audit_clean_when_plan_sharded():
    specs = {"big": P("dp_shard", "tp")}
    abs_params = {"big": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)}
    assert audit_param_shardings(abs_params, _toy_plan(specs),
                                 min_bytes=1 << 20) == []


# ---------------------------------------------------------------------------
# Recompile guard
# ---------------------------------------------------------------------------
def test_assert_compiles_once_passes_on_cache_hit_and_catches_churn():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones((4,)))
    f(jnp.ones((4,)))  # cache hit
    if compile_cache_size(f) is None:
        pytest.skip("jit cache introspection unavailable on this JAX")
    assert_compiles_once(f, "toy step")

    f(jnp.ones((8,)))  # shape churn -> second entry
    with pytest.raises(AssertionError, match="retraced"):
        assert_compiles_once(f, "toy step")


# ---------------------------------------------------------------------------
# Golden censuses of the dryrun flagship legs (the acceptance surface)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
@functools.lru_cache(maxsize=None)
def _leg_and_census(name):
    # one build + compile per leg for the whole module: ~20 tests read the
    # five censuses, and each compile is 8-12 s of tier-1's budget
    leg = build_leg(name)
    return leg, leg.census()


@pytest.mark.parametrize("name", LEG_NAMES)
def test_golden_collective_census(name):
    leg, census = _leg_and_census(name)
    diff = census.diff(load_census(golden_path(name)))
    assert not diff, (
        f"collective census of leg {name!r} drifted from the golden "
        f"(tests/data/golden_census/{name}.json):\n  " + "\n  ".join(diff)
        + "\nIf the parallelism change is intentional, regenerate with "
        "`python tools/lint.py --update-golden`.")


@pytest.mark.parametrize("name", LEG_NAMES)
def test_leg_hot_path_is_callback_free(name):
    _, census = _leg_and_census(name)
    assert census.host_callbacks == {}, (
        f"host transfer/callback in the {name} train step: "
        f"{census.host_callbacks}")


@pytest.mark.parametrize("name", LEG_NAMES)
def test_leg_sharding_audit_clean(name):
    leg, _ = _leg_and_census(name)
    findings = audit_param_shardings(leg.abstract_args[0], leg.plan,
                                     min_bytes=TINY_AUDIT_MIN_BYTES)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_zigzag_and_contiguous_legs_have_identical_ring_traffic():
    """The zig-zag layout balances WORK, it must not change the collective
    structure: same ppermute count over cp, same censuses overall."""
    _, contiguous = _leg_and_census("dp2xcp2xtp2_contiguous")
    _, zigzag = _leg_and_census("dp2xcp2xtp2_zigzag")
    assert contiguous.count("ppermute", "cp") > 0
    assert zigzag.diff(contiguous) == []


def test_moe_ep_leg_emits_expert_layout_constraints():
    """The sorted-dispatch EP leg carries the token-buffer/intermediate
    constraints (a dropped ``constrain`` silently replicates the buffers —
    the regression the old stringified-jaxpr pin guarded)."""
    _, census = _leg_and_census("moe_ep")
    assert census.sharding_constraints >= 4


def test_dcn_leg_confines_dense_collectives_to_ici():
    """The hierarchical-DP pin behind the ``dcn2_dp2xtp2`` golden (ISSUE 9):
    gradient sync across slices is a (small) all-reduce keyed to ``dcn_dp``
    alone, while the dense FSDP all-gathers and any all-to-all stay on the
    inner ICI axes — DCN only ever carries the hierarchical reduce."""
    _, census = _leg_and_census("dcn2_dp2xtp2")
    hlo = census.hlo_collectives
    # the cross-slice gradient all-reduce exists, keyed to dcn_dp only
    assert hlo["all-reduce"].get("dcn_dp", 0) > 0
    # the largest all-gather whose groups touch dcn_dp must not exceed the
    # largest ICI gather: dense parameter traffic never crosses DCN
    ag = census.hlo_allgather_max_bytes
    ici_max = max(v for k, v in ag.items() if "dcn_dp" not in k.split(","))
    for key, nbytes in ag.items():
        if "dcn_dp" in key.split(","):
            assert nbytes <= ici_max, (
                f"all-gather over {key} ({nbytes}B) exceeds the largest "
                f"ICI gather ({ici_max}B): a dense collective crossed DCN")
    # expert/token shuffles (all-to-all) must never cross slices
    for key in hlo.get("all-to-all", {}):
        assert "dcn_dp" not in key.split(",")


def test_pp_leg_boundary_permutes_keyed_to_pp_only():
    """The pipeline pin behind the ``pp2xdp2`` golden (ISSUE 13): at the
    jaxpr level the ONLY explicit permutes are the 1F1B stage-boundary
    sends (fwd) and their AD mirrors (bwd), keyed to the ``pp`` axis alone
    — a permute on any other key would mean schedule traffic leaked off the
    documented seam (``train_step._make_pp_shift``)."""
    _, census = _leg_and_census("pp2xdp2")
    perms = census.collectives.get("ppermute", {})
    assert perms, "pipelined step lowered with no stage-boundary ppermute"
    assert set(perms) == {"pp"}, (
        f"stage-boundary permutes keyed off the pp seam: {perms}")
    # and the compiled program carries them as collective-permutes over pp
    assert census.hlo_collectives["collective-permute"].get("pp", 0) > 0


def test_pp_leg_no_slab_scale_gather_over_pp():
    """Nothing bigger than ONE boundary activation buffer may cross the pp
    seam as an all-gather: a parameter/slab-sized gather over pp would mean
    a stage pulled another stage's layers — pipelining structurally broken.
    (XLA legitimately reshards a few boundary-activation-sized tensors over
    pp for the embed-select path; their exact counts are pinned by the
    golden, and this bound keeps them activation-scale forever.)"""
    leg, census = _leg_and_census("pp2xdp2")
    mesh_shape = dict(leg.plan.mesh.shape)
    pp = mesh_shape["pp"]
    # [pp, B_mb, S, H] fp32: the boundary buffer ceiling, derived from the
    # leg's OWN batch geometry so a legs.py/model resize cannot silently
    # loosen (or false-fail) the bound
    from automodel_tpu.analysis.legs import flagship_tiny_model

    _, _, batch = leg.abstract_args
    _, B, S = batch["input_ids"].shape
    k = leg.fns.pp_num_microbatches
    H = flagship_tiny_model().config.hidden_size
    bound = pp * (B // k) * S * H * 4
    for key, nbytes in (census.hlo_allgather_max_bytes or {}).items():
        if "pp" in key.split(","):
            assert nbytes <= bound, (
                f"all-gather over {key} moved {nbytes}B (> boundary buffer "
                f"{bound}B): slab-scale data crossed the pp seam")


def test_pp_leg_compiles_once_and_batch_never_shards_over_pp():
    """The pipelined step must be one XLA program (slot/microbatch counts
    are static), and the batch sharding spec must never name pp — every
    stage sees the full microbatch stream."""
    import jax

    from automodel_tpu.analysis.jaxpr_audit import assert_compiles_once

    leg = build_leg("pp2xdp2")
    params, opt, batch = leg.abstract_args

    def concrete(t):
        return jax.tree.map(
            lambda s: jax.device_put(
                np.zeros(s.shape, s.dtype), s.sharding), t)

    p, o = concrete(params), concrete(opt)
    b = {k: jax.device_put(np.zeros(v.shape, v.dtype), v.sharding)
         for k, v in batch.items()}
    p, o, m = leg.fns.train_step(p, o, b)
    p, o, m = leg.fns.train_step(p, o, b)
    assert_compiles_once(leg.fns.train_step, "pp2xdp2 train_step")
    spec = leg.fns.microbatch_sharding.spec
    flat = [a for part in spec if part
            for a in ((part,) if isinstance(part, str) else part)]
    assert "pp" not in flat, f"batch spec names pp: {spec}"
