"""Unified Pallas kernel substrate (``ops/kernel_lib``): registry fallback
chains, block-size autotune round trip (cold sweep -> persisted winners ->
warm cache hit; corrupt cache degrades — incl. the fault drill), the
``kernels.autotune`` config knob, and the SHARED interpret-mode parity
harness that holds every registered kernel to its XLA reference on one
case matrix (the five per-kernel copies of that scaffolding, unified).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

import automodel_tpu.ops.gmm_kernel as gmm_mod
import automodel_tpu.ops.linear_ce_kernel as lck
from automodel_tpu.ops.kernel_lib import autotune, parity, registry, tiling
from automodel_tpu.utils.fault_injection import configure_faults, reset_faults


@pytest.fixture(autouse=True)
def _fresh_autotuner():
    """Every test starts from the process default (mode off) and leaves no
    active cache behind."""
    yield
    autotune.configure_autotune("off")


# ---------------------------------------------------------------------------
# Registry: chains, probes, resolution
# ---------------------------------------------------------------------------
def test_default_chains_are_registered():
    assert registry.fallback_chain("attention.ring") == [
        "attention.ring", "attention.splash", "attention.flash",
        "attention.sdpa"]
    assert registry.fallback_chain("gmm.pallas") == [
        "gmm.pallas", "gmm.xla_blocked", "gmm.ragged"]
    assert registry.fallback_chain("linear_ce.pallas") == [
        "linear_ce.pallas", "linear_ce.chunked"]
    assert registry.fallback_chain("qdot.pallas") == [
        "qdot.pallas", "qdot.xla"]
    assert registry.fallback_chain("gmm_quant.pallas") == [
        "gmm_quant.pallas", "gmm_quant.xla_blocked", "gmm_quant.dense"]


def test_resolve_walks_probes_in_chain_order():
    calls = []

    def probe(accept):
        def p(request):
            calls.append(accept)
            return accept
        return p

    try:
        registry.register_kernel("_t.a", probe=probe(False), impl=lambda r: "a",
                                 fallback="_t.b")
        registry.register_kernel("_t.b", probe=probe(False), impl=lambda r: "b",
                                 fallback="_t.c")
        registry.register_kernel("_t.c", probe=probe(True), impl=lambda r: "c")
        spec = registry.resolve("_t.a", {})
        assert spec.name == "_t.c" and calls == [False, False, True]
        with pytest.raises(RuntimeError, match="no kernel"):
            registry.register_kernel("_t.c", probe=probe(False),
                                     impl=lambda r: "c")
            registry.resolve("_t.a", {})
    finally:
        for name in ("_t.a", "_t.b", "_t.c"):
            registry._REGISTRY.pop(name, None)


def test_cpu_attention_request_anchors_on_sdpa():
    # the CPU test reality: splash/flash probes decline, SDPA answers
    request = {"kind": "attention", "q_seq": 256, "kv_seq": 256,
               "head_dim": 64, "num_q_heads": 4, "num_kv_heads": 2,
               "dtype": "float32", "causal": True, "soft_cap": False,
               "window": False, "traced_window": False, "cp_active": False,
               "mesh": None, "cp_layout": None}
    before = registry.resolved_rungs().get("attention.sdpa", 0)
    assert registry.resolve("attention.ring", request).name == "attention.sdpa"
    # the resolution log names the winner (what chip_smoke.py asserts on)
    assert registry.resolved_rungs()["attention.sdpa"] == before + 1


def test_cp_active_resolves_to_ring_unconditionally():
    request = {"cp_active": True, "soft_cap": True, "traced_window": True,
               "q_seq": 64, "kv_seq": 64, "head_dim": 8}
    assert registry.resolve("attention.ring", request).name == "attention.ring"


def test_kernel_module_import_failure_raises(monkeypatch):
    """A kernel module that cannot import is a bug on the one installation
    there is — never a stubbed rung that lets the XLA rung run instead."""
    monkeypatch.setattr(registry, "_defaults_loaded", False)
    monkeypatch.setattr(registry, "_DEFAULT_KERNEL_MODULES",
                        (("automodel_tpu.ops._no_such_kernel", "_t.x"),))
    with pytest.raises(ImportError):
        registry.ensure_default_kernels()
    # not latched: the next caller sees the failure too
    assert registry._defaults_loaded is False
    # importable, but the rung it owes never registered
    monkeypatch.setattr(registry, "_DEFAULT_KERNEL_MODULES",
                        (("automodel_tpu.ops.norms", "_t.x"),))
    with pytest.raises(RuntimeError, match="registered no"):
        registry.ensure_default_kernels()


def test_probe_lets_backend_failure_out(monkeypatch):
    """A backend that fails to initialise must not read as "no kernel"."""
    def boom():
        raise RuntimeError("libtpu failed to initialise")

    monkeypatch.setattr(jax, "default_backend", boom)
    for chain, request in (
            ("gmm.pallas", {"m": 256, "k": 128, "n": 128}),
            ("qdot.pallas", {"m": 256, "k": 128, "n": 128}),
            ("linear_ce.pallas", {"t": 256, "h": 128, "v": 256}),
            ("attention.paged_decode", {"q_seq": 1, "head_dim": 128}),
            ("attention.splash", {"q_seq": 256, "kv_seq": 256,
                                  "head_dim": 64})):
        with pytest.raises(RuntimeError, match="libtpu"):
            registry.resolve(chain, request)


# ---------------------------------------------------------------------------
# Tiling helpers
# ---------------------------------------------------------------------------
def test_pick_block_largest_divisor():
    assert tiling.pick_block(16384) == 1024
    assert tiling.pick_block(1536) == 512
    assert tiling.pick_block(384) == 128
    assert tiling.pick_block(200) == 200          # nothing divides
    assert tiling.pick_block(512, (512, 256, 128)) == 512


def test_fit_tile_pair_respects_budget_and_floor():
    # generous budget -> biggest pair; tiny budget -> the floor
    big = tiling.fit_tile_pair(4096, (1024, 512), (512, 128),
                               lambda tm, tv: tm * tv)
    assert big == (1024, 512)
    floor = tiling.fit_tile_pair(4096, (1024, 512), (512, 128),
                                 lambda tm, tv: 10 ** 12)
    assert floor == (128, 128)
    # row candidates above the (128-padded) row count are skipped
    small_rows = tiling.fit_tile_pair(100, (1024, 512, 128), (128,),
                                      lambda tm, tv: tm * tv)
    assert small_rows == (128, 128)


def test_combine_online_softmax_matches_two_pass():
    rng = np.random.default_rng(0)
    B, S, Hk, G, D = 1, 8, 2, 2, 4
    logits = rng.normal(size=(B, Hk, G, S, 16)).astype(np.float32)
    v = rng.normal(size=(16, D)).astype(np.float32)
    # two-pass oracle over the full row
    p = np.exp(logits - logits.max(-1, keepdims=True))
    ref = np.einsum("bhgqk,kd->bqhgd", p / p.sum(-1, keepdims=True), v)
    # online: fold the two halves with combine_online_softmax
    state = None
    for half, vh in ((logits[..., :8], v[:8]), (logits[..., 8:], v[8:])):
        m_b = half.max(-1)
        pb = np.exp(half - m_b[..., None])
        s_b = pb.sum(-1)
        o_b = np.einsum("bhgqk,kd->bqhgd", pb, vh)
        if state is None:
            state = (jnp.asarray(o_b), jnp.asarray(m_b), jnp.asarray(s_b))
        else:
            state = tiling.combine_online_softmax(
                state[0], state[1], state[2], jnp.asarray(o_b),
                jnp.asarray(m_b), jnp.asarray(s_b))
    acc, m, s = state
    out = np.asarray(acc) / np.asarray(tiling.rowscale(s))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Autotune: round trip, degradation, knob
# ---------------------------------------------------------------------------
def _lce_request():
    return [("linear_ce", {"t": 256, "h": 128, "v": 256,
                           "dtype": "float32"})]


def test_autotune_off_mode_returns_defaults_without_cache_io(tmp_path):
    tuner = autotune.configure_autotune("off", str(tmp_path / "c.json"))
    got = autotune.lookup("linear_ce", {"t": 256}, (512, 128))
    assert got == (512, 128)
    assert not os.path.exists(tmp_path / "c.json")
    assert tuner.report()["cache_hit"] is False


def test_autotune_cold_sweep_persists_then_warm_hits(tmp_path, monkeypatch):
    monkeypatch.setattr(lck, "_INTERPRET", True)
    path = str(tmp_path / "cache.json")

    tuner = autotune.configure_autotune("on", path)
    report = tuner.sweep_requests(_lce_request())
    assert report["swept"] == 1 and report["errors"] == 0
    data = json.load(open(path))
    assert data["version"] == autotune.CACHE_VERSION
    (key, entry), = data["entries"].items()
    assert key.startswith("linear_ce|") and len(entry["block"]) == 2

    # warm process: no sweep, lookups served from the table, hit reported
    tuner2 = autotune.configure_autotune("on", path)
    report2 = tuner2.sweep_requests(_lce_request())
    assert report2["swept"] == 0 and report2["cached"] == 1
    tiles = lck._tiles(256, 128, 256)
    assert list(tiles) == entry["block"]
    assert autotune.autotune_report()["cache_hit"] is True

    # force mode re-sweeps even on a warm cache
    tuner3 = autotune.configure_autotune("force", path)
    report3 = tuner3.sweep_requests(_lce_request())
    assert report3["swept"] == 1


def test_autotune_winner_rejected_when_it_does_not_fit(tmp_path):
    tuner = autotune.configure_autotune("on", str(tmp_path / "c.json"))
    key = autotune.make_key("linear_ce",
                            {"t": 256, "h": 128, "v": 256})
    tuner.table[key] = {"block": [4096, 4096]}      # absurd winner
    tiles = lck._tiles(256, 128, 256)               # validate() rejects it
    assert tiles == (256, 512)                      # the hand-tuned default


def test_autotune_corrupt_cache_degrades_to_defaults(tmp_path, caplog):
    path = tmp_path / "cache.json"
    path.write_text("{definitely not json")
    with caplog.at_level("WARNING"):
        tuner = autotune.configure_autotune("on", str(path))
    assert not tuner.loaded_from_cache
    assert "falling back to the hand-tuned" in caplog.text
    assert lck._tiles(256, 128, 256) == (256, 512)


@pytest.mark.fault
def test_autotune_cache_fault_point_never_fails_setup(tmp_path, caplog):
    """kernel_autotune_cache drill: an unreadable cache (injected at the
    read) must warn once and leave the run on hand-tuned defaults — setup
    survives."""
    path = tmp_path / "cache.json"
    path.write_text(json.dumps({"version": autotune.CACHE_VERSION,
                                "entries": {}}))
    configure_faults("kernel_autotune_cache:1")
    try:
        with caplog.at_level("WARNING"):
            tuner = autotune.configure_autotune("on", str(path))
        assert not tuner.loaded_from_cache       # the read was killed
        assert "falling back to the hand-tuned" in caplog.text
        assert autotune.lookup("linear_ce", {"t": 64}, (128, 128)) == (128, 128)
        # second construction (fault spent) loads it fine
        tuner2 = autotune.configure_autotune("on", str(path))
        assert tuner2.loaded_from_cache
    finally:
        reset_faults()


def test_kernels_autotune_knob_enum_validated(tmp_path):
    from automodel_tpu.config.loader import load_yaml_config

    bad = tmp_path / "bad.yaml"
    bad.write_text("kernels:\n  autotune: banana\n")
    with pytest.raises(ValueError, match="kernels.autotune"):
        load_yaml_config(str(bad))
    # YAML 1.1 bool literals are the mode names' natural spellings
    for spelling, _mode in (("on", "on"), ("off", "off"),
                            ("force", "force"), ("null", None)):
        ok = tmp_path / f"ok_{spelling}.yaml"
        ok.write_text(f"kernels:\n  autotune: {spelling}\n")
        load_yaml_config(str(ok))
    assert autotune.resolve_autotune_mode(True) == "on"
    assert autotune.resolve_autotune_mode(False) == "off"
    assert autotune.resolve_autotune_mode(None) == "off"


def test_recipe_hook_configures_and_sweeps(tmp_path, monkeypatch):
    """BaseRecipe._setup_kernel_autotune: mode+cache from the kernels:
    section, sweep of the run's derivable shapes before any trace."""
    monkeypatch.setattr(lck, "_INTERPRET", True)
    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.base_recipe import BaseRecipe

    class _Cfg:
        hidden_size = 128
        vocab_size = 256

    class _Model:
        config = _Cfg()
        compute_dtype = "float32"

    path = str(tmp_path / "cache.json")
    rec = BaseRecipe()
    rec._setup_kernel_autotune(
        ConfigNode({"kernels": {"autotune": "on", "autotune_cache": path}}),
        model=_Model(), seq_len=256, local_batch=1)
    assert os.path.exists(path)
    report = autotune.autotune_report()
    assert report["mode"] == "on"
    assert report["sweep"]["swept"] >= 1
    # mode off (the default): nothing configured, no file surprises
    rec._setup_kernel_autotune(ConfigNode({}), model=_Model(), seq_len=256)
    assert autotune.active_autotuner().mode == "off"


def test_training_sweep_requests_cover_the_run():
    class _Cfg:
        hidden_size = 256
        num_attention_heads = 2
        num_key_value_heads = 1
        head_dim = 128
        vocab_size = 512
        num_experts = 4
        moe_intermediate_size = 256
        num_experts_per_tok = 2

    class _Model:
        config = _Cfg()

    reqs = autotune.training_sweep_requests(_Model(), seq_len=512,
                                            local_batch=2)
    kernels = [k for k, _ in reqs]
    # the fused backward's own triple sweeps under its own key (splash_bwd)
    assert kernels == ["splash", "splash_bwd", "linear_ce", "gmm", "gmm"]
    # gmm plans the sorted dispatch's PADDED buffer rows (N + E*block): a
    # bare N would bucket one power of two short whenever N is a power of 2
    gmm_req = dict(reqs)["gmm"]
    assert gmm_req["m"] == 2 * 512 * 2 + 4 * 128
    assert dict(reqs)["splash_bwd"] == dict(reqs)["splash"]
    # cp>1: dispatch resolves to the ring unconditionally, so the plan
    # sweeps the ring's PER-SHARD inner-tile key instead of splash
    cp_reqs = autotune.training_sweep_requests(_Model(), seq_len=512,
                                               local_batch=2, cp=2)
    cp_kernels = [k for k, _ in cp_reqs]
    assert cp_kernels == ["ring", "linear_ce", "gmm", "gmm"]
    assert cp_reqs[0][1]["q_seq"] == 256
    # no seq len (unpacked-variable) -> nothing to pre-sweep
    assert autotune.training_sweep_requests(_Model(), seq_len=None) == []
    # unaligned seq -> nothing (kernels would decline those shapes anyway)
    assert autotune.training_sweep_requests(_Model(), seq_len=100) == []


def test_training_sweep_requests_plan_qdot_under_quant():
    """fp8.enabled models plan the quantized-matmul key (their dense GEMMs
    route through qdot); quant off plans none."""
    from automodel_tpu.ops.quant import QuantConfig

    class _Cfg:
        hidden_size = 256
        intermediate_size = 512
        num_attention_heads = 2
        num_key_value_heads = 1
        head_dim = 128
        vocab_size = 512

    class _Model:
        config = _Cfg()

    assert all(k != "qdot" for k, _ in
               autotune.training_sweep_requests(_Model(), seq_len=512))
    m = _Model()
    m.quant = QuantConfig(enabled=True, dtype="int8",
                          recipe_name="rowwise")
    reqs = autotune.training_sweep_requests(m, seq_len=512, local_batch=2)
    shapes = {(r["m"], r["k"], r["n"]) for k, r in reqs if k == "qdot"}
    # ALL THREE GEMMs of a projection get a key: fwd (rows, K, N),
    # dgrad (rows, N, K), wgrad (K, rows, N) — e.g. the gate/up [256, 512]
    rows = 2 * 512
    assert {(rows, 256, 512), (rows, 512, 256), (256, rows, 512)} <= shapes
    # ... and the down / o_proj / kv projections are covered too
    assert {(512, rows, 256), (rows, 256, 256), (256, rows, 256),
            (rows, 256, 128), (256, rows, 128)} <= shapes
    # keys are deduplicated by (m-bucket, k, n)
    keyed = [(autotune.shape_bucket(r["m"]), r["k"], r["n"])
             for k, r in reqs if k == "qdot"]
    assert len(keyed) == len(set(keyed))
    assert all(r["quant_dtype"] == "int8" and r["recipe"] == "rowwise"
               for k, r in reqs if k == "qdot")


def test_qdot_sweep_candidates_are_runtime_legal():
    """A tn that does not divide n would run an EMPTY grid under forced()
    (computes nothing, wins every timing) and be validate-rejected on
    every real call — the candidate generator must filter it like the
    budget (PR-7 persisted-then-rejected hardening class)."""
    import automodel_tpu.ops.qdot_kernel as qk

    cands = qk._sweep_candidates({"m": 1024, "k": 256, "n": 256})
    assert cands
    assert all(256 % tn == 0 for _, tn in cands)
    assert (512, 512) not in cands
    # and the budget filter still applies at large k
    big = qk._sweep_candidates({"m": 4096, "k": 8192, "n": 512})
    assert big and all(
        qk._tile_bytes(tm, tn, 8192) <= 24 * 1024 * 1024
        for tm, tn in big)


def test_sweep_candidates_respect_the_runtime_budget():
    """A candidate the runtime lookup would validate-reject (over the VMEM
    tile budget) must never be timed/persisted — the sweep's winner has to
    be applicable."""
    import automodel_tpu.ops.gmm_kernel as gk

    # k=8192: (512, 512) busts the 24 MB budget and must be filtered
    cands = gk._sweep_candidates({"m": 4096, "k": 8192, "n": 512})
    assert cands and (512, 512) not in cands
    lce = lck._sweep_candidates({"t": 4096, "h": 8192, "v": 1024})
    assert lce and all(tm * 8192 * 4 < 24 * 1024 * 1024 for tm, _ in lce)


# ---------------------------------------------------------------------------
# Shared interpret-mode parity harness: every registered kernel vs its
# XLA reference on ONE case matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", parity.attention_cases(),
                         ids=lambda c: c["name"])
@pytest.mark.parametrize("spec", ["attention.splash", "attention.sdpa"])
def test_attention_kernel_parity(spec, case):
    parity.run_attention_parity(spec, case)


@pytest.mark.parametrize("case", [c for c in parity.attention_cases()
                                  if c["name"] in ("causal_gqa",
                                                   "packed_segments",
                                                   "soft_cap")],
                         ids=lambda c: c["name"])
def test_ring_kernel_parity_on_cp_mesh(case):
    from automodel_tpu.distributed.mesh import MeshManager

    mm = MeshManager(dp_size=2, cp_size=2, tp_size=2)
    parity.run_attention_parity("attention.ring", case, mesh=mm.mesh, B=2)


@pytest.mark.parametrize("case", parity.linear_ce_cases(),
                         ids=lambda c: c["name"])
@pytest.mark.parametrize("spec", ["linear_ce.pallas", "linear_ce.chunked"])
def test_linear_ce_kernel_parity(spec, case):
    parity.run_linear_ce_parity(spec, case)


@pytest.mark.parametrize("case", parity.gmm_cases(), ids=lambda c: c["name"])
@pytest.mark.parametrize("spec", ["gmm.pallas", "gmm.xla_blocked",
                                  "gmm.ragged"])
def test_gmm_kernel_parity(spec, case):
    parity.run_gmm_parity(spec, case)


@pytest.mark.parametrize("case", parity.moe_decode_cases(),
                         ids=lambda c: c["name"])
@pytest.mark.parametrize("spec", ["moe_decode.pallas", "moe_decode.loop"])
def test_moe_decode_parity(spec, case):
    """The decode step's routed experts, kernel (interpret mode) and loop,
    against every expert on every token in float32: relu and silu, experts
    nobody chose, all chosen, one token, padded rows and experts held
    elsewhere (the sentinel), 48 / 64 / 21 rows, a traced layer of a stack
    of three, both dtypes; ``tokens_per_expert`` equal to the oracle's
    count in both, so equal to each other's."""
    parity.run_moe_decode_parity(spec, case)


def test_moe_decode_probe_reads_the_call_not_a_knob(monkeypatch):
    """Which form runs is decided by what the call shows: decode-width
    rows, lane-aligned widths, no quantized compute, the stacks on one
    device, a TPU (or interpret mode)."""
    import automodel_tpu.ops.moe_decode_kernel as mk

    ok = {"rows": 48, "hidden": 2560, "inter": 768, "experts": 64,
          "quantized": False, "devices": 1}
    resolve = lambda **kw: registry.resolve(
        "moe_decode.pallas", dict(ok, **kw)).name
    assert registry.fallback_chain("moe_decode.pallas") == [
        "moe_decode.pallas", "moe_decode.loop"]
    assert resolve() == "moe_decode.loop"           # a CPU
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    assert resolve() == "moe_decode.pallas"
    assert resolve(rows=64, hidden=7168, inter=2048) == "moe_decode.pallas"
    assert resolve(rows=mk.MAX_ROWS + 1) == "moe_decode.loop"   # mixed step
    assert resolve(rows=48 * 64) == "moe_decode.loop"
    assert resolve(hidden=2560 + 64) == "moe_decode.loop"
    assert resolve(inter=96) == "moe_decode.loop"
    assert resolve(quantized=True) == "moe_decode.loop"
    assert resolve(devices=4) == "moe_decode.loop"


def test_moe_decode_sees_the_devices_its_stacks_span():
    """A stack that is an array says where it lies; a traced one lies on the
    mesh of the sharding context its forward is built under."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from automodel_tpu.distributed.mesh import MeshManager
    from automodel_tpu.distributed.shardings import sharding_context
    from automodel_tpu.ops import moe

    mesh = MeshManager(dp_size=4, tp_size=2).mesh
    stack = jnp.zeros((1, 8, 128, 128), jnp.bfloat16)
    spread = jax.device_put(stack, NamedSharding(
        mesh, P(None, tuple(mesh.axis_names))))
    assert moe._devices_spanned(stack) == 1
    assert moe._devices_spanned(spread) == 8
    seen = []
    trace = jax.jit(lambda w: seen.append(moe._devices_spanned(w)) or w)
    trace(stack)
    with sharding_context(mesh):
        jax.jit(lambda w: seen.append(moe._devices_spanned(w)) or w)(stack)
    assert seen == [1, 8]


def test_every_registered_kernel_has_parity_coverage():
    """New kernels must either carry an XLA reference (and land in the
    harness) or be consciously listed as TPU-only — silent gaps fail."""
    tpu_only = {"attention.flash"}      # upstream kernel: no interpret path
    for name in registry.kernel_names():
        if name.startswith("_t."):
            continue
        spec = registry.get_kernel(name)
        if name in tpu_only:
            continue
        assert name in parity.CPU_EXECUTABLE, (
            f"{name} is neither CPU-executable in the parity harness nor "
            "listed tpu_only")
        assert spec.reference is not None or name == "gmm.ragged", (
            f"{name} has no XLA reference for the parity harness")
