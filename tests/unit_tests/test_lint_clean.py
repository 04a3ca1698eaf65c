"""The repo invariant linter (``analysis/lint.py``): per-rule unit tests on
synthetic snippets, and THE tier-1 gate — both pillars run over the whole
package asserting zero unsuppressed findings.

The gate is what turns every rule into a standing invariant: introducing
an unregistered enum knob, a ``time.time()`` inside a jit function, a stray
hot-loop ``device_get``, an undrilled fault point or a raw Pallas
``BlockSpec``/``CompilerParams`` off the kernel substrate anywhere in
``automodel_tpu/``/``tools/`` fails HERE with a rule ID and path:line.
"""

import json
import os
import subprocess
import sys

from automodel_tpu.analysis.lint import (
    Finding,
    lint_paths,
    lint_source,
    parse_suppressions,
)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _lint(src, rel="automodel_tpu/ops/fake.py", select=None):
    return lint_source(src, rel, select=select)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# L002 — unregistered enum-like config domains
# ---------------------------------------------------------------------------
def test_l002_flags_unregistered_enum_domain():
    hits = _lint('FOO_MODES = ("fast", "slow")\n')
    assert _rules(hits) == ["L002"]
    assert "FOO_MODES" in hits[0].message


def test_l002_registered_and_non_enum_constants_clean():
    # CP_LAYOUTS / MOE_DISPATCHES / QUANT_* are registered in
    # loader._enum_fields (the DTYPES/RECIPES suffixes joined the
    # convention with the fp8.dtype / fp8.recipe_name fields)
    assert _lint('CP_LAYOUTS = ("contiguous", "zigzag")\n') == []
    assert _lint('MOE_DISPATCHES = ("sorted", "onehot")\n') == []
    assert _lint('QUANT_DTYPES = ("float8", "int8")\n') == []
    assert _rules(_lint('FOO_DTYPES = ("a", "b")\n')) == ["L002"]
    assert _rules(_lint('BAR_RECIPES = ("a", "b")\n')) == ["L002"]
    # key lists / non-string tuples / short tuples are not enum domains
    assert _lint('_PACKED_KEYS = ("loss", "grad_norm")\n') == []
    assert _lint('FOO_MODES = (1, 2)\n') == []
    assert _lint('FOO_MODES = ("solo",)\n') == []


def test_l002_post_training_suffixes():
    # ALGORITHMS/SOURCES joined the suffix convention with the
    # post_training.algorithm / rl.reward_source fields (PR 15)
    assert _lint('PT_ALGORITHMS = ("grpo", "dpo")\n') == []
    assert _lint('REWARD_SOURCES = ("length_target", "callable")\n') == []
    assert _rules(_lint('FOO_ALGORITHMS = ("a", "b")\n')) == ["L002"]
    assert _rules(_lint('BAR_SOURCES = ("a", "b")\n')) == ["L002"]


# ---------------------------------------------------------------------------
# L003 — nondeterminism / wall-clock under jit
# ---------------------------------------------------------------------------
def test_l003_flags_wallclock_and_nondeterminism_in_jit_scope():
    hits = _lint(
        "import jax, time\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    return x + t\n")
    assert _rules(hits) == ["L003"]
    hits = _lint(
        "import jax\nimport numpy as np\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnums=0)\n"
        "def step(n, x):\n"
        "    return x + np.random.rand(n)\n")
    assert _rules(hits) == ["L003"]


def test_l003_covers_functions_jitted_at_call_sites():
    hits = _lint(
        "import jax, random\n"
        "def step(x):\n"
        "    return x * random.random()\n"
        "step_jit = jax.jit(step, donate_argnums=(0,))\n")
    assert _rules(hits) == ["L003"]


def test_l003_clean_outside_jit_and_for_jax_random():
    assert _lint(
        "import time\n"
        "def host_loop(x):\n"
        "    return time.time()\n") == []
    assert _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(key, x):\n"
        "    return x + jax.random.normal(key, x.shape)\n") == []


# ---------------------------------------------------------------------------
# L004 — host syncs in the hot path
# ---------------------------------------------------------------------------
def test_l004_flags_sync_calls_in_hot_modules():
    src = ("import jax\n"
           "def f(arr, m):\n"
           "    jax.device_get(arr)\n"
           "    arr.block_until_ready()\n"
           "    x = arr.item()\n"
           "    y = float(m['loss'])\n")
    hits = _lint(src, rel="automodel_tpu/training/fake.py")
    assert _rules(hits) == ["L004"] * 4
    # recipes: only the _run_* hot-loop bodies are in scope
    wrapped = ("import jax\n"
               "def _run_train_optim_step(self, arr):\n"
               "    jax.device_get(arr)\n"
               "def setup(self, arr):\n"
               "    jax.device_get(arr)\n")
    hits = _lint(wrapped, rel="automodel_tpu/recipes/llm/fake.py")
    assert [(f.rule, f.line) for f in hits] == [("L004", 3)]


def test_l004_not_applied_outside_hot_modules():
    src = "import jax\ndef f(arr):\n    return jax.device_get(arr)\n"
    assert _lint(src, rel="automodel_tpu/checkpoint/fake.py") == []
    assert _lint(src, rel="tools/fake.py") == []


def test_l004_suppression_requires_justification():
    base = ("import jax\n"
            "def f(arr):\n"
            "    jax.device_get(arr)  # lint: disable=L004{}\n")
    justified = base.format(" (once-per-epoch fetch)")
    bare = base.format("")
    assert _lint(justified, rel="automodel_tpu/training/fake.py") == []
    assert _rules(_lint(bare, rel="automodel_tpu/training/fake.py")) == [
        "L004"]


def test_suppression_parser():
    sup = parse_suppressions(
        "x = 1\n"
        "y  # lint: disable=L003,L004 (reason here)\n"
        "z  # lint: disable=L003\n")
    assert sup == {2: {"L003", "L004"}}


# ---------------------------------------------------------------------------
# L005 — fault-point registry + drill coverage
# ---------------------------------------------------------------------------
def test_l005_flags_unregistered_fault_point():
    hits = _lint(
        "from automodel_tpu.utils.fault_injection import fault_point\n"
        "def save():\n"
        "    fault_point('ckpt_totally_new_point')\n")
    assert _rules(hits) == ["L005"]
    assert "not registered" in hits[0].message


def test_l005_registered_and_drilled_point_clean():
    assert _lint(
        "from automodel_tpu.utils.fault_injection import fault_point\n"
        "def save():\n"
        "    fault_point('ckpt_pre_commit')\n") == []


def test_l005_registry_matches_docstring_points():
    from automodel_tpu.utils.fault_injection import KNOWN_FAULT_POINTS

    assert "ckpt_pre_save" in KNOWN_FAULT_POINTS
    assert "input_producer" in KNOWN_FAULT_POINTS


# ---------------------------------------------------------------------------
# L006 — Pallas block/grid/compiler-params construction off the substrate
# ---------------------------------------------------------------------------
def test_l006_flags_raw_blockspec_and_gridspec_construction():
    hits = _lint(
        "from jax.experimental import pallas as pl\n"
        "spec = pl.BlockSpec((128, 128), lambda i, j: (i, j))\n")
    assert _rules(hits) == ["L006"]
    assert "kernel_lib" in hits[0].message
    hits = _lint(
        "from jax.experimental.pallas import tpu as pltpu\n"
        "g = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=(1,))\n")
    assert _rules(hits) == ["L006"]
    # importing the class out of pallas is flagged at the import
    hits = _lint("from jax.experimental.pallas import BlockSpec\n")
    assert _rules(hits) == ["L006"]
    # the natural long-form alias is covered too
    hits = _lint(
        "import jax.experimental.pallas as pallas\n"
        "spec = pallas.BlockSpec((128, 128), lambda i: (i,))\n")
    assert _rules(hits) == ["L006"]


def test_l006_flags_raw_compiler_params_construction():
    hits = _lint(
        "from jax.experimental.pallas import tpu as pltpu\n"
        "p = pltpu.CompilerParams(dimension_semantics=())\n")
    assert _rules(hits) == ["L006"]
    assert "tiling.py" in hits[0].message
    # an unrelated object's CompilerParams is not Pallas construction
    assert _lint("import mosaic\np = mosaic.CompilerParams()\n") == []


def test_l006_exempts_the_substrate_and_accepts_suppressions():
    src = ("from jax.experimental import pallas as pl\n"
           "spec = pl.BlockSpec((8, 8), lambda i: (i,))\n")
    assert _lint(src, rel="automodel_tpu/ops/kernel_lib/tiling.py") == []
    suppressed = ("from jax.experimental import pallas as pl\n"
                  "spec = pl.BlockSpec((8, 8), lambda i: (i,))"
                  "  # lint: disable=L006 (one-off debug kernel)\n")
    assert _lint(suppressed) == []
    # routing through the substrate is the sanctioned spelling
    assert _lint(
        "from automodel_tpu.ops.kernel_lib import tiling\n"
        "spec = tiling.vmem_block_spec((8, 8), lambda i: (i,))\n"
        "cp = tiling.compiler_params()\n") == []


# ---------------------------------------------------------------------------
# Rule selection + output formats
# ---------------------------------------------------------------------------
def test_select_restricts_rules():
    src = ("import jax, time\n"
           "FOO_MODES = ('a', 'b')\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return x + time.time()\n")
    assert _rules(_lint(src)) == ["L002", "L003"]
    assert _rules(_lint(src, select=["L003"])) == ["L003"]


def test_finding_format_carries_rule_id_and_location():
    f = Finding("L004", "automodel_tpu/ops/x.py", 12, "msg")
    assert f.format() == "automodel_tpu/ops/x.py:12: L004 msg"


# ---------------------------------------------------------------------------
# THE tier-1 gate: the whole tree is lint-clean
# ---------------------------------------------------------------------------
def test_repo_is_lint_clean():
    paths = [os.path.join(_REPO, p)
             for p in ("automodel_tpu", "tools", "__graft_entry__.py")]
    findings = lint_paths(paths, repo_root=_REPO)
    assert findings == [], (
        "unsuppressed lint findings (fix, or suppress with "
        "`# lint: disable=L00x (reason)` where the behavior is "
        "intentional):\n" + "\n".join(f.format() for f in findings))


def test_cli_exits_zero_and_emits_json(tmp_path):
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "lint.py"),
         "--format", "json"],
        capture_output=True, text=True, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout) == []


def test_cli_fails_on_a_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\nx = jax.lax.ppermute(1, 'cp', [(0, 1)])\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "lint.py"), str(bad)],
        capture_output=True, text=True, cwd=_REPO)
    assert proc.returncode == 1
    assert "L007" in proc.stdout and "bad.py:2" in proc.stdout


# ---------------------------------------------------------------------------
# Fault-coverage gate: every KNOWN_FAULT_POINTS entry wired AND drilled
# (tools/fault_coverage.py — the operator-readable generalization of L005)
# ---------------------------------------------------------------------------
def test_fault_coverage_report_is_gap_free():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from fault_coverage import build_report
    finally:
        sys.path.pop(0)
    report = build_report(_REPO)
    assert report["ok"], (
        f"fault-injection coverage gaps — undrilled: {report['undrilled']}, "
        f"unwired: {report['unwired']}, unregistered call sites: "
        f"{report['unregistered_call_sites']} (run tools/fault_coverage.py "
        "for the full report; every point needs a pytest.mark.fault drill)")
    # the report is complete: one row per registered point, each naming
    # its call sites and at least one drilling test module
    assert report["registered"] == len(report["points"]) >= 19
    for row in report["points"]:
        assert row["call_sites"] and row["drilled_by"], row


def test_fault_coverage_cli_and_gap_detection(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "fault_coverage.py"),
         "--format", "json"],
        capture_output=True, text=True, cwd=_REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["ok"] is True
    # a synthetic repo with a registered-but-undrilled point must fail
    pkg = tmp_path / "automodel_tpu" / "utils"
    pkg.mkdir(parents=True)
    (pkg / "fault_injection.py").write_text(
        "KNOWN_FAULT_POINTS = frozenset({'lonely_point'})\n"
        "def fault_point(name):\n    pass\n")
    (tmp_path / "automodel_tpu" / "hot.py").write_text(
        "from automodel_tpu.utils.fault_injection import fault_point\n"
        "def f():\n    fault_point('lonely_point')\n")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tests").mkdir()
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from fault_coverage import build_report
    finally:
        sys.path.pop(0)
    report = build_report(str(tmp_path))
    assert not report["ok"]
    assert report["undrilled"] == ["lonely_point"]
    assert report["points"][0]["call_sites"] == ["automodel_tpu/hot.py:3"]


# ---------------------------------------------------------------------------
# L007 — ppermute confined to ops/ + training/train_step.py
# ---------------------------------------------------------------------------
def test_l007_flags_ppermute_outside_its_homes():
    src = ("from jax import lax\n"
           "def f(x):\n"
           "    return lax.ppermute(x, 'pp', [(0, 1)])\n")
    hits = _lint(src, rel="automodel_tpu/training/pipeline.py",
                 select=["L007"])
    assert _rules(hits) == ["L007"]
    hits = _lint("import jax\n"
                 "def f(x):\n"
                 "    return jax.lax.ppermute(x, 'cp', [(0, 1)])\n",
                 rel="automodel_tpu/recipes/llm/train_ft.py",
                 select=["L007"])
    assert _rules(hits) == ["L007"]
    # the import form is flagged too (an aliased call would evade the
    # attribute-chain check otherwise)
    hits = _lint("from jax.lax import ppermute\n",
                 rel="automodel_tpu/serving/engine.py", select=["L007"])
    assert _rules(hits) == ["L007"]


def test_l007_clean_in_ops_train_step_and_with_suppression():
    src = ("from jax import lax\n"
           "def f(x):\n"
           "    return lax.ppermute(x, 'cp', [(0, 1)])\n")
    assert _lint(src, rel="automodel_tpu/ops/ring_attention.py",
                 select=["L007"]) == []
    assert _lint(src, rel="automodel_tpu/training/train_step.py",
                 select=["L007"]) == []
    suppressed = ("from jax import lax\n"
                  "def f(x):\n"
                  "    return lax.ppermute(x, 'pp', [(0, 1)])"
                  "  # lint: disable=L007 (drill harness permute)\n")
    assert _lint(suppressed, rel="automodel_tpu/analysis/elastic_drill.py",
                 select=["L007"]) == []
