"""What the chip bring-up (ISSUE 22) established, pinned on the CPU:

* importing the package, ``__graft_entry__`` and ``chip_smoke``
  initialises no JAX backend — on a machine with a chip, a parent that has
  touched JAX holds the chip and starves every child it starts;
* ``chip_smoke.py`` fails fast without a TPU and prints no result;
* the compile cache has one placement rule (``utils/compile_utils.py``).
"""

import os
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_IMPORT_EVERYTHING = """
import importlib, pkgutil
import automodel_tpu, __graft_entry__, chip_smoke
for m in pkgutil.walk_packages(automodel_tpu.__path__, "automodel_tpu."):
    importlib.import_module(m.name)
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "an import touched a backend"
print("IMPORTS_CLEAN")
"""


def test_imports_initialise_no_backend(subprocess_env):
    proc = subprocess.run(
        [sys.executable, "-c", _IMPORT_EVERYTHING], cwd=_REPO,
        env=subprocess_env(1), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "IMPORTS_CLEAN" in proc.stdout


def test_chip_smoke_fails_fast_without_a_tpu(subprocess_env):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], cwd=_REPO,
        env=subprocess_env(1), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60          # before any model is built
    assert "found no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and "RESULT" not in proc.stdout


def test_chip_smoke_fails_alone_in_a_directory(tmp_path, subprocess_env):
    """Without the program beside it the script must fail too (the driver
    runs it that way to prove it checks this repo and nothing else)."""
    import shutil

    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env=subprocess_env(1), capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and '"ok"' not in proc.stdout


def test_chip_smoke_last_line_is_the_contract_object(monkeypatch, capsys):
    """The driver reads the last stdout line and accepts exactly the keys
    ``ok`` and ``device`` {platform, kind, count}; everything else the
    phases report goes on the summary line before it."""
    import json

    monkeypatch.syspath_prepend(_REPO)
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(
        chip_smoke, "_run_child",
        lambda phase, deadline: {"device": device, "losses": [2.0, 1.0]})
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 0
    summary, last = capsys.readouterr().out.strip().splitlines()[-2:]
    assert json.loads(last) == {"ok": True, "device": device}
    assert summary.startswith("[chip_smoke] summary {")
    assert summary.endswith('"claim": null}')


# ---------------------------------------------------------------------------
# The compile-cache placement rule
# ---------------------------------------------------------------------------
@pytest.fixture
def restore_cache_config(monkeypatch):
    """The rule's two arms are about an accelerator backend (on the CPU
    code sets no cache): answer "tpu" for the duration, restore the
    config after."""
    import jax

    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cpu_backend_sets_no_cache(monkeypatch):
    import jax

    from automodel_tpu.utils import compile_utils

    monkeypatch.delenv(compile_utils.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_utils.setup_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_env_set_means_code_sets_nothing(monkeypatch, tmp_path,
                                               restore_cache_config):
    import jax

    from automodel_tpu.ops.kernel_lib import autotune
    from automodel_tpu.utils import compile_utils

    monkeypatch.setenv(compile_utils.CACHE_DIR_ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    assert compile_utils.setup_compile_cache() == str(tmp_path)
    # untouched by code: the operator's variable is JAX's business alone
    assert jax.config.jax_compilation_cache_dir == "/sentinel"
    # the autotune winner table follows the same rule
    assert autotune.default_cache_path() == os.path.join(
        str(tmp_path), autotune.CACHE_BASENAME)


def test_cache_env_unset_means_the_fixed_in_checkout_dir(
        monkeypatch, restore_cache_config):
    import jax

    from automodel_tpu.ops.kernel_lib import autotune
    from automodel_tpu.utils import compile_utils

    monkeypatch.delenv(compile_utils.CACHE_DIR_ENV, raising=False)
    fixed = os.path.join(_REPO, ".jax_cache")
    assert compile_utils.DEFAULT_CACHE_DIR == fixed
    assert compile_utils.setup_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert autotune.default_cache_path() == os.path.join(
        fixed, autotune.CACHE_BASENAME)
    # git-ignored, so a run never dirties the checkout
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_section_cannot_place_the_cache(monkeypatch,
                                                restore_cache_config):
    import jax

    from automodel_tpu.utils.compile_utils import (
        apply_compile_config,
        build_compile_config,
    )

    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        build_compile_config(None, cache_dir="/tmp/somewhere")
    cfg = build_compile_config(None, enabled=False, mode="max-autotune")
    assert cfg.mode == "max-autotune"      # torch knob accepted, ignored
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    assert apply_compile_config(cfg) is None       # disabled: sets nothing
    assert jax.config.jax_compilation_cache_dir == "/sentinel"
