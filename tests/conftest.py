"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's threaded-process-group trick for testing collectives
without a cluster (SURVEY §4): real XLA collectives over 8 host-platform
devices stand in for an 8-chip TPU slice.

The platform is forced to the CPU here (before any backend initializes)
so ``pytest tests/`` means the same thing on every machine: on a host with
a chip an unset ``JAX_PLATFORMS`` would otherwise run tier-1 on the TPU,
one device instead of eight, and hold the chip against every subprocess
test.  The on-chip suite is ``tpu_tests/``, driven through the chip tool.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
# No persistent compile cache under test, even if the environment exports
# JAX_COMPILATION_CACHE_DIR: XLA:CPU on this jaxlib aborts the process
# executing some cache-loaded 8-device programs, and tier-1 judges the code,
# not what an earlier run left on disk.
jax.config.update("jax_enable_compilation_cache", False)


# ---------------------------------------------------------------------------
# Suite tiering: ``pytest -m "not slow"`` is the <5-minute core tier on a
# 1-core host (VERDICT r3 weak #8).  Heavy modules — HF-transformers parity
# (torch model loads per test) and end-to-end recipe runs — are marked slow
# wholesale here so new tests in them inherit the tier automatically.
# ---------------------------------------------------------------------------
import pytest  # noqa: E402

_SLOW_MODULES = {
    # HF parity (save -> transformers reload per test)
    "test_hf_parity", "test_gemma3_parity", "test_gemma3n",
    "test_new_text_families", "test_qwen25_vl", "test_phi4_mm",
    "test_mixtral", "test_hf_io", "test_sequence_classification",
    "test_generation", "test_models", "test_deepseek_v3",
    "test_rope_scaling", "test_olmo2_starcoder2",
    # end-to-end recipe / multi-process tiers
    "test_train_ft_recipe", "test_vlm_finetune", "test_cli",
    "test_multiprocess_cpu", "test_checkpoint_resume", "test_pretrain",
    # interpret-mode Pallas kernels (minutes on 1 CPU core)
    "test_splash_attention", "test_linear_ce_kernel", "test_ring_attention",
    "test_tp_loss_parity", "test_quant",
    # heavy sharded-step compiles
    "test_training", "test_host_sharded_input", "test_ref_yaml_recipe",
    "test_pretrain_recipe", "test_train_parity_torch", "test_peft",
    "test_mesh_reshape_restore",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy parity/e2e tests excluded from the core tier")
    config.addinivalue_line(
        "markers", "core: keep in the fast tier even inside a slow module "
        "(one cheap end-to-end representative per major code path)")
    config.addinivalue_line(
        "markers", "fault: fault-injection crash-safety tests (CPU-only and "
        "fast — they run in the tier-1 core suite; select with -m fault)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.module.__name__.split(".")[-1] in _SLOW_MODULES
                and item.get_closest_marker("core") is None):
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def subprocess_env():
    """Factory: env dict for a child that must run on N virtual CPU devices
    (pins the cpu platform and re-pins
    xla_force_host_platform_device_count) — shared by every
    subprocess-launching test so the env dance cannot drift."""
    def make(n_devices: int):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
        return env
    return make
