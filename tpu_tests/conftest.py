"""On-hardware test suite: runs on the real TPU backend, through the chip
tool (``chiprun -- python -m pytest tpu_tests/ -q``).

Unlike ``tests/`` (which pins an 8-device virtual CPU platform), this
directory needs the accelerator: without one the session FAILS before
collecting — a suite that skips itself exits 0 having run nothing.  Every
test's outcome (and the error each parity test measured) is written to
``chiprun_out/tpu_tests.json``, the directory the chip tool copies back.
"""

import json
import os

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RESULTS = []


def pytest_sessionstart(session):
    dev = jax.devices()[0]          # a backend that cannot start raises here
    if dev.platform != "tpu":
        pytest.exit(
            f"tpu_tests/ needs a TPU; JAX found {dev.platform!r} "
            f"({dev.device_kind}).  Run it through the chip tool.",
            returncode=1)
    from automodel_tpu.ops.kernel_lib import parity

    on = parity.interpret_flags_on()
    if on:
        pytest.exit(f"_INTERPRET is on in {on}: results would not be the "
                    "chip's", returncode=1)


def pytest_runtest_logreport(report):
    if report.when == "call" or (report.when == "setup"
                                 and report.outcome != "passed"):
        row = {"test": report.nodeid, "outcome": report.outcome,
               **dict(report.user_properties)}
        if report.outcome != "passed":
            row["message"] = str(report.longrepr)[-2000:]
        _RESULTS.append(row)


def pytest_sessionfinish(session, exitstatus):
    if not _RESULTS:
        return
    dev = jax.devices()[0]
    out = os.path.join(_REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "tpu_tests.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())},
                   "jax": jax.__version__, "exitstatus": int(exitstatus),
                   "results": _RESULTS}, f, indent=1)
