#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two normal entry points once, at the full width of models the
repo supports, with random weights made from a seed (no hub, no checkpoint,
no network):

* **train** — ``automodel finetune llm -c
  examples/llm_finetune/llama3_2/llama3_2_1b_bench.yaml`` (the CLI's recipe
  table -> ``recipes/llm/train_ft.main``): Llama-3.2-1B, mock data packed to
  2048, bf16, AdamW, 8 rows per chip, a few optimizer steps on however many
  chips the machine has (``dp_size: null``; the global batch scales with
  the device count, so this is the one-chip AND the four-chip run).
* **serve** — ``tools/serve.py --config
  examples/serve/llama3_2_3b_serve.yaml``: Llama-3.2-3B (head_dim 128, the
  width at which the Pallas paged-decode kernel is the resolved rung)
  through ``DecodeEngine``, a few requests to FINISHED; then one request's
  greedy tokens against ``generation.generate`` on the same params
  (reported, not enforced: random-init bf16 logits have near-ties — the
  enforced numeric check is ``tpu_tests/``).

A chip belongs to one process at a time, so this parent never touches JAX:
it starts one child per phase (``--phase``), one at a time, and stops it on
the way out.  Each child fails — nonzero exit, no result line — if JAX finds
no TPU (before any model is built), a Pallas rung was expected and an XLA
rung resolved, any ``_INTERPRET`` flag is on, a loss is not finite or does
not fall, a request does not FINISH, the backend reports no memory stats,
or anything raises.  No exception is caught and turned into a null.

The last line of stdout is one JSON object with exactly these keys, the
device as JAX reports it: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  The line before it is the full summary of
both phases (``[chip_smoke] summary {...}``), which ends ``"claim": null`` —
this script claims no number.

Trailing ``--section.key value`` arguments after ``--phase train`` are
passed to the recipe as overrides (how the four-chip ``tp_size 2`` run and
the fixed-global-batch comparisons in CHANGES.md were made).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
TRAIN_YAML = os.path.join(
    ROOT, "examples", "llm_finetune", "llama3_2", "llama3_2_1b_bench.yaml")
SERVE_YAML = os.path.join(ROOT, "examples", "serve", "llama3_2_3b_serve.yaml")
SERVE_TOOL = os.path.join(ROOT, "tools", "serve.py")
TRAIN_STEPS = 6
ROWS_PER_CHIP = 8               # the YAML's local_batch_size
SERVE_REQUESTS = 8
SERVE_MAX_NEW = 16
PARITY_PROMPT_LEN = 96          # not a 128 multiple: generate() stays on XLA
TIME_LIMIT_S = 1150             # both phases, compilation included
RESULT_TAG = "CHIP_SMOKE_RESULT "


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Children: one process per phase holds the chip
# ---------------------------------------------------------------------------
def _start_on_chip() -> dict:
    """Everything a phase does before building a model: find the TPU or
    exit, print what it is, place the compile cache."""
    sys.path.insert(0, ROOT)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this script only runs on the chip",
              file=sys.stderr)
        raise SystemExit(3)
    from importlib.metadata import version

    import automodel_tpu
    from automodel_tpu.ops.kernel_lib import parity
    from automodel_tpu.utils.compile_utils import setup_compile_cache

    if not automodel_tpu.__file__.startswith(ROOT + os.sep):
        raise SystemExit(f"automodel_tpu imported from "
                         f"{automodel_tpu.__file__}, not from {ROOT}")
    on = parity.interpret_flags_on()
    if on:
        raise SystemExit(f"_INTERPRET is on in {on}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    versions = {p: version(p) for p in ("jax", "jaxlib", "libtpu")}
    cache = setup_compile_cache()
    entries = _cache_entries(cache)
    placed_by = ("JAX_COMPILATION_CACHE_DIR"
                 if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 else "the in-checkout default")
    say(f"device {device}  versions {versions}")
    say(f"compile cache {cache} ({entries} entries at start; placed by "
        f"{placed_by})")
    return {"device": device, "versions": versions, "cache_dir": cache,
            "cache_entries_at_start": entries}


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _device_memory() -> list:
    """Per-device bytes from the backend; a backend that reports none, or
    no peak, fails the smoke (KeyError/TypeError, uncaught)."""
    import jax

    rows = []
    for d in jax.devices():
        stats = d.memory_stats()
        rows.append({"id": d.id, "bytes_in_use": stats["bytes_in_use"],
                     "peak_bytes_in_use": stats["peak_bytes_in_use"]})
        say(f"device {d.id}: {stats['bytes_in_use'] / 2**30:.2f} GiB in "
            f"use, peak {stats['peak_bytes_in_use'] / 2**30:.2f} GiB")
    return rows


def _check_rungs(expected: tuple, forbidden: tuple) -> dict:
    from automodel_tpu.ops.kernel_lib import registry

    rungs = registry.resolved_rungs()
    say(f"resolved rungs {rungs}")
    missing = [r for r in expected if not rungs.get(r)]
    wrong = [r for r in forbidden if rungs.get(r)]
    if missing or wrong:
        raise SystemExit(f"kernel dispatch: expected {missing} never "
                         f"resolved; XLA rungs resolved instead: {wrong}")
    return rungs


def train_phase(overrides: list) -> dict:
    import logging
    import math

    out = _start_on_chip()
    import jax

    from automodel_tpu import native
    from automodel_tpu._cli.app import RECIPES, load_function

    step_losses, first_dispatch = {}, []

    class Capture(logging.Handler):
        # the trainer reports per-step loss and the first-dispatch time
        # only through its log lines: read them where the user does
        def emit(self, record):
            if str(record.msg).startswith("step %d | loss"):
                step_losses[record.args[0]] = float(record.args[1])
            elif str(record.msg).startswith("first train-step dispatch"):
                first_dispatch.append(float(record.args[0]))

    logging.getLogger("automodel_tpu.recipes.llm.train_ft").addHandler(
        Capture())
    n_dev = out["device"]["count"]
    argv = ["--config", TRAIN_YAML,
            "--step_scheduler.max_steps", str(TRAIN_STEPS),
            "--step_scheduler.global_batch_size", str(ROWS_PER_CHIP * n_dev),
            *overrides]
    say(f"train: automodel finetune llm {' '.join(argv)}")
    t0 = time.perf_counter()
    recipe = load_function(RECIPES[("finetune", "llm")])(argv=argv)
    wall = time.perf_counter() - t0

    losses = [step_losses[s] for s in sorted(step_losses)]
    mesh = dict(recipe.mesh_manager.mesh.shape)
    say(f"per-step loss {losses}")
    say(f"first train-step dispatch {first_dispatch} s; phase wall "
        f"{wall:.1f} s; mesh {mesh}")
    if len(losses) != TRAIN_STEPS or len(first_dispatch) != 1:
        raise SystemExit(f"expected {TRAIN_STEPS} step lines and one "
                         f"first-dispatch line, got {losses} / "
                         f"{first_dispatch}")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"loss did not fall: {losses}")
    platforms = {d.platform for leaf in jax.tree.leaves(recipe.params)
                 for d in leaf.devices()}
    if platforms != {"tpu"}:
        raise SystemExit(f"params live on {platforms}, not the TPU")
    out.update(
        mesh=mesh,
        global_batch=int(recipe.cfg.get("step_scheduler.global_batch_size")),
        grad_acc_steps=recipe.step_scheduler.grad_acc_steps,
        losses=losses, first_dispatch_s=first_dispatch[0],
        wall_s=round(wall, 1),
        rungs=_check_rungs(("attention.splash", "linear_ce.pallas"),
                           ("attention.sdpa", "attention.flash",
                            "linear_ce.chunked")),
        native_packer=native.source(), memory=_device_memory(),
        cache_entries_at_end=_cache_entries(out["cache_dir"]))
    say(f"native packer: {out['native_packer']}")
    return out


def serve_phase(overrides: list) -> dict:
    import contextlib
    import gc
    import importlib.util
    import io

    if overrides:
        raise SystemExit(f"the serve phase takes no overrides: {overrides}")
    out = _start_on_chip()
    import jax
    import numpy as np

    spec = importlib.util.spec_from_file_location("serve_tool", SERVE_TOOL)
    serve_tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_tool)
    argv = ["--config", SERVE_YAML, "--requests", str(SERVE_REQUESTS),
            "--max-new", str(SERVE_MAX_NEW)]
    say(f"serve: tools/serve.py {' '.join(argv)}")
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = serve_tool.main(argv)
    wall = time.perf_counter() - t0
    print(buf.getvalue(), end="", flush=True)
    report = json.loads(buf.getvalue().strip().splitlines()[-1])
    say(f"serve exit {rc}; outcomes {report['outcomes']}; wall {wall:.1f} s "
        "(compiles included)")
    # warm-up request + the driven ones, all FINISHED
    if rc != 0 or report["outcomes"] != {"finished": SERVE_REQUESTS + 1}:
        raise SystemExit(f"serve: not every request FINISHED: {report}")
    rungs = _check_rungs(("attention.paged_decode",),
                         ("attention.paged_gather",))
    gc.collect()        # the tool's engine (params + pools) is garbage now
    tool_memory = _device_memory()

    # One request, engine vs the dense-cache generate() on the same params.
    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.generation import GenerationConfig, generate
    from automodel_tpu.serving import DecodeEngine, build_serving_config

    cfg = load_yaml_config(SERVE_YAML)
    model = cfg.model.instantiate()
    params = jax.jit(model.init)(jax.random.key(0))
    gen = GenerationConfig(max_new_tokens=SERVE_MAX_NEW)
    prompt = np.random.default_rng(0).integers(
        1, model.config.vocab_size, (1, PARITY_PROMPT_LEN))
    engine = DecodeEngine(model, params, build_serving_config(cfg),
                          generation=gen)
    paged = np.asarray(engine.generate(prompt))[0]
    dense = np.asarray(generate(model, params, prompt, config=gen))[0]
    agree = int(np.argmax(np.append(paged != dense, True)))
    say(f"greedy tokens agreeing with generation.generate before the first "
        f"difference: {agree}/{SERVE_MAX_NEW} (reported, not enforced)")
    out.update(requests=SERVE_REQUESTS, outcomes=report["outcomes"],
               wall_s=round(wall, 1), rungs=rungs,
               greedy_agree=[agree, SERVE_MAX_NEW],
               memory_after_tool=tool_memory, memory=_device_memory(),
               cache_entries_at_end=_cache_entries(out["cache_dir"]))
    return out


PHASES = {"train": train_phase, "serve": serve_phase}


# ---------------------------------------------------------------------------
# Parent: never touches JAX
# ---------------------------------------------------------------------------
def _run_child(phase: str, deadline: float) -> dict:
    """Run one phase in its own process, echo its output, return its result
    line; the child is stopped on timeout or on any way out of here."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase],
        cwd=ROOT, stdout=subprocess.PIPE, text=True)
    killer = threading.Timer(max(deadline - time.monotonic(), 1.0), proc.kill)
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or result is None:
        print(f"chip_smoke: phase {phase!r} failed (exit {rc})",
              file=sys.stderr)
        raise SystemExit(rc or 1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run one phase in this process (what the parent "
                         "starts; also the way to pass recipe overrides)")
    args, overrides = ap.parse_known_args()
    if args.phase:
        print(RESULT_TAG + json.dumps(PHASES[args.phase](overrides)),
              flush=True)
        return 0
    if overrides:
        ap.error(f"unknown arguments {overrides} (overrides need --phase)")
    for path in (os.path.join(ROOT, "automodel_tpu", "__init__.py"),
                 TRAIN_YAML, SERVE_YAML, SERVE_TOOL):
        if not os.path.exists(path):
            print(f"chip_smoke: {path} is missing — this script runs from "
                  "the root of the repo it checks", file=sys.stderr)
            return 2
    deadline = time.monotonic() + TIME_LIMIT_S
    results = {phase: _run_child(phase, deadline) for phase in PHASES}
    device = results["train"]["device"]
    if results["serve"]["device"] != device:
        raise SystemExit(f"the phases saw different devices: {results}")
    say("summary " + json.dumps({**results, "claim": None}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
