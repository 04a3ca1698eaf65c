#!/usr/bin/env python
"""Inspect / pre-warm / clear the Pallas block-size autotune cache.

Operator companion to the kernel substrate's autotuner
(``automodel_tpu/ops/kernel_lib/autotune.py``), mirroring
``tools/verify_checkpoint.py`` ergonomics::

    python tools/autotune.py --show [--cache PATH]
    python tools/autotune.py --clear [--cache PATH]

    # pre-warm every key a recipe YAML will look up (the multihost story:
    # sweep once here, then every host reads the same warm cache)
    python tools/autotune.py --sweep --config examples/.../bench.yaml

    # or sweep one kernel at an explicit shape
    python tools/autotune.py --sweep --kernel splash \\
        --shape q_seq=16384,kv_seq=16384,head_dim=64,num_q_heads=32,num_kv_heads=8

``--force`` re-sweeps keys that are already cached.  Exit code 0 on
success; 1 when a sweep errored or the cache is unreadable (``--show``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_shape(spec: str) -> dict:
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        if v.lower() in ("true", "false"):      # causal=false etc.
            out[k] = v.lower() == "true"
            continue
        try:
            out[k] = int(v)
        except ValueError:
            out[k] = v
    return out


def _show(path: str) -> int:
    from automodel_tpu.ops.kernel_lib.autotune import CACHE_VERSION

    if not os.path.exists(path):
        print(f"no cache at {path} (cold)")
        return 0
    try:
        with open(path) as f:
            data = json.load(f)
    except Exception as e:
        print(f"FAIL  {path}: unreadable ({e}) — runs will warn once and "
              "use the hand-tuned defaults; --clear to remove it")
        return 1
    version = data.get("version")
    entries = data.get("entries", {})
    print(f"cache {path} (version {version}"
          f"{'' if version == CACHE_VERSION else f' != {CACHE_VERSION}: IGNORED by runs'}, "
          f"topology {data.get('topology', '?')}, {len(entries)} entr"
          f"{'y' if len(entries) == 1 else 'ies'})")
    for key in sorted(entries):
        e = entries[key]
        block = "x".join(map(str, e.get("block", ())))
        print(f"  {key}  ->  {block}  ({e.get('ms', '?')} ms)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Pre-warm/inspect/clear the Pallas block-size "
                    "autotune cache.")
    mode = parser.add_mutually_exclusive_group(required=True)
    mode.add_argument("--show", action="store_true",
                      help="print the cache's winners")
    mode.add_argument("--clear", action="store_true",
                      help="delete the cache file")
    mode.add_argument("--sweep", action="store_true",
                      help="time candidates and persist winners")
    parser.add_argument("--cache", help="cache file (default: beside the "
                        "XLA compile cache — JAX_COMPILATION_CACHE_DIR if "
                        "exported, else the in-checkout .jax_cache/)")
    parser.add_argument("--config", help="with --sweep: recipe YAML whose "
                        "model/sequence shapes to pre-warm")
    parser.add_argument("--kernel", help="with --sweep: one kernel key "
                        "(splash, flash, ring, linear_ce, gmm)")
    parser.add_argument("--shape", help="with --sweep --kernel: "
                        "comma-separated request fields, e.g. "
                        "q_seq=16384,kv_seq=16384,head_dim=64")
    parser.add_argument("--force", action="store_true",
                        help="re-sweep keys that are already cached")
    args = parser.parse_args(argv)

    from automodel_tpu.ops.kernel_lib import autotune

    path = args.cache or autotune.default_cache_path()
    if args.show:
        return _show(path)
    if args.clear:
        if os.path.exists(path):
            os.unlink(path)
            print(f"removed {path}")
        else:
            print(f"no cache at {path}")
        return 0

    # --sweep
    from automodel_tpu.utils.compile_utils import setup_compile_cache

    setup_compile_cache()
    requests = []
    if args.kernel:
        if not args.shape:
            parser.error("--sweep --kernel needs --shape")
        requests.append((args.kernel, _parse_shape(args.shape)))
    elif args.config:
        from automodel_tpu.config.arg_parser import (
            parse_args_and_load_config,
        )
        from automodel_tpu.recipes.llm.train_ft import build_model

        cfg = parse_args_and_load_config(["--config", args.config])
        model = build_model(cfg.get("model"))
        seq_len = (int(cfg.get("packed_sequence.packed_sequence_size", 0)
                       or 0)
                   or int(cfg.get("dataloader.fixed_length", 0) or 0)
                   or None)
        local_bs = int(cfg.get("step_scheduler.local_batch_size", 1) or 1)
        # cp>1 recipes dispatch the ring, not splash — the pre-warm must
        # plan the same keys the run will look up
        cp = int(cfg.get("distributed.cp_size", 1) or 1)
        requests = autotune.training_sweep_requests(
            model, seq_len=seq_len, local_batch=local_bs, cp=cp)
        if not requests:
            print("config derives no sweepable kernel shapes (no packed "
                  "sequence / fixed length?) — nothing to do")
            return 0
    else:
        parser.error("--sweep needs --config or --kernel/--shape")

    tuner = autotune.configure_autotune("force" if args.force else "on",
                                        path)
    report = tuner.sweep_requests(requests)
    print(json.dumps({"cache": path, **report}))
    for key, entry in sorted(tuner.table.items()):
        print(f"  {key}  ->  {'x'.join(map(str, entry['block']))}  "
              f"({entry.get('ms', '?')} ms)")
    return 1 if report.get("errors") else 0


if __name__ == "__main__":
    sys.exit(main())
