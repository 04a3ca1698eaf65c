#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its traffic mix, its runner and its
per-layer metric readers by the names in BENCHMARK.json; nothing here names
a cell.  Exits non-zero with no result line when the manifest breaks its own
rules, JAX finds no TPU, the device count differs from the cell's, the
device kind is not in peaks.json, an XLA rung resolved where the cell names
a Pallas rung, or anything compiled inside the window.  The last line of
standard output is the result object; everything else goes before it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None, on_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: run the program's own lower-precision path; "
                         "the run must come out as not correct")
    args = ap.parse_args(argv)

    from benchmark import common, manifest as mf

    if not os.path.isfile(os.path.join(ROOT, "automodel_tpu", "__init__.py")):
        print("benchmark: no automodel_tpu/ beside benchmark/: this runs "
              "from the root of the repo it measures", file=sys.stderr)
        return 2
    manifest = mf.load()
    errors = mf.self_check(manifest)
    if errors:
        print("benchmark: BENCHMARK.json breaks its rules:\n  "
              + "\n  ".join(errors), file=sys.stderr)
        return 2
    cell = mf.cell_of(manifest, args.workload)
    cell_file = mf.read_json("workloads", cell["name"] + ".json")
    peaks = mf.read_json("peaks.json")
    ctx = {
        "t_start": T_START, "cell": cell, "cell_file": cell_file,
        "config": mf.config_of(manifest, cell["config"]), "seed": args.seed,
        "seconds": (min(args.seconds, cell_file["trace_seconds"])
                    if args.trace else args.seconds),
        "trace": bool(args.trace), "control": bool(args.control),
        "on_chip": on_chip, "spans": common.Spans(),
    }
    if on_chip:
        ctx["device"] = common.find_chips(cell["chips"], peaks)
        ctx["peaks"] = peaks[ctx["device"]["kind"]]
        common.say(f"device {ctx['device']}; compile cache "
                   f"{common.place_compile_cache()}")
    else:       # tests drive the rest of a run without the look for a chip
        import jax

        d = jax.devices()
        ctx["device"] = {"platform": d[0].platform, "kind": d[0].device_kind,
                         "count": len(d)}
        ctx["peaks"] = next(iter(peaks.values()))
    ctx["compiles"] = common.CompileCounter()
    if args.trace:
        ctx["trace_dir"] = common.trace_dir()

    result = mf.load_by_name("runners", cell_file["runner"]).run(ctx)

    window = ctx["window"]
    if window["compiles_in_window"]:
        print(f"benchmark: {window['compiles_in_window']} compilations "
              "inside the window: a shape was not warmed up",
              file=sys.stderr)
        return 5
    device = dict(ctx["device"], memory_peak_bytes=ctx["memory_peak_bytes"])
    common.say(f"peak memory {ctx['memory_peak_bytes'] / 2**30:.3f} GiB; "
               f"set-up {ctx['setup_s']:.3f} s; rungs {ctx.get('rungs')}")
    out = {"correct": ctx["compared"].correct,
           "attempted": result["attempted"], "failed": result["failed"]}
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = mf.metrics_of(manifest, kind, cell["name"])
    if args.trace:
        from benchmark import trace_reduce

        t0 = time.perf_counter()
        ctx["reduced"] = trace_reduce.reduce_file(ctx["xplane"])
        common.say(f"trace reduced in {time.perf_counter() - t0:.1f} s: "
                   f"{ctx['reduced']['summary']}")
        values = {m["name"]: mf.load_by_name("metrics",
                                             m["name"]).read(ctx)
                  for m in wanted}
        silent = [n for n, v in values.items() if v is None]
        if silent:      # left out, as the contract has it, but said aloud
            common.say(f"WARNING the manifest lists {silent} for this cell "
                       "and their readers found nothing to read: left out")
        device["busy_s"] = ctx["reduced"]["busy_s"]
        device["window_s"] = ctx["reduced"]["window_s"]
        out["breakdown"] = ctx["reduced"]["breakdown"]
    else:
        values = dict(result["end_to_end"], setup_s=ctx["setup_s"])
    units = {m["name"]: m["unit"] for m in wanted}
    # a reader that finds nothing to read returns nothing: left out
    out["metrics"] = {n: {"value": values[n], "unit": units[n]}
                      for n in units if values.get(n) is not None}
    out["device"] = device
    out["compared"] = ctx["compared"].rows
    sys.stderr.flush()
    ctx["compared"].print()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
