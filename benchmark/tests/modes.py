"""Does a serving process's step time depend on the profiler?  One process
of a closed-loop serving cell, stepped through phases of ``seconds`` each:
plain, the profiler tracing the device only, plain, tracing the host only,
plain, as ``--trace 1`` traces, plain.  Prints each phase's median decode
and mixed step time (PERF.md section 7, the modes of a serving process):

    python3 benchmark/tests/modes.py <cell> <seed> [seconds]

Run on the chip; not run by the benchmark's own runs nor by pytest.
"""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import numpy as np

    from benchmark import common, manifest as mf, trafficgen
    from benchmark.runners import serve

    name, seed = argv[0], int(argv[1])
    seconds = float(argv[2]) if len(argv) > 2 else 12.0
    man = mf.load()
    cell = mf.cell_of(man, name)
    ctx = {"cell": cell, "seed": seed, "control": False,
           "cell_file": mf.read_json("workloads", name + ".json"),
           "config": mf.config_of(man, cell["config"]),
           "spans": common.Spans()}
    common.find_chips(cell["chips"], mf.read_json("peaks.json"))
    common.place_compile_cache()
    drive = serve.Drive(ctx, serve._build_engine(ctx))
    traffic = trafficgen.load(cell["traffic"])
    queues = mf.traffic_kind(traffic).generate(
        traffic, seed, ctx["config"]["vocab_size"])
    for c, q in enumerate(queues):
        q.reverse()
        drive.submit(q.pop(), time.perf_counter(), client=c)

    def phase(label, host_level=None, tpu_trace_mode=None):
        where = common.trace_dir()
        if host_level is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = host_level
            if tpu_trace_mode:
                opts.advanced_configuration = {
                    "tpu_trace_mode": tpu_trace_mode}
            jax.profiler.start_trace(where, profiler_options=opts)
        first, end = len(drive.steps), time.perf_counter() + seconds
        while time.perf_counter() < end:
            for rec in drive.step():
                drive.submit(queues[rec["client"]].pop(),
                             time.perf_counter(), client=rec["client"])
        if host_level is not None:
            jax.profiler.stop_trace()
        shutil.rmtree(where, ignore_errors=True)
        steps = drive.steps[first:]
        med = lambda mixed: np.median(
            [1e3 * (s["t1"] - s["t0"]) for s in steps
             if (s["width"] > 1) == mixed] or [np.nan])
        print(f"PHASE {label:30s} steps {len(steps):4d} decode "
              f"{med(False):8.3f} ms mixed {med(True):8.3f} ms", flush=True)

    phase("ramp, plain")
    phase("plain")
    phase("device only (TRACE_ONLY_XLA)", 0, "TRACE_ONLY_XLA")
    phase("plain")
    phase("host only (TRACE_ONLY_HOST)", 2, "TRACE_ONLY_HOST")
    phase("plain")
    phase("as --trace 1", 2)
    phase("plain")


if __name__ == "__main__":
    main(sys.argv[1:])
