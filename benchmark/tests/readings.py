"""The readings that the limits of ``correct`` are set from, taken on the
chip at a cell's own size, several seeds in ONE process (one hold of the
chip, one compile):

    python3 benchmark/tests/readings.py <cell> <seconds> <mode> <seed>...

``mode``: ``sound`` (the program as the configuration states: the lower
readings), ``control`` (the program's own int8 path: the upper readings),
one of the faults of ``test_correct.py`` (``state_unchanged``,
``half_batch_left_out``, ``altered_token``), or ``rate=<per second>[,<per second>...]`` (sound
runs with the open loop's rate replaced, the n-th seed at the n-th rate:
the sweep for the knee).
Prints one ``READING`` line per seed with the numbers compared.  Not run by
the benchmark's own runs nor by pytest.
"""
import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

KEEP = ("[bench] window", "[bench] engine stats", "[bench] step ",
        "[bench] worst", "[bench] reference", "[bench] compared",
        "[bench] peak", "[bench] decode steps", "[bench] mixed steps",
        "[bench] steps began")


class _Patch:
    """What ``monkeypatch.setattr`` does, undone by ``undo()``."""

    def __init__(self):
        self.saved = []

    def setattr(self, obj, name, value):
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        for obj, name, value in reversed(self.saved):
            setattr(obj, name, value)


def main(argv):
    from benchmark import run as bench_run, trafficgen
    from benchmark.tests import test_correct as faults

    cell, seconds, mode, seeds = argv[0], argv[1], argv[2], argv[3:]
    patch = _Patch()
    control = "0"
    if mode == "control":
        control = "1"
    elif mode.startswith("rate="):
        real, rates = trafficgen.load, [float(r) for r in
                                        mode[5:].split(",")]
        patch.setattr(trafficgen, "load", lambda name: dict(
            real(name), rate_per_s=rates[0]))
    elif mode == "altered_token":
        faults.alter_tokens(patch)
    elif mode != "sound":
        faults._break_train(patch, getattr(faults, mode))
    try:
        for seed in seeds:
            if mode.startswith("rate="):
                print(f"rate {rates[0]}", flush=True)
            buf, t0 = io.StringIO(), time.time()
            try:
                with contextlib.redirect_stdout(buf):
                    rc = bench_run.main(
                        ["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", "0", "--control", control])
            except BaseException as e:      # a crash is a reading too
                rc = f"raised {e!r}"
            lines = buf.getvalue().strip().splitlines()
            for line in lines:
                if line.startswith(KEEP):
                    print(line[:400])
            last = lines[-1] if lines and lines[-1].startswith("{") else "{}"
            out = json.loads(last)
            if mode.startswith("rate=") and len(rates) > 1:
                rates.pop(0)
            print(f"READING cell={cell} mode={mode} seed={seed} rc={rc} "
                  f"wall={time.time() - t0:.1f} correct={out.get('correct')}"
                  f" attempted={out.get('attempted')} failed="
                  f"{out.get('failed')} metrics="
                  + json.dumps({k: v["value"] for k, v in
                                out.get("metrics", {}).items()})
                  + " compared=" + json.dumps(
                      {k: v["value"] for k, v in
                       out.get("compared", {}).items()}), flush=True)
    finally:
        patch.undo()


if __name__ == "__main__":
    main(sys.argv[1:])
