"""The last line of standard output is the contract's object and nothing
else, with stubbed runners (no model is built)."""
import json
import types

import pytest

from benchmark import common, manifest as mf, run as bench_run
from benchmark.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _stub(e2e):
    def run(ctx):
        ctx["setup_s"] = 1.25
        ctx["memory_peak_bytes"] = 123
        ctx["window"] = {"compiles_in_window": 0}
        ctx["compared"] = common.Compared()
        ctx["compared"].add("served_token_gap", 0.01, 0.1)
        return {"attempted": 7, "failed": 0, "end_to_end": e2e}
    return types.SimpleNamespace(run=run)


@pytest.mark.parametrize("cell,e2e", [
    (tiny.TRAIN, {"train_tok_s_chip": 9000.5}),
    (tiny.CHAT, {"serve_tok_s": 900.0, "itl_p95_ms": 40.0,
                 "ttft_p95_ms": 1500.0}),
    (tiny.ROLLOUT, {"serve_tok_s": 3000.0, "itl_p95_ms": 40.0})])
def test_last_line_is_the_contract_object(monkeypatch, capsys, cell, e2e):
    monkeypatch.setattr(mf, "load_by_name",
                        lambda kind, name: _stub(e2e))
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 9),
                         "--seconds", "1", "--trace", "0"], on_chip=False)
    assert rc == 0
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert KEYS <= set(last) and list(last)[-1] == "compared"
    assert set(last["metrics"]) == set(e2e) | {"setup_s"}
    assert all(set(v) == {"value", "unit"} for v in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is True and last["attempted"] == 7
    assert err.strip().splitlines()[-1].startswith(
        "compared served_token_gap = 0.01 (limit 0.1)")


def test_a_compile_inside_the_window_prints_no_result(monkeypatch, capsys):
    stub = _stub({"train_tok_s_chip": 1.0})

    def run(ctx):
        out = stub.run(ctx)
        ctx["window"]["compiles_in_window"] = 1
        return out

    monkeypatch.setattr(mf, "load_by_name",
                        lambda kind, name: types.SimpleNamespace(run=run))
    rc = bench_run.main(["--workload", tiny.TRAIN, "--seed", "1",
                         "--seconds", "1", "--trace", "0"], on_chip=False)
    assert rc != 0 and "{" not in capsys.readouterr().out


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", tiny.TRAIN, "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out
