"""The manifest's self-check: sound as committed, and it refuses what a
driver would refuse, PR 23's fault first."""
import copy

import pytest

from benchmark import manifest as mf

TRAIN, CHAT = "olmo2-1b.sft-packed-4k", "olmo2-1b.serve-chat-steady"


def _metric(m, name):
    return next(x for x in m["per_layer"] + m["end_to_end"]
                if x["name"] == name)


def pr23_fault(m):
    _metric(m, "input_wait_share.train")["workloads"].append(CHAT)


def no_workloads_list(m):
    del _metric(m, "mfu.train")["workloads"]


def moves_nothing(m):
    _metric(m, "mfu.serve")["moves"] = "tokens"


def unknown_cell(m):
    _metric(m, "mfu.serve")["workloads"].append("olmo2-1b.serve-nothing")


def bad_name(m):
    m["workloads"][0]["name"] = "olmo2 1b/sft"


def bad_unit(m):
    _metric(m, "serve_tok_s")["unit"] = "tokens per second"


def long_source(m):
    m["configs"][0]["source"] = "x" * 201


def too_many_four_chip_cells(m):
    for w in m["workloads"][:2]:
        w["chips"] = 4


def config_without_cell(m):
    m["configs"].append(dict(m["configs"][0], name="olmo2-7b",
                             file="benchmark/configs/olmo2-1b.json"))


def missing_file(m):
    m["configs"][0]["file"] = "benchmark/configs/nothing.json"


def extra_key_on_metric(m):
    _metric(m, "mfu.train")["why"] = "because"


def loose_bound(m):
    _metric(m, "serve_tok_s")["bound"] = 0.2


def test_the_committed_manifest_is_sound():
    assert mf.self_check(mf.load()) == []


@pytest.mark.parametrize("breach", [
    pr23_fault, no_workloads_list, moves_nothing, unknown_cell, bad_name,
    bad_unit, long_source, too_many_four_chip_cells, config_without_cell,
    missing_file, extra_key_on_metric, loose_bound],
    ids=lambda f: f.__name__)
def test_self_check_refuses(breach):
    m = copy.deepcopy(mf.load())
    breach(m)
    assert mf.self_check(m), breach.__name__


def test_pr23_fault_is_named_as_the_driver_named_it():
    m = copy.deepcopy(mf.load())
    pr23_fault(m)
    assert any("input_wait_share.train is reported on workload " + CHAT
               + ", where train_tok_s_chip" in e for e in mf.self_check(m))


def test_every_cell_finds_its_files_and_metrics():
    m = mf.load()
    for w in m["workloads"]:
        cell = mf.read_json("workloads", w["name"] + ".json")
        assert {"runner", "trace_seconds", "limits", "expected_rungs",
                "forbidden_rungs"} <= set(cell)
        assert mf.config_of(m, w["config"])["hidden_size"] > 0
        assert mf.metrics_of(m, "per_layer", w["name"])
        e2e = [x["name"] for x in mf.metrics_of(m, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
