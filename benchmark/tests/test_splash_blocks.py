"""``splash_blocks_run_share.train``: reads the counters the train loop
puts on its ``dispatch`` spans; a program without them reads nothing."""
from benchmark import manifest as mf

READER = mf.load_by_name("metrics", "splash_blocks_run_share.train")


def _ctx(host):
    return {"reduced": {"lo_ns": 1000, "hi_ns": 9000},
            "program_trace": {"host": host, "scopes": [], "ops": []}}


def test_share_is_summed_over_the_windows_dispatch_spans():
    host = [
        ["dispatch", 500, 10, 0, {"step": 4, "attn_blocks_run": 36,
                                  "attn_blocks_static": 36}],   # before it
        ["dispatch", 2000, 10, 0, {"step": 5, "attn_blocks_run": 18,
                                   "attn_blocks_static": 36}],
        ["dispatch", 4000, 10, 0, {"step": 6, "attn_blocks_run": 27,
                                   "attn_blocks_static": 36}],
        ["data_wait", 4100, 10, 0, {"attn_blocks_run": 1,
                                    "attn_blocks_static": 1}],
        ["dispatch", 9500, 10, 0, {"step": 7, "attn_blocks_run": 36,
                                   "attn_blocks_static": 36}],  # after it
    ]
    assert READER.read(_ctx(host)) == 100.0 * 45 / 72


def test_a_program_without_the_counters_is_left_out():
    host = [["dispatch", 2000, 10, 0, {"step": 5}],
            ["dispatch", 4000, 10, 0, {"step": 6}]]
    assert READER.read(_ctx(host)) is None
    assert READER.read(_ctx([])) is None


def test_the_metric_is_listed_for_the_training_cell_only():
    listed = {m["name"]: m for m in mf.load()["per_layer"]}
    m = listed["splash_blocks_run_share.train"]
    assert m["workloads"] == ["olmo2-1b.sft-packed-4k"]
    assert (m["better"], m["source"], m["layer"], m["moves"]) == (
        "lower", "program_counter", "kernels, training", "train_tok_s_chip")
