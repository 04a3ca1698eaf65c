"""A toy world for driving the runners on the CPU: the cells' own files
(recipe, serving settings, limits) with a toy configuration and traffic cut
to what a test can hold.  It skips the harness's look for a chip and
drives the rest of a run."""
import copy
import json
import os
import time

from benchmark import common, manifest as mf, trafficgen

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN = "olmo2-1b.sft-packed-4k"
CHAT = "olmo2-1b.serve-chat-steady"
ROLLOUT = "olmo2-1b.serve-rollout-saturated"
ROW = 256       # tokens a packed row holds in the toy world
# The toy world has limits of its own, set as the cells' are (PERF.md
# section 2) from readings at ITS size on the CPU (PR 25).  Training, four
# seeds: sound grad_norm_gap <= 0.0039, change_norm_gap <= 0.0028, loss
# gaps <= 2.3e-5; the int8 control reads grad_norm_gap >= 0.0078, half a
# batch >= 0.17, an unchanged state 1.  Serving, six seeds of each mix:
# sound served_token_gap <= 0.0047; the int8 control 0.0028-0.061 (a toy's
# logits are small, so at this size its lowest seeds overlap the sound
# ones; on the seed the tests run, 3, it reads 0.018 and 0.054 against
# 0.0031 and 0.0047 sound); an altered token >= 0.74.
LIMITS = {"train": {"grad_norm_gap": 0.006, "change_norm_gap": 0.02},
          "serve": {"served_token_gap": 0.008}}


def config():
    with open(os.path.join(DATA, "tiny-olmo2.json")) as f:
        return json.load(f)


def traffic(name):
    t = copy.deepcopy(mf.read_json("traffic", name + ".json"))
    if t["kind"] == "packed_docs":
        t.update(num_docs=64, packed_sequence_size=ROW)
        t["doc_len"].update(median=60, max=ROW)
    else:
        t["ramp_s"] = 0.5
        t["prompt_len"].update(median=40, min=8, max=120)
        t["output_len"].update(median=12, min=4, max=24)
        if t["kind"] == "open_loop":
            t.update(rate_per_s=8.0, running_since_s=1.0, token_s=0.05)
        else:
            t.update(clients=4, rounds=64)
    return t


def cell_file(name):
    c = copy.deepcopy(mf.read_json("workloads", name + ".json"))
    c["limits"] = LIMITS[c["runner"]]
    if "serving" in c:
        c["serving"].update(max_num_seqs=4, num_kv_blocks=128,
                            max_model_len=256, prefill_chunk=8)
    return c


def patch(monkeypatch):
    """Point the generator at the toy traffic and the recipe at toy rows."""
    from benchmark.runners import train

    monkeypatch.setattr(trafficgen, "load", traffic)
    real = train._recipe_config

    def recipe_config(ctx):
        cfg = real(ctx)
        cfg.set_by_dotted("packed_sequence.packed_sequence_size", ROW)
        cfg.set_by_dotted("loss_fn.chunk_len", ROW)
        return cfg

    monkeypatch.setattr(train, "_recipe_config", recipe_config)


def ctx(name, seed=3, seconds=0.5, control=False):
    man = mf.load()
    return {"t_start": time.perf_counter(), "cell": mf.cell_of(man, name),
            "cell_file": cell_file(name), "config": config(), "seed": seed,
            "seconds": seconds, "trace": False, "control": control,
            "on_chip": False, "spans": common.Spans(),
            "compiles": common.CompileCounter()}
