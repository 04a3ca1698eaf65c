#!/usr/bin/env python
"""Standalone online-eval watcher: score committed checkpoints as they
land.

The production shape of the ROADMAP's online-eval loop: run this on its
own host/devices next to a training run, pointed at the same checkpoint
root.  It polls for COMMITTED ``epoch_*_step_*`` directories (the PR-1
atomic-rename protocol makes commit detection a name test), loads each
new checkpoint's weights, scores it through the serving engine
(``serving/eval.py`` greedy continuation scoring — the hellaswag-style
config schema), and prints one JSON line of ``eval/*`` metrics per
checkpoint.  Training is never touched — the watcher is a pure reader.

    # watch a run's checkpoints, scoring each once as it commits
    python tools/eval_watch.py --config examples/rl/tiny_llama_grpo_mock.yaml

    # score everything already committed, then exit
    python tools/eval_watch.py --config <yaml> --once

    # dense generate() path instead of the paged engine
    python tools/eval_watch.py --config <yaml> --via generate

The config needs ``model:`` (the architecture to load weights into),
``checkpoint.checkpoint_dir`` (overridable via --checkpoint-dir), and a
dataset section (default ``validation_dataset``) whose rows follow the
SFT schema ``serving/eval.split_prompt_target`` consumes.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True,
                   help="YAML with model: + a dataset section")
    p.add_argument("--checkpoint-dir", default=None,
                   help="checkpoint root (default: the config's "
                        "checkpoint.checkpoint_dir)")
    p.add_argument("--section", default="validation_dataset",
                   help="dataset section to score (SFT row schema)")
    p.add_argument("--limit", type=int, default=16,
                   help="rows per eval (default 16)")
    p.add_argument("--max-new-tokens", type=int, default=None)
    p.add_argument("--via", choices=("engine", "generate"),
                   default="engine")
    p.add_argument("--poll-s", type=float, default=10.0,
                   help="poll cadence in seconds (default 10)")
    p.add_argument("--once", action="store_true",
                   help="score everything committed now, then exit")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    from automodel_tpu.checkpoint.checkpointing import (
        build_checkpoint_config,
    )
    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.post_training.eval_watch import (
        CheckpointEvalWatcher,
        rows_from_eval_config,
    )
    from automodel_tpu.utils.compile_utils import setup_compile_cache

    setup_compile_cache()
    cfg = load_yaml_config(args.config)
    model = cfg.get("model").instantiate()
    ckpt_cfg = build_checkpoint_config(cfg.get("checkpoint"))
    ckpt_dir = args.checkpoint_dir or ckpt_cfg.checkpoint_dir
    if not ckpt_dir:
        p.error("no checkpoint dir: set checkpoint.checkpoint_dir in the "
                "config or pass --checkpoint-dir")
    section = args.section
    if cfg.get(section) is None and cfg.get("dataset") is not None:
        section = "dataset"
    rows = rows_from_eval_config(cfg, section=section, limit=args.limit)

    watcher = CheckpointEvalWatcher(
        model, ckpt_dir, rows, via=args.via,
        max_new_tokens=args.max_new_tokens, checkpoint_config=ckpt_cfg,
        on_result=lambda res: print(json.dumps(res), flush=True))
    scored_any = False
    try:
        while True:
            scored_any |= bool(watcher.poll())
            if args.once:
                break
            time.sleep(args.poll_s)
    except KeyboardInterrupt:
        pass
    if args.once and not scored_any:
        print(json.dumps({"warning": "no committed checkpoints under "
                          + ckpt_dir}), flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
