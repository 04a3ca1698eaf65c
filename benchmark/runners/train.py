"""Runner ``train``: the users' loop.  ``setup()`` then
``run_train_validation_loop()`` of the recipe class the CLI starts, with the
prefetching loader and the double-buffered staging; the harness only wraps
the recipe instance's bound ``_run_train_optim_step`` to stamp steps and
count tokens, and ends the loop through ``step_scheduler.max_steps``.

One object, one loop: the first ``WARM`` optimizer steps are set-up (step 1
compiles; steps 1 and 2 are the ones the reference follows), the window
opens at the ``block_until_ready`` after them and closes at the
``block_until_ready`` of the step whose dispatch crossed ``--seconds``.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict

import numpy as np

from benchmark import check, common, manifest as mf, weights

WARM = 4            # optimizer steps before the window opens
FOLLOWED = 2        # of which the reference follows the first two


def _recipe_config(ctx) -> Any:
    from automodel_tpu.config.loader import load_yaml_config

    cell, config = ctx["cell_file"], ctx["config"]
    cfg = load_yaml_config(os.path.join(mf.HERE, "workloads",
                                        cell["recipe"]))
    chips = ctx["cell"]["chips"]
    rows = int(cell["rows_per_chip"])
    cfg.set_by_dotted("model.config",
                      mf.family(config).model_config(config))
    cfg.set_by_dotted("step_scheduler.local_batch_size", rows)
    cfg.set_by_dotted("step_scheduler.global_batch_size", rows * chips)
    cfg.set_by_dotted("rng.seed", ctx["seed"] & 0x7FFFFFFF)
    cfg.set_by_dotted("dataset.traffic", ctx["cell"]["traffic"])
    cfg.set_by_dotted("dataset.seed", ctx["seed"])
    cfg.set_by_dotted("dataset.vocab_size", config["vocab_size"])
    if ctx.get("control"):
        # the control: the program's own lower-precision path, switched on
        cfg.set_by_dotted("fp8", {"enabled": True, "dtype": "int8",
                                  "recipe_name": "tensorwise",
                                  "filter_fqns": []})
    return cfg


def _find_mu(state):
    """Adam's first moment inside the optimizer's state, wherever the chain
    put it."""
    if hasattr(state, "mu"):
        return state.mu
    for child in (getattr(state, "inner_state", None),
                  *(state if type(state) in (tuple, list) else ())):
        if child is not None:
            mu = _find_mu(child)
            if mu is not None:
                return mu
    return None


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from automodel_tpu.recipes.llm.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction,
    )
    cell, config, seed = ctx["cell_file"], ctx["config"], ctx["seed"]
    ref = mf.family(config)     # layout, norms of leaves, plain reference
    spans: common.Spans = ctx["spans"]
    seconds = ctx["seconds"]
    recipe = TrainFinetuneRecipeForNextTokenPrediction(_recipe_config(ctx))
    recipe.setup()
    common.say(f"recipe.setup() done at {time.perf_counter() - ctx['t_start']:.1f} s")
    if ctx["on_chip"] and recipe.mesh_manager.mesh.size != ctx["cell"]["chips"]:
        raise SystemExit(f"mesh {dict(recipe.mesh_manager.mesh.shape)} does "
                         f"not span {ctx['cell']['chips']} chips")

    # The benchmark's weights in the program's tree, placed by the
    # program's plan; the recipe's own random init is dropped first.
    words = weights.seed_words(seed)
    make = jax.jit(lambda w: ref.to_program_tree(ref.make(config, w)),
                   out_shardings=recipe.param_sharding)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), recipe.params)
    recipe.params = None
    recipe.params = jax.block_until_ready(make(words))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), recipe.params)
    if got != want:
        raise SystemExit(f"{ref.__name__} does not make the program's "
                         f"parameter tree: {got} != {want}")
    common.say(f"weights made at {time.perf_counter() - ctx['t_start']:.1f} s")

    b1 = float(recipe.cfg.get("optimizer.betas")[0])
    grad_norms = jax.jit(lambda mu: ref.leaf_norm_arrays(
        ref.from_program_tree(mu), scale=1.0 / (1.0 - b1)))
    change_norms = jax.jit(lambda p, w: ref.leaf_norm_arrays(
        ref.from_program_tree(p), minus=ref.make(config, w)))

    st: Dict[str, Any] = {
        "n": 0, "steps": [], "followed": [], "losses": [], "lrs": [],
        "t_open": None, "t_close": None, "compiles_at_open": None}
    counter: common.CompileCounter = ctx["compiles"]
    inner = recipe._run_train_optim_step
    timers = recipe.timers

    def input_wait() -> float:
        return sum(timers(n).elapsed(reset=False)
                   for n in ("data_wait", "data_staging"))

    def stepped(batches):
        st["n"] += 1
        n = st["n"]
        if n == WARM + 1:
            jax.block_until_ready((recipe.params, recipe.opt_state))
            st["compiles_at_open"] = counter.count
            st["wait_at_open"] = input_wait()
            if ctx["trace"]:
                common.start_trace(ctx["trace_dir"])
            st["t_open"] = time.perf_counter()
            ctx["setup_s"] = st["t_open"] - ctx["t_start"]
        if n > WARM:
            st["steps"].append(
                (time.perf_counter(),
                 [np.asarray(b["segment_ids"]) for b in batches]))
        with spans.span("train_step"):
            out = inner(batches)
        if n <= FOLLOWED:
            st["followed"].append([{k: np.array(v) for k, v in b.items()}
                                   for b in batches])
            st["losses"].append(recipe._pending_metrics["device_metrics"])
            st["lrs"].append(float(recipe.lr_scheduler.current_lr))
        if n == 1:
            st["grad_norms"] = grad_norms(_find_mu(recipe.opt_state))
            common.say(f"first step dispatched at "
                       f"{time.perf_counter() - ctx['t_start']:.1f} s")
        if n == FOLLOWED:
            st["change_norms"] = change_norms(recipe.params, words)
        if (n > WARM and st["t_close"] is None
                and time.perf_counter() - st["t_open"] >= seconds):
            # the recipe's own stop condition: the loop ends after this step
            recipe.step_scheduler.max_steps = recipe.step_scheduler.step
            jax.block_until_ready(recipe.params)
            st["t_close"] = time.perf_counter()
            st["wait_at_close"] = input_wait()
            st["compiles_in_window"] = counter.count - st["compiles_at_open"]
            if ctx["trace"]:
                ctx["xplane"] = common.stop_trace(ctx["trace_dir"])
        return out

    recipe._run_train_optim_step = stepped
    recipe.run_train_validation_loop()
    if st["t_close"] is None:
        raise SystemExit("the training loop ended before the window closed "
                         f"(after {st['n']} steps): the data ran out")
    ctx["memory_peak_bytes"] = (common.peak_memory_bytes()
                                if ctx["on_chip"] else 0)
    if ctx["on_chip"]:
        ctx["rungs"] = common.check_rungs(cell["expected_rungs"],
                                          cell["forbidden_rungs"])

    window = st["t_close"] - st["t_open"]
    tokens = [sum(int(np.count_nonzero(s)) for s in segs)
              for _, segs in st["steps"]]
    slots = sum(s.size for _, segs in st["steps"] for s in segs)
    ctx["window"] = {
        "t_open": st["t_open"], "t_close": st["t_close"], "seconds": window,
        "steps": len(st["steps"]), "tokens": sum(tokens),
        "padding_share": 1.0 - sum(tokens) / slots,
        "segments": [segs for _, segs in st["steps"]],
        "input_wait_s": st["wait_at_close"] - st["wait_at_open"],
        "compiles_in_window": st["compiles_in_window"],
    }
    starts = [t for t, _ in st["steps"]] + [st["t_close"]]
    between = sorted(1e3 * (b - a) for a, b in zip(starts, starts[1:]))
    common.say(f"steps began {between[len(between) // 2]:.3f} ms apart at "
               f"the median, {between[-1]:.3f} ms at the most (a stall "
               "shows in the most, a slow process in the median)")
    common.say(f"window {window:.3f} s, {len(tokens)} steps, "
               f"{sum(tokens)} non-padding tokens, padding share "
               f"{ctx['window']['padding_share']:.4f}, compiles in window "
               f"{st['compiles_in_window']}")
    program = {
        "losses": [float(np.asarray(m["loss"])) for m in st["losses"]],
        "lrs": st["lrs"],
        "grad_norms": ref.norm_dict(st["grad_norms"]),
        "change_norms": ref.norm_dict(st["change_norms"]),
    }
    followed = st["followed"]
    optimizer = recipe.cfg.get("optimizer").to_dict()
    # free the program's state before the reference makes its own
    recipe._run_train_optim_step = None
    del recipe, inner, stepped, make, grad_norms, change_norms, st
    gc.collect()

    t0 = time.perf_counter()
    ctx["compared"] = check.train(
        config, seed, ctx["cell"]["traffic"], followed, program, optimizer,
        cell["limits"])
    common.say(f"reference followed {FOLLOWED} steps in "
               f"{time.perf_counter() - t0:.1f} s")
    chips = ctx["cell"]["chips"]
    return {
        "attempted": ctx["window"]["steps"], "failed": 0,
        "end_to_end": {
            "train_tok_s_chip": ctx["window"]["tokens"] / window / chips},
    }
