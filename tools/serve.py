#!/usr/bin/env python
"""Operator smoke drive for the paged decode engine.

Loads a serving YAML (model + ``serving:`` knobs, see
``examples/serve/tiny_llama_serve.yaml`` and ``docs/guides/serving.md``),
drives synthetic prompts — or, with ``--eval``, the config's
``validation_dataset`` rows through the greedy-continuation scorer — and
prints one JSON report: tokens/s, engine stats (preemptions, peak blocks,
compiled widths; ``lookahead``, ``ahead_steps`` of ``steps`` dispatched
while the step before was still unfetched, ``discarded_rows`` computed for
a request that had already finished), the per-terminal-state outcome
summary, and the eval score when asked.

Robustness drills (docs/guides/serving.md "Production hardening"):

* SIGTERM/SIGINT trigger a **graceful drain** — stop admitting, finish
  in-flight work within ``--drain-grace-s`` (default:
  ``serving.drain_grace_s``), then expire stragglers with their blocks
  reclaimed — mirroring the trainer's preemption grace window.  A second
  ^C still aborts a hung run (sig_utils chaining).
* ``--fault`` arms a fault-injection spec (``serve_block_alloc:3,...``)
  for CI drills without touching the environment.
* The exit code is **0 only when every driven request FINISHED**; any
  aborted/expired/rejected/unfinished request exits 1 with the summary
  printed — so a CI drill that silently sheds work cannot pass.

Elastic fleet (docs/guides/serving.md "Elastic fleet"): ``--replicas N``
drives the same trace through a :class:`FleetRouter` over N per-slice
engines (``--router-policy`` picks the routing policy), and
``--drill-loss-at K`` arms ``fleet_replica_loss`` on the K-th health poll
— the drive loop polls fleet health every step, so the drill loses a
replica mid-traffic, replays its requests on survivors, then heals it
through probation + live-peer-params admission.  The exit contract is
unchanged: 0 only when every request FINISHED — a loss the fleet fails
to absorb cannot pass CI.

Multi-tenant serving (docs/guides/serving.md "Multi-tenant serving"):
``--adapters N`` arms the adapter slot registry (overrides
``serving.max_adapters``), loads N synthetic rank-r adapters into slots
1..N, and round-robins every driven request over adapter ids 0..N — so
the mixed batch exercises the grouped-GEMM multi-LoRA decode path plus
base traffic in one drive.  ``--tenant Q`` caps concurrent slots per
tenant (overrides ``serving.tenant_quota``).  The exit contract is
unchanged: 0 only when every request FINISHED.

    python tools/serve.py --config examples/serve/tiny_llama_serve.yaml
    python tools/serve.py --config ... --requests 32 --kv-dtype int8
    python tools/serve.py --config ... --deadline-s 30 --watchdog-s 10
    python tools/serve.py --config ... --fault serve_watchdog_stall:3
    python tools/serve.py --config ... --eval --limit 16
    python tools/serve.py --config ... --replicas 2 --drill-loss-at 5
    python tools/serve.py --config ... --adapters 4 --tenant 2
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _drive(engine, prompts, *, deadline_s, max_queue_s, drain_grace_s,
           handler, adapter_ids=None) -> dict:
    """Submit every prompt and step to completion, draining on a trapped
    signal.  Returns {"wall_s": ..., "drained": bool}.  Carries the same
    stall bound as ``engine.run()``: a scheduler wedge is a loud
    RuntimeError, never a silent CI hang."""
    t0 = time.perf_counter()
    drained = False
    ids = adapter_ids or [0] * len(prompts)
    for p, aid in zip(prompts, ids):
        engine.submit(p, deadline_s=deadline_s, max_queue_s=max_queue_s,
                      adapter_id=aid)
    from automodel_tpu.serving.kv_cache import blocks_needed

    max_steps = 64 + 8 * sum(
        blocks_needed(len(r.prompt), engine.config.prefill_chunk)
        + r.max_new_tokens + 1
        for r in engine.requests.values() if not r.finished)
    steps = 0
    while engine.scheduler.has_work():
        if handler is not None and handler.received:
            engine.drain(drain_grace_s)
            drained = True
            break
        engine.step()
        steps += 1
        if steps > max_steps:
            raise RuntimeError(
                f"engine made no progress within {max_steps} steps — "
                "scheduler stall (file a bug with the request trace)")
    return {"wall_s": time.perf_counter() - t0, "drained": drained}


def _drive_fleet(fleet, prompts, *, deadline_s, max_queue_s, drain_grace_s,
                 handler, adapter_ids=None) -> dict:
    """The fleet-mode drive: same contract as :func:`_drive`, plus one
    fleet health poll per step (the loop IS the health-poll cadence an
    operator deployment would run) and automatic grow-back: once a drill
    loses a replica, it is marked returning so subsequent polls walk it
    through probation and the live-peer-params admission."""
    t0 = time.perf_counter()
    drained = False
    ids = adapter_ids or [0] * len(prompts)
    for p, aid in zip(prompts, ids):
        fleet.submit(p, deadline_s=deadline_s, max_queue_s=max_queue_s,
                     adapter_id=aid)
    from automodel_tpu.serving.kv_cache import blocks_needed

    max_steps = 64 + 8 * sum(
        blocks_needed(len(r.prompt), fleet.config.prefill_chunk)
        + r.max_new_tokens + 1
        for r in fleet.requests.values() if not r.finished)
    steps = 0
    while fleet.has_work():
        if handler is not None and handler.received:
            fleet.drain(drain_grace_s)
            drained = True
            break
        fleet.poll_health(step=steps)
        for rep in fleet.replicas:
            if not rep.alive:
                fleet.note_return(rep.replica_id)
        fleet.step()
        steps += 1
        if steps > max_steps:
            raise RuntimeError(
                f"fleet made no progress within {max_steps} steps — "
                "scheduler stall (file a bug with the request trace)")
    # a drill that lost a replica late may still be mid-probation: keep
    # polling (idle — no traffic) until grow-back lands or gives up
    for extra in range(steps, steps + 4 * fleet.probation_polls):
        if all(r.alive for r in fleet.replicas):
            break
        fleet.poll_health(step=extra)
    return {"wall_s": time.perf_counter() - t0, "drained": drained}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", "-c", required=True)
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to drive (ignored with --eval)")
    ap.add_argument("--max-new", type=int, default=None,
                    help="tokens per request (default: generation section)")
    ap.add_argument("--kv-dtype", default=None,
                    help="override serving.kv_cache_dtype (e.g. int8)")
    ap.add_argument("--policy", default=None,
                    help="override serving.scheduler_policy")
    ap.add_argument("--prefix-cache", default=None, choices=["on", "off"],
                    dest="prefix_cache",
                    help="override serving.prefix_caching (content-hash "
                         "prefix reuse with copy-on-write forks)")
    ap.add_argument("--speculative", default=None, choices=["off", "ngram"],
                    help="override serving.speculative (n-gram draft + "
                         "width-(spec_k+1) verify; greedy output stays "
                         "token-identical to off)")
    ap.add_argument("--spec-k", type=int, default=None, dest="spec_k",
                    help="override serving.spec_k (draft tokens per decode "
                         "row; verify width is spec_k+1)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request end-to-end deadline (None: unbounded)")
    ap.add_argument("--max-queue-s", type=float, default=None,
                    help="per-request WAITING-time TTL (None: unbounded)")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="override serving.watchdog_s")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="override serving.max_waiting (queue bound)")
    ap.add_argument("--shed-policy", default=None,
                    help="override serving.shed_policy")
    ap.add_argument("--drain-grace-s", type=float, default=None,
                    help="drain window after SIGTERM/SIGINT "
                         "(default: serving.drain_grace_s)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="override serving.replicas (>1 drives a "
                         "FleetRouter over per-slice engines)")
    ap.add_argument("--router-policy", default=None,
                    help="override serving.router_policy "
                         "(round_robin/least_loaded/by_deadline)")
    ap.add_argument("--drill-loss-at", type=int, default=None,
                    help="arm fleet_replica_loss on the Nth health poll "
                         "(the drive loop polls once per step); implies "
                         "fleet mode")
    ap.add_argument("--adapters", type=int, default=None,
                    help="override serving.max_adapters, load that many "
                         "synthetic LoRA adapters into slots 1..N, and "
                         "round-robin requests over adapter ids 0..N "
                         "(multi-tenant grouped-GEMM decode)")
    ap.add_argument("--tenant", type=int, default=None,
                    help="override serving.tenant_quota (max concurrent "
                         "engine slots per adapter id)")
    ap.add_argument("--fault", default=None,
                    help="arm a fault-injection spec for CI drills, e.g. "
                         "'serve_block_alloc:3,serve_watchdog_stall:5'")
    ap.add_argument("--eval", action="store_true",
                    help="score the config's validation_dataset instead")
    ap.add_argument("--limit", type=int, default=16,
                    help="eval rows (with --eval)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from automodel_tpu.config.loader import load_yaml_config
    from automodel_tpu.generation import GenerationConfig
    from automodel_tpu.serving import (
        DecodeEngine,
        FleetRouter,
        build_serving_config,
    )
    from automodel_tpu.training.timers import (
        SERVE_PHASE_TIMERS,
        SERVE_TIMERS,
        Timers,
    )
    from automodel_tpu.utils import fault_injection as fi
    from automodel_tpu.utils.compile_utils import setup_compile_cache
    from automodel_tpu.utils.sig_utils import DistributedSignalHandler

    setup_compile_cache()
    cfg = load_yaml_config(args.config)
    for flag, dotted in (("kv_dtype", "serving.kv_cache_dtype"),
                         ("policy", "serving.scheduler_policy"),
                         ("prefix_cache", "serving.prefix_caching"),
                         ("speculative", "serving.speculative"),
                         ("spec_k", "serving.spec_k"),
                         ("watchdog_s", "serving.watchdog_s"),
                         ("max_waiting", "serving.max_waiting"),
                         ("shed_policy", "serving.shed_policy"),
                         ("drain_grace_s", "serving.drain_grace_s"),
                         ("replicas", "serving.replicas"),
                         ("router_policy", "serving.router_policy"),
                         ("adapters", "serving.max_adapters"),
                         ("tenant", "serving.tenant_quota")):
        v = getattr(args, flag)
        if v is not None:
            cfg.set_by_dotted(dotted, v)
    scfg = build_serving_config(cfg)
    model = cfg.model.instantiate()
    # jitted: the eager init materialises f32 temporaries per stacked leaf
    # (a 3B model peaked at 15.5 of a v5e's 15.75 GiB before any request)
    params = jax.jit(model.init)(jax.random.key(args.seed))
    gen_node = cfg.get("generation")
    gen = GenerationConfig(**(gen_node.to_dict() if gen_node else {}))
    if args.max_new is not None:
        gen = GenerationConfig(**{**gen.__dict__,
                                  "max_new_tokens": args.max_new})

    if args.eval:
        from automodel_tpu.serving.eval import eval_config_dataset

        report = eval_config_dataset(cfg, model, params, via="engine",
                                     limit=args.limit, serving=scfg)
        report.pop("tokens")
        print(json.dumps(report))
        return 0

    fleet_mode = (scfg.replicas or 1) > 1 or args.drill_loss_at is not None
    fault_spec = args.fault
    if args.drill_loss_at is not None:
        drill = f"fleet_replica_loss:{args.drill_loss_at}"
        fault_spec = f"{fault_spec},{drill}" if fault_spec else drill
    if fault_spec:
        fi.configure_faults(fault_spec)
    timers = Timers()
    if fleet_mode:
        engine = FleetRouter(model, params, scfg, generation=gen,
                             timers=timers)
    else:
        engine = DecodeEngine(model, params, scfg, generation=gen,
                              timers=timers)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(args.seed)
    n_adapters = args.adapters or 0
    if n_adapters:
        # synthetic tenants: one rank-r adapter per slot, loaded through
        # the digest-verified hot-swap path the production loader uses
        from automodel_tpu.peft.lora import PeftConfig, adapter_slab_shapes

        slots = (engine.replicas[0].engine if fleet_mode
                 else engine).adapter_slots
        shapes = adapter_slab_shapes(
            model, PeftConfig(dim=slots.rank), 1)
        for slot in range(1, n_adapters + 1):
            tree = {
                path: {"A": 0.01 * rng.standard_normal(
                           (a[0],) + a[2:]).astype(np.float32),
                       "B": 0.01 * rng.standard_normal(
                           (b[0],) + b[2:]).astype(np.float32)}
                for path, (a, b) in shapes.items()}
            engine.load_adapter(slot, tree, name=f"tenant-{slot}")
    prompts = [rng.integers(1, vocab, int(n)).tolist()
               for n in rng.integers(
                   4, max(5, scfg.max_model_len - gen.max_new_tokens),
                   args.requests)]
    # mixed-tenant traffic: round-robin over base (0) + every loaded slot
    adapter_ids = [i % (n_adapters + 1) for i in range(len(prompts))]
    # warm compiles off the clock (fleet: one request per replica so every
    # engine's step widths are compiled before traffic)
    for _ in range(len(engine.replicas) if fleet_mode else 1):
        engine.submit(prompts[0])
    engine.run()
    # GKE preemption (SIGTERM) and operator ^C both take the graceful
    # drain; a SECOND ^C chains the default handler so a hung drain stays
    # abortable — the trainer's grace-window pattern.
    with DistributedSignalHandler([signal.SIGTERM, signal.SIGINT]) as h:
        drive_fn = _drive_fleet if fleet_mode else _drive
        drive = drive_fn(engine, prompts, deadline_s=args.deadline_s,
                         max_queue_s=args.max_queue_s,
                         drain_grace_s=args.drain_grace_s
                         if args.drain_grace_s is not None
                         else scfg.drain_grace_s, handler=h,
                         adapter_ids=adapter_ids)
    if fault_spec:
        fi.reset_faults()
    stats = engine.stats()
    outcomes = engine.outcome_counts()
    if fleet_mode:
        engine.teardown()   # retract live-params advertisements
    # the warm-up request is part of self.requests: it finished pre-drive
    not_finished = sum(n for state, n in outcomes.items()
                       if state != "finished")
    dt = drive["wall_s"]
    report = {
        "requests": args.requests,
        "decode_tok_s": round(args.requests * gen.max_new_tokens / dt, 1),
        "wall_s": round(dt, 3),
        "drained": drive["drained"],
        "not_finished": not_finished,
        "timers_ms": {n: round(v * 1e3, 2) for n, v in
                      timers.get_elapsed(names=list(SERVE_TIMERS
                                                    + SERVE_PHASE_TIMERS),
                                         reset=False).items()},
        **stats,
    }
    print(json.dumps(report))
    if not_finished:
        print(f"serve: {not_finished} request(s) did not finish "
              f"(outcomes: {outcomes}) — exiting nonzero for CI",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
