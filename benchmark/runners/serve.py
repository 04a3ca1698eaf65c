"""Runner ``serve``: the decode engine as ``tools/serve.py`` builds it
(``build_model`` -> weights made by one jitted call -> ``build_serving_config``
-> ``DecodeEngine``), driven by ``engine.submit()`` and ``engine.step()`` in
one thread.

The loop that offers the load is the traffic kind's (``traffic/<kind>.py``:
``drive``), found by the name in the mix's file; it sends requests through
:class:`Drive`, which stamps them.  Where the kind's requests have due
times (``DUE_TIMES``), latency counts from the DUE time, and how late the
generator ran is reported.  A ramp before the window brings the slots to
steady occupancy; it is set-up the traffic needs.  A token is delivered
when the ``engine.step()`` that produced it returns.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

from benchmark import check, common, manifest as mf, trafficgen, weights

DRAIN_S = 60.0      # wait this long past the close for first tokens due


def _build_engine(ctx):
    import jax

    from automodel_tpu.generation import GenerationConfig
    from automodel_tpu.models.auto_model import build_model
    from automodel_tpu.serving import DecodeEngine, build_serving_config

    config, seed = ctx["config"], ctx["seed"]
    family = mf.family(config)
    model = build_model(config=family.model_config(config))
    if ctx.get("control"):
        # the control: the program's own lower-precision path, switched on
        from automodel_tpu.quantization.fp8 import (
            apply_fp8_to_model,
            build_fp8_config,
        )

        apply_fp8_to_model(model, build_fp8_config(
            enabled=True, dtype="int8", recipe_name="tensorwise"))
    # wait for it: the maker's float32 temporaries are gone only then, and
    # the pools that come next need the room
    params = jax.block_until_ready(jax.jit(
        lambda w: family.to_program_tree(family.make(config, w)))(
            weights.seed_words(seed)))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != want:
        raise SystemExit(f"{family.__name__} does not make the program's "
                         f"parameter tree: {got} != {want}")
    scfg = build_serving_config(dict(ctx["cell_file"]["serving"]))
    gen = GenerationConfig(max_new_tokens=scfg.max_model_len,
                           do_sample=False, eos_token_id=None)
    return DecodeEngine(model, params, scfg, generation=gen)


class Drive:
    """The load generator and the stamps, for both loops."""

    def __init__(self, ctx, engine):
        self.ctx, self.engine = ctx, engine
        self.spans: common.Spans = ctx["spans"]
        self.reqs: Dict[int, Dict[str, Any]] = {}    # rid -> record
        self.live: Dict[int, Dict[str, Any]] = {}    # not finished yet
        self.steps: List[Dict[str, Any]] = []
        self.plan = None
        inner = engine.scheduler.schedule

        def schedule(*a, **k):
            self.plan = inner(*a, **k)
            return self.plan

        engine.scheduler.schedule = schedule

    def submit(self, spec: Dict[str, Any], due: float, client=None) -> None:
        now = time.perf_counter()
        rid = self.engine.submit(spec["prompt"],
                                 max_new_tokens=spec["max_new_tokens"],
                                 eos_token_id=None)
        rec = {"rid": rid, "due": due, "submitted": now, "client": client,
               "in_window": bool(spec.get("in_window")),
               "prompt": spec["prompt"],
               "max_new_tokens": spec["max_new_tokens"],
               "scheduled": None, "token_times": [], "tokens": None,
               "req": self.engine.requests[rid]}
        self.reqs[rid] = self.live[rid] = rec

    def step(self) -> List[Dict[str, Any]]:
        """One engine step; returns the records that finished on it."""
        t0 = time.perf_counter()
        self.plan = None
        with self.spans.span("engine_step"):
            self.engine.step()
        t1 = time.perf_counter()
        plan = self.plan
        if plan is not None:
            active = plan.active
            self.steps.append({
                "t0": t0, "t1": t1, "width": plan.step_width,
                "rows": len(active),
                "positions": sum(len(w.tokens) for w in active),
                "context": sum(w.start_pos + len(w.tokens) for w in active),
                # sum over this step's new positions of the keys each sees
                "attended": sum(
                    len(w.tokens) * w.start_pos
                    + len(w.tokens) * (len(w.tokens) + 1) // 2
                    for w in active),
                "sampled": sum(1 for w in active if w.samples_next)})
            for w in active:
                rec = self.live.get(w.req.rid)
                if rec is not None and rec["scheduled"] is None:
                    rec["scheduled"] = t0
        done = []
        for rid, rec in list(self.live.items()):
            req = rec["req"]
            have = len(req.out_tokens)
            if have > len(rec["token_times"]):
                rec["token_times"].extend(
                    [t1] * (have - len(rec["token_times"])))
            if req.finished:
                rec["tokens"] = list(req.out_tokens)
                rec["state"] = req.state.name
                del self.live[rid], rec["req"]
                done.append(rec)
        return done


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config = ctx["cell_file"], ctx["config"]
    traffic = trafficgen.load(ctx["cell"]["traffic"])
    kind = mf.traffic_kind(traffic)
    engine = _build_engine(ctx)
    drive = Drive(ctx, engine)
    counter: common.CompileCounter = ctx["compiles"]

    # warm up the cell's step widths and nothing else: one short request
    # takes a prefill-width step and then decode-width steps
    with drive.spans.span("warmup"):
        drive.submit({"prompt": [1] * (engine.config.prefill_chunk + 1),
                      "max_new_tokens": 4}, time.perf_counter())
        while engine.scheduler.has_work():
            drive.step()
    drive.reqs.clear()
    drive.steps.clear()
    st: Dict[str, Any] = {}

    def opened(t_zero: float) -> None:
        st["compiles_at_open"] = counter.count
        if ctx["trace"]:
            common.start_trace(ctx["trace_dir"])
        st["t_open"] = time.perf_counter()
        ctx["setup_s"] = st["t_open"] - ctx["t_start"]

    kind.drive(ctx, drive, traffic, opened)
    t_close = time.perf_counter()
    waiting_at_close = len(engine.scheduler.waiting)
    if ctx["trace"]:
        ctx["xplane"] = common.stop_trace(ctx["trace_dir"])
    compiles = counter.count - st["compiles_at_open"]
    t_open = st["t_open"]
    window = t_close - t_open
    ctx["memory_peak_bytes"] = (common.peak_memory_bytes()
                                if ctx["on_chip"] else 0)

    # Past the close: no new request, but every request that was due in the
    # window is waited for until its first token (late is late, not
    # missing); a closed loop's requests have no due time to wait for.
    due_in = [r for r in drive.reqs.values() if r["in_window"]]
    deadline = t_close + DRAIN_S
    if kind.DUE_TIMES:
        while (any(not r["token_times"] for r in due_in)
               and engine.scheduler.has_work()
               and time.perf_counter() < deadline):
            drive.step()
    if ctx["on_chip"]:
        ctx["rungs"] = common.check_rungs(cell["expected_rungs"],
                                          cell["forbidden_rungs"])
    stats = engine.stats()
    common.say(f"engine stats: steps {stats['steps']} mixed "
               f"{stats['mixed_steps']} decode {stats['decode_steps']} "
               f"admissions {stats['admissions']} preemptions "
               f"{stats['preemptions']} outcomes {stats['outcomes']} "
               f"kv_blocks_peak {stats['kv_blocks_peak']}")

    records = list(drive.reqs.values())
    delivered = [t for r in records for t in r["token_times"]
                 if t_open <= t <= t_close]
    gaps = [b - a for r in records
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if t_open <= b <= t_close]
    steps = [s for s in drive.steps if t_open <= s["t0"] and s["t1"] <= t_close]
    finished = [r for r in records
                if r.get("state") == "FINISHED" and r["token_times"]
                and r["token_times"][-1] >= t_open]
    failed = sum(1 for r in records
                 if r.get("state") not in (None, "FINISHED"))
    if kind.DUE_TIMES:
        failed += sum(1 for r in due_in if not r["token_times"])
    ctx["window"] = {
        "t_open": t_open, "t_close": t_close, "seconds": window,
        "steps": steps, "records": records, "due_in": due_in,
        "delivered": len(delivered), "compiles_in_window": compiles,
        "max_num_seqs": engine.config.max_num_seqs,
        "kv_block_size": engine.config.kv_block_size,
    }
    mixed = sum(1 for s in steps if s["width"] > 1)
    for label, rows in (("decode", [s for s in steps if s["width"] == 1]),
                        ("mixed", [s for s in steps if s["width"] > 1])):
        if rows:
            ms = sorted(1e3 * (s["t1"] - s["t0"]) for s in rows)
            common.say(f"{label} steps: {len(rows)}, median "
                       f"{ms[len(ms) // 2]:.3f} ms, mean "
                       f"{sum(ms) / len(ms):.3f} ms, most {ms[-1]:.3f} ms")
    common.say(f"window {window:.3f} s: {len(steps)} steps ({mixed} mixed), "
               f"{len(delivered)} tokens delivered, {len(due_in)} requests "
               f"due, {len(finished)} finished, waiting at close "
               f"{waiting_at_close}, compiles in window "
               f"{compiles}, rate {traffic.get('rate_per_s')} of knee "
               f"{traffic.get('knee_per_s')}")
    e2e = {"serve_tok_s": len(delivered) / window,
           "itl_p95_ms": 1e3 * common.quantile(gaps, 0.95) if gaps
           else float("nan")}
    if kind.DUE_TIMES:
        ttft = [r["token_times"][0] - r["due"] for r in due_in
                if r["token_times"]]
        ctx["window"]["waiting_at_close"] = waiting_at_close
        # a request that never got its first token counts as missing: it
        # takes the drain's whole wait
        ttft += [DRAIN_S] * (len(due_in) - len(ttft))
        e2e["ttft_p95_ms"] = 1e3 * common.quantile(ttft, 0.95)
    sample = [{k: r[k] for k in ("prompt", "tokens", "max_new_tokens")}
              for r in finished]
    attempted = len(due_in) if kind.DUE_TIMES else len(
        [r for r in records if r["token_times"]
         and r["token_times"][-1] >= t_open])
    # free the engine (params and pools) before the reference makes its own
    del engine, drive
    gc.collect()

    t0 = time.perf_counter()
    ctx["compared"] = check.serve(config, ctx["seed"], sample,
                                  cell["limits"], cell["check_sample"])
    common.say(f"reference ran in {time.perf_counter() - t0:.1f} s")
    return {"attempted": attempted, "failed": failed, "end_to_end": e2e}
