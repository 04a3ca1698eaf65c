"""Traffic kind ``open_loop`` (serving): requests fall due at times fixed
in advance, whatever the system does: a Poisson stream at the rate the
mix's file fixes, optionally with bursts.  Latency counts from the DUE time.

The window opens on a server that has been running for
``running_since_s``: the stream starts that long before the window, and
``ramp_s`` before the window the requests that such a server would still
hold are sent all at once, aged: the output tokens a request would have
produced by then (its age over ``token_s``, the mix's reckoning of a
token's time) are moved into its prompt as further ids.  A request that
would have finished by then is not sent.  So the window sees the slots and
contexts of a steady state without a ramp as long as the longest request.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import trafficgen

DUE_TIMES = True        # requests have due times: ttft and lateness exist


def _arrival_times(traffic, start: float, end: float) -> np.ndarray:
    """Due times in [start, end): exponential gaps at ``rate_per_s``.
    ``burst`` {every_s, for_s, times}: during the first ``for_s`` of every
    ``every_s`` (counted from the window's opening) the rate is ``times``
    the mix's rate, which it is outside the bursts."""
    rate = float(traffic["rate_per_s"])
    burst = traffic.get("burst")
    peak = rate * (burst["times"] if burst else 1.0)
    n = int((end - start) * peak * 1.5) + 64
    unit = np.cumsum(trafficgen.schedule(traffic, 0).exponential(size=n))
    if not burst:
        t = start + unit / rate
    else:       # a unit-rate stream read through the inverse of the
        #         cumulative rate, which is linear between the bursts' edges
        every, on = float(burst["every_s"]), float(burst["for_s"])
        k = np.arange(np.floor(start / every), np.ceil(end / every) + 1)
        edges = np.sort(np.concatenate([k * every, k * every + on]))
        edges = edges[(edges > start) & (edges < end)]
        edges = np.concatenate([[start], edges, [end]])
        mids = (edges[:-1] + edges[1:]) / 2
        rates = np.where(np.mod(mids, every) < on, peak, rate)
        cum = np.concatenate([[0.0], np.cumsum(rates * np.diff(edges))])
        t = np.interp(unit, cum, edges, right=np.inf)
    if not t[-1] >= end:
        raise ValueError("too few gaps drawn to fill the span")
    return t[t < end]


def generate(traffic: Dict[str, Any], seed: int, vocab_size: int,
             seconds: float) -> List[Dict[str, Any]]:
    """The requests of a run, in order of their due times (the window opens
    at 0 and lasts ``seconds``), each with ``in_window``."""
    ramp = float(traffic["ramp_s"])
    since = float(traffic.get("running_since_s", ramp))
    due = _arrival_times(traffic, -since, seconds)
    n = len(due)
    prompts = trafficgen.lengths(traffic["prompt_len"], n,
                                 trafficgen.schedule(traffic, 1))
    outputs = trafficgen.lengths(traffic["output_len"], n,
                                 trafficgen.schedule(traffic, 2))
    ids = np.random.default_rng(seed)
    out = []
    for t, p, o in zip(due.tolist(), prompts.tolist(), outputs.tolist()):
        done = 0
        if t < -ramp:       # sent at the ramp's start, aged
            done = int((-ramp - t) / float(traffic["token_s"]))
            t = -ramp
        prompt = trafficgen.token_ids(ids, vocab_size, p + done)
        if done < o:
            out.append({"prompt": prompt, "max_new_tokens": o - done,
                        "due": t, "in_window": t >= 0.0})
    return out


def drive(ctx, drive, traffic, opened) -> None:
    """Send each request when it falls due and step the engine meanwhile,
    all in one thread, until the window closes."""
    seconds, engine = ctx["seconds"], drive.engine
    pending = generate(traffic, ctx["seed"], ctx["config"]["vocab_size"],
                       seconds)
    pending.reverse()                           # pop() takes the earliest
    t_zero = time.perf_counter() + traffic["ramp_s"]

    def submit_due(now: float) -> None:
        while pending and t_zero + pending[-1]["due"] <= now:
            spec = pending.pop()
            drive.submit(spec, t_zero + spec["due"])

    is_open = False
    while True:
        now = time.perf_counter()
        if not is_open and now >= t_zero:
            opened(t_zero)
            is_open = True
        if now >= t_zero + seconds:
            break
        submit_due(now)
        if engine.scheduler.has_work():
            drive.step()
        else:
            until = min(t_zero + pending[-1]["due"] if pending
                        else t_zero + seconds, t_zero + seconds)
            if not is_open:
                until = min(until, t_zero)
            with drive.spans.span("wait_arrival"):
                time.sleep(max(0.0, until - now))
    # a request that fell due while the last step ran is sent late, not
    # dropped: every request due in the window is attempted
    submit_due(t_zero + seconds)
