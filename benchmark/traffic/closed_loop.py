"""Traffic kind ``closed_loop`` (serving): a fixed number of clients, each
sending its next request when its last completes.  No request has a due
time, so there is no time to first token from one; the loop is judged by
the tokens it completes."""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import trafficgen

DUE_TIMES = False


def generate(traffic: Dict[str, Any], seed: int,
             vocab_size: int) -> List[List[Dict[str, Any]]]:
    """One list of ``rounds`` requests per client.  Each client's FIRST
    request starts part-way through: a share of its output, drawn from the
    schedule, is moved into its prompt as further ids, so that after a
    short ramp the clients are spread over their cycle, with the contexts
    of a loop that has run for long, and not aligned at its start."""
    clients, rounds = traffic["clients"], traffic["rounds"]
    n = clients * rounds
    prompts = trafficgen.lengths(traffic["prompt_len"], n,
                                 trafficgen.schedule(traffic, 1))
    outputs = trafficgen.lengths(traffic["output_len"], n,
                                 trafficgen.schedule(traffic, 2))
    share = trafficgen.schedule(traffic, 3).random(clients)
    ids = np.random.default_rng(seed)
    per_client = []
    for c in range(clients):
        mine = []
        for r in range(rounds):
            p, o = int(prompts[c * rounds + r]), int(outputs[c * rounds + r])
            done = min(int(o * share[c]), o - 1) if r == 0 else 0
            mine.append({
                "prompt": trafficgen.token_ids(ids, vocab_size, p + done),
                "max_new_tokens": o - done})
        per_client.append(mine)
    return per_client


def drive(ctx, drive, traffic, opened) -> None:
    seconds = ctx["seconds"]
    queues = generate(traffic, ctx["seed"], ctx["config"]["vocab_size"])
    for q in queues:
        q.reverse()
    t_zero = time.perf_counter() + traffic["ramp_s"]
    for c, q in enumerate(queues):
        drive.submit(q.pop(), time.perf_counter(), client=c)
    is_open = False
    while True:
        now = time.perf_counter()
        if not is_open and now >= t_zero:
            opened(t_zero)
            is_open = True
        if now >= t_zero + seconds:
            break
        for rec in drive.step():
            q = queues[rec["client"]]
            if not q:
                raise SystemExit("a client ran out of requests: raise "
                                 "rounds in the traffic file")
            drive.submit(q.pop(), time.perf_counter(), client=rec["client"])
