"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference, each number beside a limit of its own.

The limits live in the cell's file (``limits``); ``PERF.md`` gives the
readings each was set from.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from benchmark import common, manifest as mf, trafficgen, weights

KEY = 6     # tokens that identify a document's start (ids are uniform over
            # the vocabulary, so six of them are unique among the documents)


def segment_rows(rows: np.ndarray, docs: List[np.ndarray]):
    """The benchmark's OWN segmentation of packed rows: which of the
    generator's documents lie where.  Returns (labels, positions, segments)
    as the reference takes them; raises if a row is not whole documents of
    the generator, in any order, followed by padding."""
    starts = {tuple(d[:KEY].tolist()): d for d in docs}
    labels = np.full(rows.shape, -100, np.int32)
    pos = np.zeros(rows.shape, np.int32)
    seg = np.zeros(rows.shape, np.int32)
    for r, row in enumerate(rows):
        o, s = 0, 0
        while o < len(row):
            doc = starts.get(tuple(row[o:o + KEY].tolist()))
            if doc is None:
                if np.any(row[o:] != row[o]):
                    raise ValueError(f"row {r}: tokens at {o} are neither a "
                                     "generated document nor padding")
                break
            n = len(doc)
            if not np.array_equal(row[o:o + n], doc):
                raise ValueError(f"row {r}: document at {o} is cut or "
                                 "altered")
            s += 1
            labels[r, o:o + n - 1] = doc[1:]
            pos[r, o:o + n] = np.arange(n)
            seg[r, o:o + n] = s
            o += n
    return labels, pos, seg


def _worst(prog: Dict[Any, float], ref: Dict[Any, float],
           keys: Sequence[Any]):
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def train(config: Dict[str, Any], seed: int, traffic: str,
          followed: List[List[Dict[str, np.ndarray]]],
          program: Dict[str, Any], optimizer: Dict[str, Any],
          limits: Dict[str, float]) -> common.Compared:
    """Follow the program's first steps with the reference and compare the
    first gradient's norm per leaf as the optimizer got it (Adam's first
    moment after one step over 1 - b1) and the norm of each leaf's change
    after the steps followed; each step's loss is printed beside the
    reference's."""
    import jax

    ref = mf.family(config)
    mix = trafficgen.load(traffic)
    docs = mf.traffic_kind(mix).documents(mix, seed, config["vocab_size"])
    words = weights.seed_words(seed)
    make = jax.jit(lambda w: ref.make(config, w))
    follower = ref.TrainReference(make(words), lambda: make(words), config,
                                  optimizer)
    for step, batches in enumerate(followed):
        if len(batches) != 1:
            raise ValueError("the reference follows steps of one microbatch")
        ids = batches[0]["input_ids"]
        labels, pos, seg = segment_rows(ids, docs)
        loss = follower.step(ids, labels, pos, seg)
        common.say(f"step {step + 1}: loss program "
                   f"{program['losses'][step]:.6f} reference {loss:.6f} "
                   f"(lr {program['lrs'][step]:g})")
    common.say("reference seconds " + ", ".join(
        f"{k} {v:.1f}" for k, v in follower.seconds.items()))
    out = common.Compared()
    # The losses are printed above and NOT compared: at a random start the
    # loss is ln(vocabulary) whatever the arithmetic, so neither the int8
    # control nor a planted fault reads three times what sound runs read
    # (PERF.md section 2 has the readings); a limit could only fail sound runs.
    g_ref = follower.grad_norms()
    gap, leaf = _worst(program["grad_norms"], g_ref, list(g_ref))
    common.say(f"worst first-gradient leaf {leaf}: program "
               f"{program['grad_norms'][leaf]:.6g} reference "
               f"{g_ref[leaf]:.6g}")
    out.add("grad_norm_gap", gap, limits["grad_norm_gap"])
    c_ref = follower.change_norms()
    # leaves whose gradient is nought to rounding move under Adam by
    # round-off alone: left out by a rule on the reference's gradient
    floor = 1e-3 * float(np.median(list(g_ref.values())))
    moved = [k for k in c_ref if g_ref[k] >= floor]
    gap, leaf = _worst(program["change_norms"], c_ref, moved)
    common.say(f"worst changed leaf {leaf}: program "
               f"{program['change_norms'][leaf]:.6g} reference "
               f"{c_ref[leaf]:.6g}; {len(c_ref) - len(moved)} leaves left "
               "out for a gradient of nought")
    out.add("change_norm_gap", gap, limits["change_norm_gap"])
    return out


def serve(config: Dict[str, Any], seed: int,
          finished: List[Dict[str, Any]], limits: Dict[str, float],
          sample: int) -> common.Compared:
    """A sample, drawn from the seed, of the requests the window finished,
    the longest among them: the widest gap by which a served token's logit
    lies below the reference's best at its position."""
    import jax

    ref = mf.family(config)
    out = common.Compared()
    if not finished:
        out.add("served_token_gap", float("nan"), limits["served_token_gap"])
        return out
    rng = np.random.default_rng(seed)
    longest = max(range(len(finished)), key=lambda i: len(
        finished[i]["prompt"]) + len(finished[i]["tokens"]))
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    chosen = [longest] + rest[:max(sample - 1, 0)]
    flat = jax.jit(lambda w: ref.make(config, w))(
        weights.seed_words(seed))
    widest, tokens = 0.0, 0
    for i in chosen:
        req = finished[i]
        if len(req["tokens"]) != req["max_new_tokens"]:
            widest = float("nan")   # a request cut short says the wrong thing
            break
        gaps = ref.served_token_gaps(flat, config, req["prompt"],
                                     req["tokens"])
        widest, tokens = max(widest, float(gaps.max())), tokens + len(gaps)
    common.say(f"compared {tokens} served tokens of {len(chosen)} requests "
               f"(longest {len(finished[longest]['prompt'])} + "
               f"{len(finished[longest]['tokens'])})")
    out.add("served_token_gap", widest, limits["served_token_gap"])
    return out
