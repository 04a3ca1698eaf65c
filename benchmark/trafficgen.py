"""The one traffic generator's general part.  A traffic mix is a data file
under ``benchmark/traffic/``: ``<mix>.json`` names a ``kind`` and gives its
parameters (lengths, rate, bursts, ramp, clients, the schedule's seed) and
the public source they were set from.  The code of a kind is
``traffic/<kind>.py``, found by that name; a later cell of a kind that is
there adds a data file and nothing else.

Every seed does the SAME work.  Lengths and arrival gaps are independent
random draws (lognormal or from a table of quantiles; exponential gaps),
but from the mix's own fixed ``schedule_seed`` and not from ``--seed``:
which request comes when, how long it is and which client gets it are one
schedule for every seed, bunched as chance bunched it.  ``--seed`` draws
the token ids (and, elsewhere, the weights).  So a run-to-run spread is
the system's, not the draw's.  (With ``--seed`` dealing the order, six
seeds of a chat mix read 294-310 tokens/s and 4.5-4.9 s at the 95th
percentile while two runs of one seed agreed to 1 %: PERF.md section 6.)
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import manifest as mf

IGNORE = -100


def load(name: str) -> Dict[str, Any]:
    return mf.read_json("traffic", name + ".json")


def schedule(traffic: Dict[str, Any], stream: int) -> np.random.Generator:
    """Generator number ``stream`` of the mix's schedule.  Each quantity
    (gaps, prompt lengths, output lengths, ...) has a stream of its own, so
    a shorter window is the start of a longer one."""
    return np.random.default_rng([int(traffic["schedule_seed"]), stream])


def lengths(spec: Dict[str, Any], n: int,
            rng: np.random.Generator) -> np.ndarray:
    """n whole numbers drawn independently from the stated distribution,
    clipped to ``min``..``max``.  ``lognormal``: ``median`` and ``sigma``.
    ``table``: ``quantiles`` as [[share, value], ...] rising from share 0
    to 1, read by linear interpolation (any measured distribution)."""
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif spec["dist"] == "table":
        q = np.asarray(spec["quantiles"], float)
        x = np.interp(rng.random(n), q[:, 0], q[:, 1])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def token_ids(rng: np.random.Generator, vocab_size: int, n: int) -> list:
    """n ids uniform over the vocabulary (0 is left to padding)."""
    return rng.integers(1, vocab_size, int(n), dtype=np.int32).tolist()
