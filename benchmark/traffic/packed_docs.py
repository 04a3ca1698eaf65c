"""Traffic kind ``packed_docs`` (training): documents for the recipe's own
dataset -> packer -> loader path.  A cell's recipe names :func:`build` as
its ``dataset._target_``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark import trafficgen


def documents(traffic: Dict[str, Any], seed: int,
              vocab_size: int) -> List[np.ndarray]:
    """``num_docs`` documents whose lengths are drawn from the mix's
    schedule, so that they come in ONE order for every seed: the program's
    packer makes the same rows and a window trains the same rows whatever
    the seed.  The seed draws the ids."""
    lens = trafficgen.lengths(traffic["doc_len"], traffic["num_docs"],
                              trafficgen.schedule(traffic, 0))
    ids = np.random.default_rng(seed).integers(
        1, vocab_size, int(lens.sum()), dtype=np.int32)
    return np.split(ids, np.cumsum(lens)[:-1])


def build(*, traffic: str, seed: int, vocab_size: int,
          tokenizer=None) -> List[Dict[str, np.ndarray]]:
    """One example per document, labels shifted inside the document (its
    last token has none).  The recipe's own packer and loader take it from
    here."""
    out = []
    for doc in documents(trafficgen.load(traffic), seed, vocab_size):
        labels = np.empty_like(doc)
        labels[:-1], labels[-1] = doc[1:], trafficgen.IGNORE
        out.append({"input_ids": doc, "labels": labels})
    return out
