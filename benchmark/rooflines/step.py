"""The whole step's required work: what `mfu.*` divides by the chip's peak.

Only what the algorithm needs counts: matmul parameters at 2 FLOPs per
parameter per position forward (6 with the backward pass for a trained
parameter, 4 for a frozen one: no weight gradient), and attention over the
keys a position may see (within its document, causal).  Recomputation,
padding rows and columns and the embedding lookup are not credited.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List

import numpy as np

from benchmark import manifest as mf


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product, per layer and in the head:
    the configuration's family says (``reference/<model_type>.py``)."""
    return mf.family(cfg).matmul_params(cfg)


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter: matrices, the embedding, the norm weights."""
    shapes = mf.family(cfg).leaf_shapes(cfg)
    return int(sum(np.prod(shape) for shape, _ in shapes.values()))


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per (query, key) pair over all layers: QK^T and PV."""
    return mf.family(cfg).attention_pair_flops(cfg)


def causal_pairs(segments: Iterable[np.ndarray]) -> int:
    """(query, key) pairs of packed rows: within each document, causal."""
    pairs = 0
    for seg in segments:
        for row in np.atleast_2d(seg):
            _, counts = np.unique(row[row != 0], return_counts=True)
            pairs += int(np.sum(counts * (counts + 1) // 2))
    return pairs


def train_flops(cfg: Dict[str, Any], steps: List[List[np.ndarray]],
                trained: bool = True) -> float:
    """Required FLOPs of training steps given each step's segment ids."""
    mm = matmul_params(cfg)
    per_token = (6 if trained else 4) * (mm["layers"] + mm["head"])
    tokens = sum(int(np.count_nonzero(s)) for segs in steps for s in segs)
    pairs = sum(causal_pairs(segs) for segs in steps)
    return per_token * tokens + 3 * attention_pair_flops(cfg) * pairs


def serve_flops(cfg: Dict[str, Any], steps: List[Dict[str, Any]]) -> float:
    """Required FLOPs of engine steps: every real position through the
    layers once, the head once per sampled token, attention over the real
    context each position sees."""
    mm = matmul_params(cfg)
    return sum(2 * mm["layers"] * s["positions"] + 2 * mm["head"] * s["sampled"]
               + attention_pair_flops(cfg) * s["attended"] for s in steps)
