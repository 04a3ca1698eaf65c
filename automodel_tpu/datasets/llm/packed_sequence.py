"""Sequence packing with **segment ids** — the TPU-native encoding.

Re-design of the reference's torchtune-derived packer
(``nemo_automodel/components/datasets/llm/packed_sequence.py:29-334``): the
same ``split_across_pack`` switch, but instead of the reference's 4-D
block-diagonal causal masks
(``create_block_causal_mask``/``packed_block_causal_mask``), each pack emits
``segment_ids`` (1-based per sample; 0 = padding) — the encoding Pallas
flash/splash attention and ``automodel_tpu.ops.attention`` consume directly,
and which survives CP sequence sharding.  Whole documents
(``split_across_pack: false``) are placed by best fit inside a window of the
dataset (:func:`best_fit_rows`) rather than in arrival order, so a row's
slots hold tokens and not padding.
"""

from __future__ import annotations

import bisect
import itertools
import logging
from typing import Dict, List, Optional, Sequence

import numpy as np

from automodel_tpu.datasets.utils import CROSS_ENTROPY_IGNORE_IDX

logger = logging.getLogger(__name__)

PACK_TYPE = Dict[str, List[int]]

# Whole documents are placed inside consecutive windows of this many: on
# heavy-tailed lengths (lognormal, median 600, packed to 4096) 256 leaves
# ~1 % of the slots empty where 128 leaves 2 % and arrival order 18 %, and
# a row never draws from documents further apart, so the dataset's order
# (a curriculum, a sorted mixture) survives at that grain.
WINDOW = 256


def best_fit_rows(lengths: Sequence[int], size: int) -> List[List[int]]:
    """The rows of one window: which documents (indices into ``lengths``)
    share a row of ``size`` slots.  Longest first (ties by index), each into
    the open row with the least room that still holds it (ties by the row
    opened first), else into a new row.  Inside a row the documents stand in
    their own order, and the rows come out by their earliest document.
    Documents of no length are in no row."""
    rows: List[List[int]] = []
    rooms: List[tuple] = []     # (slots left, row), sorted: the open rows
    for i in sorted((i for i, n in enumerate(lengths) if n > 0),
                    key=lambda i: (-lengths[i], i)):
        at = bisect.bisect_left(rooms, (lengths[i], -1))
        if at == len(rooms):
            room, row = size, len(rows)
            rows.append([])
        else:
            room, row = rooms.pop(at)
        rows[row].append(i)
        if room > lengths[i]:
            bisect.insort(rooms, (room - lengths[i], row))
    for row in rows:
        row.sort()
    rows.sort(key=lambda row: row[0])
    return rows


class PackedSequence:
    """Packs samples into rows of ``packed_sequence_size`` slots.

    ``split_across_pack: true`` concatenates the samples in order and cuts
    at every row's end, so no slot is padding.  ``false`` keeps every
    sample whole: the dataset is taken in consecutive windows of
    :data:`WINDOW` samples, and inside a window :func:`best_fit_rows` says
    which samples share a row; nothing carries over between windows, and
    the same dataset gives the same rows.  ``max_packs`` stops after that
    many rows.

    Each pack carries ``input_ids``, ``labels``, ``position_ids`` (restarting
    per sample — RoPE sees each sample from position 0), ``segment_ids``, and
    ``seq_lens``; ``loss_mask`` passes through when present.  After
    :meth:`pack`, ``rows``, ``tokens`` and ``fill`` (tokens over slots) say
    how full the rows came out.
    """

    def __init__(self, dataset, split: str = "train",
                 packed_sequence_size: int = 2048,
                 split_across_pack: bool = False,
                 max_packs: Optional[int] = None,
                 padding_idx: int = 0):
        self.dataset = dataset
        self.split = split
        self.packed_sequence_size = packed_sequence_size
        self.split_across_pack = split_across_pack
        self.max_packs = max_packs
        self.padding_idx = padding_idx
        self.packs: List[PACK_TYPE] = []
        self.packed_dataset: Optional[List[Dict[str, np.ndarray]]] = None
        self.rows = self.tokens = 0
        self.fill = 0.0

    # -- packing -----------------------------------------------------------
    def pack(self):
        contains_loss_mask = "loss_mask" in _first(self.dataset)
        if self.split_across_pack:
            self.packed_dataset = self._pack_split(contains_loss_mask)
        else:
            self.packed_dataset = self._pack_whole(contains_loss_mask)
        self.rows = len(self.packed_dataset)
        self.tokens = sum(int(p["seq_lens"].sum())
                          for p in self.packed_dataset)
        self.fill = self.tokens / max(1, self.rows * self.packed_sequence_size)
        logger.info("Total number of packs created: %d (%d tokens, fill "
                    "%.4f)", self.rows, self.tokens, self.fill)
        return self

    def _pack_split(self, contains_loss_mask: bool):
        size = self.packed_sequence_size
        cur = _empty_pack()
        if contains_loss_mask:
            cur["loss_mask"] = []
        next_seg = 1

        for sample in self.dataset:
            seq_len = len(sample["input_ids"])
            cur["input_ids"] += list(sample["input_ids"])
            cur["labels"] += list(sample["labels"])
            cur["position_ids"] += [p % size for p in range(seq_len)]
            cur["segment_ids"] += [next_seg] * seq_len
            cur["seq_lens"].append(seq_len)
            if contains_loss_mask:
                cur["loss_mask"] += list(sample["loss_mask"])
            next_seg += 1

            while len(cur["input_ids"]) > size and not self._stop():
                cur, next_seg = self._split_and_add(cur, next_seg)
            if self._stop():
                break

        if len(cur["input_ids"]) > 0 and not self._stop():
            self._add(cur)

        return [{k: np.asarray(v, dtype=np.int32) for k, v in pack.items()}
                for pack in self.packs]

    def _pack_whole(self, contains_loss_mask: bool):
        """Place the documents (:func:`best_fit_rows`, window by window),
        then lay the rows out: in C++ (``automodel_tpu/native``) where
        there is a compiler and no ``loss_mask`` to carry, else here."""
        from automodel_tpu import native

        size = self.packed_sequence_size
        docs, counts = [], []       # documents in row order; how many a row
        samples = iter(self.dataset)
        while self.max_packs is None or len(counts) < self.max_packs:
            window = list(itertools.islice(samples, WINDOW))
            if not window:
                break
            lengths = [len(s["input_ids"]) for s in window]
            if max(lengths) > size:
                raise ValueError(
                    f"Dataset sample is too long ({max(lengths)} > {size}). "
                    "Set split_across_pack=True or increase "
                    "packed_sequence_size.")
            for row in best_fit_rows(lengths, size):
                docs += [window[i] for i in row]
                counts.append(len(row))
        if self.max_packs is not None:
            del counts[self.max_packs:]
            del docs[sum(counts):]
        lengths = np.asarray([len(d["input_ids"]) for d in docs], np.int32)
        if native.available() and not contains_loss_mask:
            return self._lay_out_native(docs, lengths, counts)
        return self._lay_out(docs, lengths, counts, contains_loss_mask)

    def _lay_out_native(self, docs, lengths, counts):
        from automodel_tpu.native.build import pack_rows

        out = pack_rows(
            lengths, counts,
            np.concatenate([np.asarray(d["input_ids"], np.int32)
                            for d in docs]),
            np.concatenate([np.asarray(d["labels"], np.int32) for d in docs]),
            self.packed_sequence_size, self.padding_idx,
            CROSS_ENTROPY_IGNORE_IDX)
        seq_lens = np.split(lengths, np.cumsum(counts)[:-1])
        return [{k: out[k][i] for k in ("input_ids", "labels",
                                        "position_ids", "segment_ids")}
                | {"seq_lens": seq_lens[i]} for i in range(len(counts))]

    def _lay_out(self, docs, lengths, counts, contains_loss_mask: bool):
        size = self.packed_sequence_size

        def padded(row, key, fill):
            flat = np.concatenate([np.asarray(d[key], np.int32) for d in row])
            return np.pad(flat, (0, size - len(flat)), constant_values=fill)

        packs, at = [], 0
        for count in counts:
            row, lens = docs[at:at + count], lengths[at:at + count]
            at += count
            used = int(lens.sum())
            # positions restart per document; padding keeps counting (it is
            # masked out by segment 0 either way)
            pos = np.arange(size, dtype=np.int32)
            pos[:used] -= np.repeat(np.cumsum(lens) - lens, lens)
            seg = np.zeros(size, np.int32)
            seg[:used] = np.repeat(np.arange(1, count + 1, dtype=np.int32),
                                   lens)
            pack = {"input_ids": padded(row, "input_ids", self.padding_idx),
                    "labels": padded(row, "labels", CROSS_ENTROPY_IGNORE_IDX),
                    "position_ids": pos, "segment_ids": seg,
                    "seq_lens": lens}
            if contains_loss_mask:
                pack["loss_mask"] = padded(row, "loss_mask", 0)
            packs.append(pack)
        return packs

    def _stop(self) -> bool:
        return self.max_packs is not None and len(self.packs) >= self.max_packs

    def _split_and_add(self, cur: PACK_TYPE, next_seg: int):
        """Cut ``cur`` at the row's end: the row goes out, the rest of the
        sample that straddles the cut opens the next."""
        size = self.packed_sequence_size
        leftover = size - sum(cur["seq_lens"][:-1])
        seq_lens = cur["seq_lens"][:-1] + ([leftover] if leftover > 0 else [])
        pack = {k: cur[k][:size] for k in cur if k != "seq_lens"}
        pack["seq_lens"] = seq_lens
        self._add(pack)

        rest = {k: cur[k][size:] for k in cur if k != "seq_lens"}
        n = len(rest["input_ids"])
        # the continuation gets its own fresh segment id (consuming
        # next_seg, so the next appended sample cannot collide with it) and
        # restarted positions
        rest["seq_lens"] = [n]
        rest["position_ids"] = [p % size for p in range(n)]
        rest["segment_ids"] = [next_seg] * n
        return rest, next_seg + 1

    def _add(self, pack: PACK_TYPE) -> None:
        """Pad to packed_sequence_size and renumber segments densely from 1."""
        size = self.packed_sequence_size
        n = len(pack["input_ids"])
        pad = size - n
        out = dict(pack)
        if pad > 0:
            out["input_ids"] = pack["input_ids"] + [self.padding_idx] * pad
            out["labels"] = pack["labels"] + [CROSS_ENTROPY_IGNORE_IDX] * pad
            out["position_ids"] = pack["position_ids"] + [p % size for p in range(n, size)]
            out["segment_ids"] = pack["segment_ids"] + [0] * pad   # 0 = padding
            if "loss_mask" in pack:
                out["loss_mask"] = pack["loss_mask"] + [0] * pad
        remap: Dict[int, int] = {}
        seg = []
        for s in out["segment_ids"]:
            if s == 0:
                seg.append(0)
            else:
                remap.setdefault(s, len(remap) + 1)
                seg.append(remap[s])
        out["segment_ids"] = seg
        self.packs.append(out)

    # -- dataset protocol --------------------------------------------------
    def __len__(self) -> int:
        assert self.packed_dataset is not None, "call .pack() first"
        return len(self.packed_dataset)

    def __getitem__(self, idx: int) -> Dict[str, np.ndarray]:
        assert self.packed_dataset is not None, "call .pack() first"
        item = dict(self.packed_dataset[idx])
        item.pop("seq_lens", None)
        return item


def _empty_pack() -> PACK_TYPE:
    return {"input_ids": [], "labels": [], "position_ids": [],
            "segment_ids": [], "seq_lens": []}


def _first(dataset):
    for x in dataset:
        return x
    raise ValueError("empty dataset")
