"""Pretraining dataset over nanogpt-style ``.bin`` token shards.

Reference parity: ``nemo_automodel/components/datasets/llm/nanogpt_dataset.py``
— header ``int32[256]`` with magic 278895051 (new, ``header[3]`` = token
itemsize) or 20240520 (legacy uint16), version 1, token count at
``header[2]``; optional ``.bos.idx`` sidecar caches BOS-aligned window
starts; shards and windows are split across (process, dataloader-worker)
just like the reference's (DDP rank × worker) split.
"""

from __future__ import annotations

import glob as globlib
import os
from typing import Iterator, List, Optional

import numpy as np

MAGIC = 278895051
LEGACY_MAGIC = 20240520
VERSION = 1
HEADER_SIZE = 256  # int32s


def _peek_num_tokens(path: str) -> int:
    header = np.memmap(path, dtype=np.int32, mode="r", shape=(HEADER_SIZE,))
    assert header[0] in (MAGIC, LEGACY_MAGIC), f"{path} magic mismatch ({header[0]})"
    return int(header[2])


def _token_dtype(n_bytes: int):
    if n_bytes == 2:
        return np.uint16
    if n_bytes == 4:
        return np.uint32
    raise ValueError(f"Expected itemsize 2 or 4, got {n_bytes}")


def load_shard(path: str) -> np.ndarray:
    """Memory-map a .bin shard's tokens (header validated)."""
    header = np.memmap(path, dtype=np.int32, mode="r", shape=(HEADER_SIZE,))
    assert header[0] in (MAGIC, LEGACY_MAGIC), f"{path} magic mismatch ({header[0]})"
    assert header[1] == VERSION, f"{path} version mismatch ({header[1]})"
    num_tokens = int(header[2])
    dtype = np.uint16 if header[0] == LEGACY_MAGIC else _token_dtype(int(header[3]))
    offset = HEADER_SIZE * 4
    return np.memmap(path, dtype=dtype, mode="r", offset=offset,
                     shape=(num_tokens,))


def write_shard(path: str, tokens: np.ndarray) -> None:
    """Write tokens in the new .bin format (used by the data processor tool)."""
    tokens = np.asarray(tokens)
    dtype = np.uint32 if tokens.max(initial=0) >= 2 ** 16 else np.uint16
    tokens = tokens.astype(dtype)
    header = np.zeros(HEADER_SIZE, dtype=np.int32)
    header[0] = MAGIC
    header[1] = VERSION
    header[2] = len(tokens)
    header[3] = tokens.dtype.itemsize
    with open(path, "wb") as f:
        f.write(header.tobytes())
        f.write(tokens.tobytes())


class NanogptDataset:
    """Iterable dataset yielding ``{"input_ids", "labels"}`` windows.

    Windows are ``seq_len + 1`` tokens, shifted into input/label pairs.
    ``bos_token``: when set, windows are aligned to BOS boundaries using a
    cached ``.bos.idx`` sidecar (built on first use).
    """

    def __init__(
        self,
        file_pattern: str,
        seq_len: int = 1024,
        shuffle_files: bool = False,
        align_to_bos: bool = False,
        bos_token: Optional[int] = None,
        *,
        rank: Optional[int] = None,
        world_size: Optional[int] = None,
    ):
        self.files: List[str] = sorted(globlib.glob(file_pattern))
        if not self.files:
            raise FileNotFoundError(f"No files match {file_pattern!r}")
        self.seq_len = seq_len
        self.shuffle_files = shuffle_files
        self.align_to_bos = align_to_bos
        self.bos_token = bos_token
        if align_to_bos:
            assert bos_token is not None, "align_to_bos requires bos_token"
        if rank is None:
            import jax

            rank = jax.process_index()
            world_size = jax.process_count()
        self.rank = rank
        self.world_size = world_size or 1

    # -- BOS sidecar -------------------------------------------------------
    def _bos_starts(self, path: str, tokens: np.ndarray) -> np.ndarray:
        sidecar = path + ".bos.idx"
        if os.path.exists(sidecar):
            return np.fromfile(sidecar, dtype=np.int64)
        starts = np.flatnonzero(
            np.asarray(tokens) == self.bos_token).astype(np.int64)
        try:
            starts.tofile(sidecar)
        except OSError:
            pass  # read-only data dir: recompute next time
        return starts

    def __iter__(self) -> Iterator[dict]:
        files = list(self.files)
        if self.shuffle_files:
            rng = np.random.default_rng(1234)
            rng.shuffle(files)
        need = self.seq_len + 1
        # round-robin interleave: (process, worker) strides over windows
        stride_id, n_strides = self.rank, self.world_size
        widx = 0
        for path in files:
            tokens = load_shard(path)
            if self.align_to_bos:
                starts = self._bos_starts(path, tokens)
                for s in starts:
                    if s + need > len(tokens):
                        break
                    if widx % n_strides == stride_id:
                        window = np.asarray(tokens[s:s + need], dtype=np.int64)
                        yield {
                            "input_ids": window[:-1].astype(np.int32),
                            "labels": window[1:].astype(np.int32),
                        }
                    widx += 1
            else:
                n_windows = (len(tokens) - 1) // self.seq_len
                for w in range(n_windows):
                    if widx % n_strides == stride_id:
                        s = w * self.seq_len
                        window = np.asarray(tokens[s:s + need], dtype=np.int64)
                        yield {
                            "input_ids": window[:-1].astype(np.int32),
                            "labels": window[1:].astype(np.int32),
                        }
                    widx += 1

    def __len__(self) -> int:
        need = self.seq_len + 1
        total = 0
        for path in self.files:
            if self.align_to_bos:
                tokens = load_shard(path)
                starts = self._bos_starts(path, tokens)
                total += int(np.sum(starts + need <= len(tokens)))
            else:
                total += (_peek_num_tokens(path) - 1) // self.seq_len
        # round-robin split: first (total % world_size) strides get one extra
        base, extra = divmod(total, self.world_size)
        return base + (1 if self.rank < extra else 0)
