"""Build + bind the native core.

Compilation happens once per (source hash, compiler) into
``_build/libampack-<hash>.so`` next to this file; concurrent builders race
benignly (atomic rename).  No pybind11 in this environment — the ABI is
plain C called through ctypes (see ``src/packing.cpp``).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import tempfile
from typing import Optional

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "packing.cpp")
_BUILD_DIR = os.path.join(_DIR, "_build")

_lib: Optional[ctypes.CDLL] = None
_tried = False
# how this process got the library: "built" (compiled here from
# src/packing.cpp), "cached" (an existing _build/*.so of the same source
# hash), "python" (no compiler: the numpy row layout and collater are the
# path)
_source: Optional[str] = None


def _compiler() -> Optional[str]:
    for cc in (os.environ.get("CXX"), "g++", "clang++"):
        if cc and shutil.which(cc):
            return cc
    return None


def _so_path(cc: str) -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + cc.encode()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libampack-{digest}.so")


def _bind(dll: ctypes.CDLL) -> ctypes.CDLL:
    i32p = ctypes.POINTER(ctypes.c_int32)
    dll.am_pack_rows.restype = ctypes.c_int32
    dll.am_pack_rows.argtypes = [
        i32p, i32p, ctypes.c_int64, i32p, i32p,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        i32p, i32p, i32p, i32p,
    ]
    dll.am_collate_pad.restype = ctypes.c_int32
    dll.am_collate_pad.argtypes = [
        i32p, i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, i32p,
    ]
    return dll


def lib() -> Optional[ctypes.CDLL]:
    """The bound native library, or None when no C++ compiler is on PATH
    (the one case where the Python reference path is the path).  A compiler
    that fails to build, or a library that fails to load, raises: those are
    bugs, not a reason to run a different input pipeline in silence."""
    global _lib, _tried, _source
    if _lib is not None or _tried:
        return _lib
    _tried = True
    cc = _compiler()
    if cc is None:
        _source = "python"
        logger.warning("native core disabled (no C++ compiler on PATH): "
                       "the numpy row layout and collater run instead")
        return None
    so = _so_path(cc)
    _source = "cached"
    if not os.path.exists(so):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
        os.close(fd)
        cmd = [cc, "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)  # atomic: racing builders converge
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native core build failed: {' '.join(cmd)}\n"
                f"{e.stderr.decode(errors='replace')}") from e
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        _source = "built"
    _lib = _bind(ctypes.CDLL(so))
    logger.info("native core %s: %s", _source, so)
    return _lib


def source() -> str:
    """How this process got the library: built | cached | python (see
    ``_source``)."""
    lib()
    return _source


def available() -> bool:
    return lib() is not None


def _i32ptr(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def pack_rows(lengths, counts, ids, labels, pack_size: int, pad_id: int,
              ignore_index: int):
    """numpy front-end for am_pack_rows: documents already in row order
    (``lengths`` per document, ``counts`` documents per row, ``ids`` and
    ``labels`` concatenated) laid out as a dict of [n_rows, pack_size] int32
    arrays, or None when the native core is unavailable."""
    import numpy as np

    dll = lib()
    if dll is None:
        return None
    lengths = np.ascontiguousarray(lengths, np.int32)
    counts = np.ascontiguousarray(counts, np.int32)
    ids = np.ascontiguousarray(ids, np.int32)
    labels = np.ascontiguousarray(labels, np.int32)
    if (counts.sum() != len(lengths) or counts.min(initial=0) < 0
            or not lengths.sum() == len(ids) == len(labels)):
        raise ValueError(
            f"{len(lengths)} documents of {lengths.sum()} tokens do not "
            f"match counts summing to {counts.sum()}, {len(ids)} ids and "
            f"{len(labels)} labels")
    out = {k: np.empty((len(counts), pack_size), np.int32)
           for k in ("input_ids", "labels", "position_ids", "segment_ids")}
    rc = dll.am_pack_rows(
        _i32ptr(lengths), _i32ptr(counts), len(counts), _i32ptr(ids),
        _i32ptr(labels), pack_size, pad_id, ignore_index,
        _i32ptr(out["input_ids"]), _i32ptr(out["labels"]),
        _i32ptr(out["position_ids"]), _i32ptr(out["segment_ids"]))
    if rc != 0:
        raise ValueError(
            f"a row's documents exceed packed_sequence_size={pack_size}")
    return out


def collate_pad(rows, max_len: int, pad_value: int):
    """Pad a list of int sequences to [n, max_len] int32, or None when the
    native core is unavailable."""
    import numpy as np

    dll = lib()
    if dll is None:
        return None
    lengths = np.asarray([len(r) for r in rows], np.int32)
    flat = (np.concatenate([np.asarray(r, np.int32) for r in rows])
            if len(rows) else np.empty((0,), np.int32))
    flat = np.ascontiguousarray(flat)
    out = np.empty((len(rows), max_len), np.int32)
    rc = dll.am_collate_pad(_i32ptr(flat), _i32ptr(lengths), len(rows),
                            max_len, pad_value, _i32ptr(out))
    if rc != 0:
        raise ValueError(f"row longer than max_len={max_len}")
    return out
