"""Native C++ data-plane core vs the Python reference implementations."""

import numpy as np
import pytest

from automodel_tpu import native
from automodel_tpu.datasets.llm.packed_sequence import PackedSequence

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no C++ toolchain")


def _dataset(lengths, seed=0, loss_mask=False):
    rng = np.random.default_rng(seed)
    out = []
    for ln in lengths:
        ids = rng.integers(1, 1000, int(ln)).tolist()
        out.append({"input_ids": ids, "labels": [t + 1 for t in ids]})
        if loss_mask:
            out[-1]["loss_mask"] = [t % 2 for t in ids]
    return out


def _python_rows(ds, monkeypatch, **kw):
    """The rows laid out by the numpy path, the one that runs where there
    is no compiler."""
    with monkeypatch.context() as m:
        m.setattr(native, "available", lambda: False)
        return PackedSequence(ds, packed_sequence_size=64, **kw).pack()


def _native_rows(ds, monkeypatch, **kw):
    """The rows laid out by ``am_pack_rows``; fails if it was not called."""
    from automodel_tpu.native import build

    calls = []
    with monkeypatch.context() as m:
        real = build.pack_rows
        m.setattr(build, "pack_rows",
                  lambda *a, **k: calls.append(1) or real(*a, **k))
        ps = PackedSequence(ds, packed_sequence_size=64, **kw).pack()
    assert calls == [1]
    return ps


def _assert_same_rows(a, b, keys=None):
    assert len(a) == len(b)
    for i in range(len(a)):
        x, y = a.packed_dataset[i], b.packed_dataset[i]
        assert list(x) == list(y)[:len(x)]      # loss_mask, if any, last
        for k in keys or x:
            assert x[k].dtype == y[k].dtype == np.int32
            np.testing.assert_array_equal(x[k], y[k], err_msg=f"pack {i} {k}")


DISTRIBUTIONS = {
    "uniform": lambda rng: rng.integers(0, 49, 700),
    "heavy_tailed": lambda rng: np.clip(
        np.rint(9 * np.exp(1.2 * rng.standard_normal(900))), 1, 64),
    "two_sizes": lambda rng: rng.choice([5, 40], 2 * 256 + 11),
}


@pytest.mark.parametrize("name", sorted(DISTRIBUTIONS))
def test_native_packer_matches_python(name, monkeypatch):
    lengths = DISTRIBUTIONS[name](np.random.default_rng(7))
    ds = _dataset(lengths)
    nat = _native_rows(ds, monkeypatch)
    py = _python_rows(ds, monkeypatch)
    _assert_same_rows(nat, py)
    assert (nat.rows, nat.tokens, nat.fill) == (py.rows, py.tokens, py.fill)
    assert nat.tokens == int(lengths.sum())

    # max_packs: the same placement, stopped after that many rows, natively
    capped = _native_rows(ds, monkeypatch, max_packs=9)
    assert len(capped) == 9
    _assert_same_rows(capped, _python_rows(ds, monkeypatch, max_packs=9))
    for i in range(9):
        np.testing.assert_array_equal(capped[i]["input_ids"],
                                      nat[i]["input_ids"])

    # loss_mask: laid out in Python, by the same placement
    masked = PackedSequence(_dataset(lengths, loss_mask=True),
                            packed_sequence_size=64).pack()
    _assert_same_rows(nat, masked, keys=("input_ids", "labels",
                                         "position_ids", "segment_ids",
                                         "seq_lens"))
    for i in range(len(masked)):
        np.testing.assert_array_equal(
            masked[i]["loss_mask"],
            np.where(masked[i]["segment_ids"] > 0,
                     masked[i]["input_ids"] % 2, 0))


def test_native_pack_rows_refuses_what_does_not_add_up():
    from automodel_tpu.native.build import pack_rows

    ids = np.arange(10, dtype=np.int32)
    out = pack_rows([4, 6], [1, 1], ids, ids, 8, 0, -100)
    np.testing.assert_array_equal(out["segment_ids"],
                                  [[1] * 4 + [0] * 4, [1] * 6 + [0] * 2])
    with pytest.raises(ValueError, match="exceed"):
        pack_rows([4, 6], [2], ids, ids, 8, 0, -100)      # 10 in a row of 8
    with pytest.raises(ValueError, match="do not match"):
        pack_rows([4, 6], [1], ids, ids, 8, 0, -100)      # a document left
    with pytest.raises(ValueError, match="do not match"):
        pack_rows([4, 6], [1, 1], ids[:9], ids, 8, 0, -100)


def test_native_collate_matches_python():
    from automodel_tpu.datasets.utils import (
        batchify,
        default_collater,
        pad_within_micro,
    )
    from automodel_tpu.native.build import collate_pad

    rng = np.random.default_rng(1)
    rows = [rng.integers(0, 99, int(rng.integers(1, 30))).tolist()
            for _ in range(16)]
    max_len = max(map(len, rows))
    nat = collate_pad(rows, max_len, -100)
    ref = batchify(np.asarray(pad_within_micro(rows, -100), np.int32))
    np.testing.assert_array_equal(nat, ref)

    # end-to-end through the collater (both keys + divisible rounding);
    # labels pad with the ignore index, matching the -100 reference above
    batch = [{"input_ids": r, "labels": list(r)} for r in rows]
    out = default_collater([dict(b) for b in batch], pad_seq_len_divisible=16)
    assert out["labels"].shape[1] % 16 == 0
    np.testing.assert_array_equal(out["labels"][:, :max_len], ref)


def test_native_packer_rejects_oversized_sample():
    ds = [{"input_ids": list(range(100)), "labels": list(range(100))}]
    with pytest.raises(ValueError, match="too long"):
        PackedSequence(ds, packed_sequence_size=64).pack()
