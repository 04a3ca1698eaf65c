"""Data layer tests: collation, packing w/ segment ids, nanogpt bins, loader."""

import numpy as np
import pytest

from automodel_tpu.datasets.dataloader import StatefulDataLoader
from automodel_tpu.datasets.llm.mock import build_packed_dataset, build_unpacked_dataset
from automodel_tpu.datasets.llm.nanogpt_dataset import (
    NanogptDataset,
    load_shard,
    write_shard,
)
from automodel_tpu.datasets.llm.packed_sequence import (
    WINDOW,
    PackedSequence,
    best_fit_rows,
)
from automodel_tpu.datasets.utils import (
    CROSS_ENTROPY_IGNORE_IDX,
    default_collater,
    make_attention_mask_from_labels,
    pad_within_micro,
)


def test_pad_within_micro_divisible():
    out = pad_within_micro([[1, 2, 3], [4]], pad_token_id=0,
                           pad_seq_len_divisible=8)
    assert all(len(r) == 8 for r in out)
    assert out[1] == [4, 0, 0, 0, 0, 0, 0, 0]


def test_default_collater_pads_labels_with_ignore():
    batch = [
        {"input_ids": [1, 2, 3], "labels": [2, 3, -100]},
        {"input_ids": [1], "labels": [5]},
    ]
    out = default_collater(batch)
    assert out["input_ids"].shape == (2, 3)
    assert out["labels"][1, 1] == CROSS_ENTROPY_IGNORE_IDX
    assert out["input_ids"].dtype == np.int32


def test_attention_mask_from_labels():
    assert make_attention_mask_from_labels([1, 2, -100, -100]) == [1, 1, 0, 0]
    assert make_attention_mask_from_labels([-100, 1, 2]) == [1, 1, 1]


def test_packed_sequence_segment_ids():
    data = [
        {"input_ids": [1, 2, 3], "labels": [2, 3, -100]},
        {"input_ids": [4, 5], "labels": [5, -100]},
        {"input_ids": [6, 7, 8, 9], "labels": [7, 8, 9, -100]},
    ]
    ps = PackedSequence(data, packed_sequence_size=8).pack()
    p0 = ps[0]
    # longest first: sample 3 (4 tokens) opens a row, sample 1 (3) fits
    # beside it and stands first, as in the dataset; sample 2 (2) no longer
    # fits and opens the second row
    np.testing.assert_array_equal(p0["input_ids"], [1, 2, 3, 6, 7, 8, 9, 0])
    np.testing.assert_array_equal(p0["segment_ids"], [1, 1, 1, 2, 2, 2, 2, 0])
    np.testing.assert_array_equal(p0["position_ids"], [0, 1, 2, 0, 1, 2, 3, 7])
    np.testing.assert_array_equal(p0["labels"][:7],
                                  [2, 3, -100, 7, 8, 9, -100])
    assert p0["labels"][7] == CROSS_ENTROPY_IGNORE_IDX
    p1 = ps[1]
    np.testing.assert_array_equal(p1["segment_ids"][:3], [1, 1, 0])
    assert (p1["labels"][2:] == CROSS_ENTROPY_IGNORE_IDX).all()
    assert len(ps) == 2
    assert (ps.rows, ps.tokens, ps.fill) == (2, 9, 9 / 16)


def test_best_fit_rows_by_hand():
    # 5 opens row a (3 left), 4 opens row b (4 left), 3 fills a exactly
    # (least room that holds it), 2 and 1 go to b; nothing of length 0
    assert best_fit_rows([3, 2, 4, 5, 0, 1], 8) == [[0, 3], [1, 2, 5]]
    # equal lengths go by index, equal rooms by the row opened first
    assert best_fit_rows([2, 2, 2, 2, 1, 1], 3) == [[0, 4], [1, 5], [2], [3]]
    assert best_fit_rows([], 8) == best_fit_rows([0, 0], 8) == []


def _named_docs(lengths, loss_mask=False):
    """Documents whose every token is unique in the dataset, so that a row
    says which documents it holds; labels are the negated ids."""
    out, base = [], 1
    for n in lengths:
        ids = np.arange(base, base + n, dtype=np.int32)
        doc = {"input_ids": ids, "labels": -ids}
        if loss_mask:
            doc["loss_mask"] = (ids % 2).astype(np.int32)
        out.append(doc)
        base += n
    return out


def _rows_of_whole_documents(ps, docs, size):
    """Hold ``ps`` to the batch format and return its rows as lists of
    document indices: every document whole, contiguous, with its labels,
    positions from 0 and a segment id of its own; ids dense from 1 per row;
    padding after the last document and nowhere else."""
    first = {int(d["input_ids"][0]): i for i, d in enumerate(docs)
             if len(d["input_ids"])}
    rows = []
    for r in range(len(ps)):
        item, lens = ps[r], ps.packed_dataset[r]["seq_lens"]
        assert all(v.shape == (size,) and v.dtype == np.int32
                   for v in item.values())
        used, row = int(lens.sum()), []
        for k, n in enumerate(lens):
            o = int(lens[:k].sum())
            i = first[int(item["input_ids"][o])]
            d = docs[i]
            assert n == len(d["input_ids"])
            np.testing.assert_array_equal(item["input_ids"][o:o + n],
                                          d["input_ids"])
            np.testing.assert_array_equal(item["labels"][o:o + n],
                                          d["labels"])
            np.testing.assert_array_equal(item["position_ids"][o:o + n],
                                          np.arange(n))
            assert (item["segment_ids"][o:o + n] == k + 1).all()
            if "loss_mask" in d:
                np.testing.assert_array_equal(item["loss_mask"][o:o + n],
                                              d["loss_mask"])
            row.append(i)
        assert used <= size and (item["segment_ids"][used:] == 0).all()
        assert (item["input_ids"][used:] == 0).all()
        assert (item["labels"][used:] == CROSS_ENTROPY_IGNORE_IDX).all()
        rows.append(row)
    return rows


def _lognormal(rng, n, size, median=600.0, sigma=1.2, low=16):
    return np.clip(np.rint(median * np.exp(sigma * rng.standard_normal(n))),
                   low, size).astype(int)


LENGTH_SETS = {
    "uniform": lambda rng: rng.integers(1, 129, 700),
    "lognormal_clipped_at_the_row": lambda rng: _lognormal(
        rng, 600, 128, median=20.0, low=1),
    "all_equal_to_the_row": lambda rng: np.full(300, 128),
    "all_tiny": lambda rng: rng.integers(1, 4, 1000),
    "one_document": lambda rng: np.array([77]),
    "not_a_multiple_of_the_window": lambda rng: rng.integers(
        0, 100, 2 * WINDOW + 37),
}


@pytest.fixture(params=["native", "python"])
def layout(request, monkeypatch):
    """Both ways the rows are laid out: the C++ core, and the numpy one
    that runs where there is no compiler (or a ``loss_mask`` to carry)."""
    from automodel_tpu import native

    if request.param == "python":
        monkeypatch.setattr(native, "available", lambda: False)
    elif not native.available():
        pytest.skip("no C++ toolchain")
    return request.param


@pytest.mark.parametrize("name", sorted(LENGTH_SETS))
def test_packed_whole_every_document_once(name, layout):
    size = 128
    lengths = LENGTH_SETS[name](np.random.default_rng(len(name)))
    docs = _named_docs(lengths)
    ps = PackedSequence(docs, packed_sequence_size=size).pack()
    rows = _rows_of_whole_documents(ps, docs, size)
    placed = sorted(i for row in rows for i in row)
    assert placed == [i for i, n in enumerate(lengths) if n > 0]
    assert ps.rows == len(rows) and ps.tokens == int(lengths.sum())
    assert ps.fill == ps.tokens / (ps.rows * size)
    # (d) a row draws from one window, its documents stand in dataset
    # order, and the rows come out by their earliest document
    assert all(row == sorted(row) for row in rows)
    assert all(row[0] // WINDOW == row[-1] // WINDOW for row in rows)
    heads = [row[0] for row in rows]
    assert heads == sorted(heads)
    # the same dataset gives the same rows
    again = PackedSequence(docs, packed_sequence_size=size).pack()
    for a, b in zip(ps.packed_dataset, again.packed_dataset):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_packed_whole_fills_heavy_tailed_rows(layout):
    """4,096 lognormal documents (median 600, sigma 1.2, 16-4096) into rows
    of 4096: closing a row at the first document that does not fit leaves a
    fifth of the slots empty, best fit inside a window of 256 under 2 %."""
    size = 4096
    lengths = _lognormal(np.random.default_rng(2025), 4096, size)
    in_order, room = 0, 0
    for n in lengths:
        if n > room:
            in_order, room = in_order + 1, size
        room -= n
    assert 0.75 < lengths.sum() / (in_order * size) < 0.85
    ps = PackedSequence(_named_docs(lengths), packed_sequence_size=size).pack()
    assert ps.fill >= 0.98, ps.fill
    assert ps.rows < 0.83 * in_order


@pytest.mark.parametrize("max_packs", [1, 7, 10_000])
def test_packed_whole_max_packs_and_loss_mask_follow_the_placement(
        max_packs, layout):
    size = 64
    lengths = np.random.default_rng(3).integers(1, 65, WINDOW + 50)
    docs = _named_docs(lengths)
    full = PackedSequence(docs, packed_sequence_size=size).pack()
    rows = _rows_of_whole_documents(full, docs, size)
    capped = PackedSequence(docs, packed_sequence_size=size,
                            max_packs=max_packs).pack()
    assert _rows_of_whole_documents(capped, docs, size) == rows[:max_packs]
    masked_docs = _named_docs(lengths, loss_mask=True)
    masked = PackedSequence(masked_docs, packed_sequence_size=size,
                            max_packs=max_packs).pack()
    assert "loss_mask" in masked[0]
    assert _rows_of_whole_documents(masked, masked_docs,
                                    size) == rows[:max_packs]


def test_packed_whole_takes_a_stream_window_by_window():
    """``max_packs`` stops reading: a dataset that cannot be listed is
    packed as far as asked."""
    def stream():
        n = 0
        while True:
            n += 1
            yield {"input_ids": [n] * 5, "labels": [n] * 5}

    class Endless:
        __iter__ = staticmethod(stream)

    ps = PackedSequence(Endless(), packed_sequence_size=16, max_packs=3).pack()
    assert ps.rows == 3 and ps.tokens == 45
    np.testing.assert_array_equal(ps[0]["segment_ids"][:15],
                                  [1] * 5 + [2] * 5 + [3] * 5)


def test_pack_says_how_full_the_rows_are(caplog):
    import logging

    from automodel_tpu.config.loader import ConfigNode
    from automodel_tpu.recipes.llm.train_ft import build_dataloader

    docs = _named_docs([5, 3, 8, 2])
    cfg = ConfigNode({"packed_sequence": {"packed_sequence_size": 8}})
    with caplog.at_level(logging.INFO):
        loader = build_dataloader(cfg, docs)
    assert "packs created: 3 (18 tokens, fill 0.7500)" in caplog.text
    assert "dataloader: pack_fill 0.7500 (3 rows hold 18 tokens)" in caplog.text
    assert len(loader.dataset) == 3
    split = PackedSequence(docs, packed_sequence_size=8,
                           split_across_pack=True).pack()
    assert (split.rows, split.tokens, split.fill) == (3, 18, 0.75)


def test_packed_sequence_split_across_pack():
    data = [{"input_ids": list(range(10)), "labels": list(range(10))}]
    ps = PackedSequence(data, packed_sequence_size=6,
                        split_across_pack=True).pack()
    assert len(ps) == 2
    assert len(ps[0]["input_ids"]) == 6
    # continuation lands in pack 2 with fresh positions
    np.testing.assert_array_equal(ps[1]["position_ids"][:4], [0, 1, 2, 3])


def test_packed_split_continuation_distinct_segment():
    """A split continuation and the next sample must get different segment
    ids — otherwise unrelated documents attend to each other."""
    data = [{"input_ids": [i * 10 + j for j in range(6)],
             "labels": [i * 10 + j for j in range(6)]} for i in range(3)]
    ps = PackedSequence(data, packed_sequence_size=8,
                        split_across_pack=True).pack()
    p1 = ps[1]  # continuation of sample 2 + sample 3
    segs = p1["segment_ids"]
    ids = p1["input_ids"]
    # tokens from different source samples never share a segment id
    doc_of = {int(t): int(t) // 10 for t in ids if segs[list(ids).index(t)] != 0}
    seg_to_docs = {}
    for t, s in zip(ids, segs):
        if s == 0:
            continue
        seg_to_docs.setdefault(int(s), set()).add(int(t) // 10)
    for docs in seg_to_docs.values():
        assert len(docs) == 1, seg_to_docs


def test_packed_too_long_raises(layout):
    data = [{"input_ids": [1, 2], "labels": [1, 2]},
            {"input_ids": list(range(10)), "labels": list(range(10))}]
    with pytest.raises(ValueError, match=r"too long \(10 > 4\)"):
        PackedSequence(data, packed_sequence_size=4).pack()


def test_mock_packed_dataset():
    ps = build_packed_dataset(num_sentences=20, packed_sequence_size=64, seed=1)
    item = ps[0]
    assert set(item) == {"input_ids", "labels", "position_ids", "segment_ids"}
    assert item["input_ids"].shape == (64,)


def test_nanogpt_roundtrip(tmp_path):
    toks = np.arange(1000) % 7
    write_shard(str(tmp_path / "shard0.bin"), toks)
    back = load_shard(str(tmp_path / "shard0.bin"))
    np.testing.assert_array_equal(np.asarray(back), toks.astype(np.uint16))

    ds = NanogptDataset(str(tmp_path / "*.bin"), seq_len=64, rank=0, world_size=1)
    items = list(ds)
    assert len(items) == len(ds) == (1000 - 1) // 64
    first = items[0]
    np.testing.assert_array_equal(first["labels"][:-1], first["input_ids"][1:])


def test_nanogpt_rank_split(tmp_path):
    toks = np.arange(2000)  # unique tokens -> window prefixes are unique
    write_shard(str(tmp_path / "s.bin"), toks)
    a = list(NanogptDataset(str(tmp_path / "s.bin"), seq_len=64, rank=0, world_size=2))
    b = list(NanogptDataset(str(tmp_path / "s.bin"), seq_len=64, rank=1, world_size=2))
    total = (2000 - 1) // 64
    assert len(a) + len(b) == total
    # disjoint windows
    a0 = {tuple(x["input_ids"][:4]) for x in a}
    b0 = {tuple(x["input_ids"][:4]) for x in b}
    assert not (a0 & b0)


def test_nanogpt_bos_alignment(tmp_path):
    toks = np.zeros(500, dtype=np.int64)
    bos = 99
    toks[::50] = bos
    write_shard(str(tmp_path / "s.bin"), toks)
    ds = NanogptDataset(str(tmp_path / "s.bin"), seq_len=64,
                        align_to_bos=True, bos_token=bos, rank=0, world_size=1)
    for item in ds:
        assert item["input_ids"][0] == bos


def test_dataloader_resume_mid_epoch():
    data = build_unpacked_dataset(num_sentences=32, seed=3)
    dl = StatefulDataLoader(data, batch_size=4, shuffle=True, seed=7)
    it = iter(dl)
    first_two = [next(it), next(it)]
    sd = dl.state_dict()

    dl2 = StatefulDataLoader(data, batch_size=4, shuffle=True, seed=7)
    dl2.load_state_dict(sd)
    resumed = next(iter(dl2))
    # the resumed batch must equal batch #3 of a fresh run
    dl3 = StatefulDataLoader(data, batch_size=4, shuffle=True, seed=7)
    it3 = iter(dl3)
    next(it3), next(it3)
    expected = next(it3)
    np.testing.assert_array_equal(resumed["input_ids"], expected["input_ids"])


def test_dataloader_length_bucket_pool():
    """Length-bucketed batching: every sample still appears exactly once
    per epoch, the order is deterministic per (seed, epoch), mid-epoch
    resume holds, and per-batch length spread shrinks vs plain shuffle."""
    data = build_unpacked_dataset(num_sentences=128, mean_len=60,
                                  std_len=30, max_sentence_len=127, seed=3)
    kw = dict(batch_size=8, shuffle=True, seed=7, length_bucket_pool=64)

    dl = StatefulDataLoader(data, **kw)
    spreads = []
    seen = 0
    for b in iter(dl):
        lens = np.sum(np.asarray(b["labels"]) != -100, axis=1)
        spreads.append(int(lens.max() - lens.min()))
        seen += b["input_ids"].shape[0]
    assert seen == 128                      # full coverage, once each

    plain = StatefulDataLoader(data, batch_size=8, shuffle=True, seed=7)
    plain_spreads = []
    for b in iter(plain):
        lens = np.sum(np.asarray(b["labels"]) != -100, axis=1)
        plain_spreads.append(int(lens.max() - lens.min()))
    assert np.mean(spreads) < 0.5 * np.mean(plain_spreads)

    # determinism: same seed -> identical batches
    a = [b["input_ids"] for b in iter(StatefulDataLoader(data, **kw))]
    c = [b["input_ids"] for b in iter(StatefulDataLoader(data, **kw))]
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)

    # resume mid-epoch matches a fresh run's third batch
    dl4 = StatefulDataLoader(data, **kw)
    it = iter(dl4)
    next(it), next(it)
    sd = dl4.state_dict()
    dl5 = StatefulDataLoader(data, **kw)
    dl5.load_state_dict(sd)
    resumed = next(iter(dl5))
    np.testing.assert_array_equal(resumed["input_ids"], a[2])


def test_dataloader_length_bucket_pool_misaligned():
    """Pool not a multiple of batch_size (and n not a multiple of pool):
    sub-batch_size remainders must park at the END of the order, so every
    full batch stays inside one sorted group — batch spread must STILL
    shrink (the bug class: a short tail shuffled mid-epoch shifts all
    later fixed-stride windows across groups)."""
    data = build_unpacked_dataset(num_sentences=130, mean_len=60,
                                  std_len=30, max_sentence_len=127, seed=4)
    dl = StatefulDataLoader(data, batch_size=8, shuffle=True, seed=7,
                            length_bucket_pool=100, drop_last=False)
    spreads, seen = [], 0
    for b in iter(dl):
        lens = np.sum(np.asarray(b["labels"]) != -100, axis=1)
        if b["input_ids"].shape[0] == 8:
            spreads.append(int(lens.max() - lens.min()))
        seen += b["input_ids"].shape[0]
    assert seen == 130
    plain = StatefulDataLoader(data, batch_size=8, shuffle=True, seed=7,
                               drop_last=False)
    plain_spreads = []
    for b in iter(plain):
        lens = np.sum(np.asarray(b["labels"]) != -100, axis=1)
        if b["input_ids"].shape[0] == 8:
            plain_spreads.append(int(lens.max() - lens.min()))
    assert np.mean(spreads) < 0.6 * np.mean(plain_spreads), (
        np.mean(spreads), np.mean(plain_spreads))


def test_dataloader_length_bucket_pool_rejects_iterable():
    class Stream:
        def __iter__(self):
            return iter([])

    with pytest.raises(ValueError, match="map-style"):
        StatefulDataLoader(Stream(), batch_size=4, length_bucket_pool=64)


def test_dataloader_epoch_shuffles_differ():
    data = build_unpacked_dataset(num_sentences=16, seed=3)
    dl = StatefulDataLoader(data, batch_size=16, shuffle=True, seed=7,
                            drop_last=False)
    e0 = next(iter(dl))
    e1 = next(iter(dl))
    assert not np.array_equal(e0["input_ids"], e1["input_ids"])


def test_dataloader_iterable(tmp_path):
    toks = np.arange(1300) % 13
    write_shard(str(tmp_path / "s.bin"), toks)
    ds = NanogptDataset(str(tmp_path / "s.bin"), seq_len=32, rank=0, world_size=1)
    dl = StatefulDataLoader(ds, batch_size=4, shuffle=False)
    batches = list(dl)
    assert batches[0]["input_ids"].shape == (4, 32)


def test_mock_packed_fixed_blocks():
    from automodel_tpu.datasets.llm.mock_packed import build_packed_dataset

    ds = build_packed_dataset(num_blocks=6, block_size=32, vocab_size=50,
                              seed=3)
    assert len(ds) == 6
    for ex in ds:
        assert len(ex["input_ids"]) == 32
        assert len(ex["position_ids"]) == 32
        assert ex["labels"] == ex["input_ids"]
        # position ids restart after eos
        for i in range(1, 32):
            if ex["input_ids"][i - 1] == 1:
                assert ex["position_ids"][i] == 0
    # deterministic under the same seed
    again = build_packed_dataset(num_blocks=6, block_size=32, vocab_size=50,
                                 seed=3)
    assert again == ds


def test_nanogpt_data_processor_tool(tmp_path):
    import json
    import sys

    sys.path.insert(0, "tools")
    try:
        from nanogpt_data_processor import ShardWriter, parse_token_count
    finally:
        sys.path.pop(0)

    assert parse_token_count("500M") == 500_000_000
    assert parse_token_count("2K") == 2000
    assert parse_token_count(123) == 123
    assert parse_token_count(None) == 0

    import numpy as np

    from automodel_tpu.datasets.llm.nanogpt_dataset import load_shard

    w = ShardWriter(str(tmp_path), shard_size=100, prefix="t")
    rng = np.random.default_rng(0)
    all_tokens = []
    for _ in range(7):
        t = rng.integers(0, 50000, 37).astype(np.uint32)
        all_tokens.append(t)
        w.add(t)
    w.finalize()
    flat = np.concatenate(all_tokens)
    out = np.concatenate([np.asarray(load_shard(p)) for p in w.shard_paths])
    np.testing.assert_array_equal(out, flat)
    assert all(len(np.asarray(load_shard(p))) == 100
               for p in w.shard_paths[:-1])
