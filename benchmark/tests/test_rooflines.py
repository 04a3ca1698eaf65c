"""The FLOP and byte functions against values worked by hand at the
configuration's sizes."""
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.rooflines import linear_ce, paged_decode, splash, step


@pytest.fixture(scope="module")
def olmo2_1b():
    return mf.config_of(mf.load(), "olmo2-1b")


def test_sizes_of_olmo2_1b(olmo2_1b):
    mm = step.matmul_params(olmo2_1b)
    # 2048 x (16 + 2 x 16) x 128 + 16 x 128 x 2048 + 3 x 2048 x 8192
    assert mm["layer"] == 12_582_912 + 4_194_304 + 50_331_648 == 67_108_864
    assert mm["layers"] == 1_073_741_824
    assert mm["head"] == 2048 * 100352 == 205_520_896
    # + embedding + 16 x (2 x 2048 q/k norm + 2 x 2048 block norms) + 2048
    assert step.total_params(olmo2_1b) == (
        1_073_741_824 + 2 * 205_520_896 + 16 * 8192 + 2048) == 1_484_916_736
    # K and V, 16 layers, 16 heads of 128, bf16
    assert paged_decode.kv_bytes_per_token(olmo2_1b) == 131_072
    assert step.attention_pair_flops(olmo2_1b) == 4 * 16 * 128 * 16


def test_train_work_counts_documents_not_rows(olmo2_1b):
    seg = np.zeros((1, 4096), np.int32)
    seg[0, :1000], seg[0, 1000:3000] = 1, 2         # 1,096 slots of padding
    pairs = 1000 * 1001 // 2 + 2000 * 2001 // 2
    assert step.causal_pairs([seg]) == pairs
    flops = step.train_flops(olmo2_1b, [[seg]])
    assert flops == 6 * (1_073_741_824 + 205_520_896) * 3000 \
        + 3 * 131_072 * pairs
    assert step.train_flops(olmo2_1b, [[seg]], trained=False) \
        == flops - 2 * (1_073_741_824 + 205_520_896) * 3000
    f, b = splash.work(olmo2_1b, [[seg]])
    assert f == 3 * 131_072 * pairs
    assert b == 2 * (16 * 8 + 16 * 4) * 128 * 3000 * 16
    f, b = linear_ce.work(olmo2_1b, [[seg]])
    assert f == 6 * 3000 * 2048 * 100352


def test_serve_work_counts_real_positions(olmo2_1b):
    # one decode row at context 500 and one prefill chunk of 32 from 64
    steps = [{"positions": 33, "sampled": 1, "context": 501 + 96,
              "attended": 501 + 32 * 64 + 32 * 33 // 2}]
    assert step.serve_flops(olmo2_1b, steps) == (
        2 * 1_073_741_824 * 33 + 2 * 205_520_896 * 1
        + 131_072 * steps[0]["attended"])
    f, b = paged_decode.work(olmo2_1b, steps)
    assert b == 131_072 * (597 + 33)
