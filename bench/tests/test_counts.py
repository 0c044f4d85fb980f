"""The work counts against hand counts."""

import math

import pytest

from work import counts

QWEN3 = {"n_layers": 28, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 6144, "vocab": 151936, "sell": "acdc"}


def test_dense_projection():
    # 2 * in * out per row; bytes: rows x (in + out) + in x out, at 2 bytes
    assert counts.proj_flops(2048, 6144, sell=False) == 2 * 2048 * 6144
    assert counts.proj_bytes(2048, 6144, False, rows=4, elem=2) == \
        2 * (4 * (2048 + 6144) + 2048 * 6144)


def test_acdc_layer():
    # one layer at N = 6144: two DCTs of 2.5 N log2 N, two diagonals
    n = 6144
    one = 2 * 2.5 * n * math.log2(n) + 2 * n
    assert counts.proj_flops(2048, 6144, sell=True, k=1) == pytest.approx(one)
    assert counts.proj_flops(2048, 6144, sell=True, k=2) == \
        pytest.approx(2 * one)
    # with a bias-on-D: N more
    assert counts.proj_flops(6144, 2048, sell=True, k=1, bias=True) == \
        pytest.approx(one + n)
    # the N x N transform is in no count: params are the 2K diagonals
    assert counts.proj_params(2048, 6144, sell=True) == 2 * 2 * 6144
    assert counts.op_size(2048, 2000) == 2048
    assert counts.op_size(100, 200) == 256


def test_sell_work_covers_the_four_targets_of_every_layer():
    flops, nbytes = counts.sell_work(QWEN3, rows=1, calls=1, elem=2)
    per_row = (counts.proj_flops(2048, 2048, True)
               + 3 * counts.proj_flops(2048, 6144, True))
    assert flops == pytest.approx(28 * per_row)
    dense = dict(QWEN3, sell="dense")
    assert counts.sell_work(dense, 1, 1, 2) == (0.0, 0.0)


def test_attention_by_context():
    assert counts.attn_flops(QWEN3, 100) == 4 * 100 * 16 * 128
    assert counts.attn_bytes(QWEN3, 100, 2) == 2 * (2 * 100 * 8 * 128
                                                   + 2 * 8 * 128
                                                   + 2 * 16 * 128)


def test_token_flops():
    dense = dict(QWEN3, sell="dense")
    per_layer = 2 * 2048 * (2048 + 1024 + 1024 + 2048 + 3 * 6144)
    head = 2 * 2048 * 151936
    ctx = 10
    assert counts.token_flops(dense, ctx) == pytest.approx(
        28 * (per_layer + 4 * ctx * 16 * 128) + head)
