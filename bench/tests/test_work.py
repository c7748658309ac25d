"""The work counts against yi-6b numbers computed by hand."""

import pytest

from bench import work

YI6B_2L = dict(L=2, d=4096, H=32, KV=4, hd=128, ff=11008, V=64000)


def test_matmul_and_total_params():
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert per_layer == 173_015_040
    assert work.matmul_params(YI6B_2L) == 2 * per_layer + 4096 * 64000
    assert work.matmul_params(YI6B_2L) == 608_174_080
    # 262.1 M embedding + 262.1 M head + 2 x 173.0 M + 5 norm vectors
    assert work.params(YI6B_2L) == 2 * 262_144_000 + 2 * per_layer + 5 * 4096
    assert work.params(YI6B_2L) == 870_338_560


def test_train_flops_per_token():
    # 6 N (matmul weights) + 3 x 2 x 2 x H x hd x (S + 1) per layer
    attn = 3 * 2 * 2 * 32 * 128 * 2049 / 2 * 2
    want = 6 * 608_174_080 + attn
    assert work.train_flops_per_token(YI6B_2L, 2048) == pytest.approx(want)
    # ~7.68 TFLOP per 2048-token step, 39 ms at 197 TFLOP/s
    assert work.train_flops_per_token(YI6B_2L, 2048) * 2048 == \
        pytest.approx(7.6795e12, rel=1e-4)


def test_compress_bytes_dense_and_compact():
    elems = 2 * 64000 * 4096 + 4096 + 2 * 2 * 4096 \
        + 2 * (4096 + 512 + 512 + 4096 + 3 * 11008) * 4096
    assert elems == work.params(YI6B_2L)
    dense = work.compress_bytes(YI6B_2L, {"policy": "topk:k=0.01",
                                          "wire": "dense_psum"})
    rows = 2 * 64000 + 1 + 2 * 2 + 2 * (4096 + 512 + 512 + 4096 + 3 * 11008)
    assert dense == 3 * 4 * elems + 4 * rows
    compact = work.compress_bytes(YI6B_2L, {"policy": "topk:k=0.01",
                                            "wire": "sparse_allgather"})
    assert compact == 2 * 4 * elems + rows * 41 * 8 + 4 * rows


def test_peaks_known_and_unknown():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    assert pk["ici_bits_per_s"] == 1.6e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_topk_fraction_parses_only_catch_all():
    assert work.topk_fraction("topk:k=0.01") == 0.01
    with pytest.raises(ValueError):
        work.topk_fraction("norm->identity;.*->topk:k=0.01")
