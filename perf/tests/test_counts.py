"""The count functions against numbers worked by hand."""

import pytest

from perf import counts, weights


@pytest.fixture(scope="module")
def m410():
    return weights.load_sizes("pythia410m-sizes")


@pytest.fixture(scope="module")
def b1():
    return weights.load_sizes("pythia1b-sizes")


def test_parameters(m410, b1):
    # 2 * 50304 * 1024 + 24 * (4 * 1024^2 + 2 * 1024 * 4096 + 4096 + 1024
    # + 4 * 1024) + 2 * 1024
    assert counts.num_params(m410) == 405_235_712
    assert counts.num_params(b1) == 1_011_650_560


def test_kv_bytes_per_token(b1):
    # K and V, 16 layers, 2048 wide, 2 bytes
    assert counts.kv_bytes_per_token(b1) == 131_072


def test_train_flops_per_token(m410):
    matmul = 24 * (8 * 1024 ** 2 + 4 * 1024 * 4096) + 2 * 1024 * 50304
    assert counts.matmul_flops_per_token(m410) == matmul == 707_002_368
    # causal attention: 2049 / 2 keys a token on average, 4 e FLOPs a key
    attn = 24 * 4 * 1024 * (2048 * 2049 // 2) / 2048
    assert counts.train_flops_per_token(m410, 2048) == 3 * (matmul + attn)
    assert round(counts.train_flops_per_token(m410, 2048) / 1e9, 2) == 2.42


def test_flash_call(m410):
    # one T x T x D product over the causal pairs: 2 * 8 * 16 * 2098176 * 64
    one = 2 * 8 * 16 * (2048 * 2049 // 2) * 64
    assert counts.flash_call_flops(8, 16, 2048, 64, 1) == one
    assert counts.flash_call_flops(8, 16, 2048, 64, 4) == 4 * one
    assert counts.flash_call_bytes(8, 16, 2048, 64, 4) == 4 * 8 * 16 * 2048 * 64 * 2


def test_decode_tick_bytes(b1):
    weights_ = (1_011_650_560 - 50304 * 2048) * 2
    assert counts.decode_tick_bytes(b1, 0) == weights_
    assert counts.decode_tick_bytes(b1, 20_000) == weights_ + 20_000 * 131_072


def test_serve_flops(b1):
    per = counts.matmul_flops_per_token(b1)
    # one prompt of 3 tokens (pairs 1 + 2 + 3) and one decode at context 10
    want = 4 * per + 16 * 4 * 2048 * (6 + 10)
    assert counts.serve_flops(b1, [3], [10]) == want


def test_peaks_know_the_v5e_and_nothing_else():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9 mega", "source"):
        with pytest.raises(KeyError):
            counts.peaks(kind)
