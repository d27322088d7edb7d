"""The operation and byte counters against hand-worked numbers."""

import pytest

from conftest import real_cfg


def test_parameters(run, qwen2):
    small, big = real_cfg(run, "qwen2.5-1.5b"), real_cfg(run, "qwen2.5-3b")
    # 1.5B: 28 x (1536x1536 + 2x1536x256 + 1536x1536 + 1536+256+256 + 3x1536x8960 + 2x1536) + 151936x1536 + 1536
    layer = 1536 * 1536 * 2 + 2 * 1536 * 256 + (1536 + 256 + 256) + 3 * 1536 * 8960 + 2 * 1536
    assert qwen2.num_params(small) == 28 * layer + 151936 * 1536 + 1536 == small["parameters"]
    assert round(qwen2.num_params(small) / 1e9, 3) == 1.544
    layer = 2048 * 2048 * 2 + 2 * 2048 * 256 + (2048 + 256 + 256) + 3 * 2048 * 11008 + 2 * 2048
    assert qwen2.num_params(big) == 36 * layer + 151936 * 2048 + 2048 == big["parameters"]
    assert round(qwen2.num_params(big) / 1e9, 2) == 3.09


def test_bytes(run, qwen2):
    big = real_cfg(run, "qwen2.5-3b")
    assert qwen2.kv_row_bytes(big) == 2 * 36 * 2 * 128 * 2 == 36864
    assert qwen2.weight_bytes(big) == 2 * qwen2.num_params(big)
    assert qwen2.weight_bytes(big) / 2**30 == pytest.approx(5.75, abs=0.01)


def test_train_flops(run, qwen2):
    small = real_cfg(run, "qwen2.5-1.5b")
    matmul = 28 * (2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960) + 151936 * 1536
    assert qwen2.matmul_params(small) == matmul
    pairs = 2 * (2048 * 2049 // 2)
    forward = 2 * matmul * 4096 + 4 * 28 * 12 * 128 * pairs
    assert qwen2.train_flops_per_step(small, 2, 2048) == 3 * forward
    assert qwen2.train_flops_per_step(small, 2, 2048) / 4096 / 1e9 == pytest.approx(9.79, abs=0.01)


def test_flash_cost(run, qwen2):
    small = real_cfg(run, "qwen2.5-1.5b")
    cost = qwen2.flash_cost(small, 2, 2048)
    pairs = 2 * (2048 * 2049 // 2)
    assert cost["flops"] == 14 * 28 * 12 * 128 * pairs
    q, kv = 2 * 2048 * 12 * 128 * 2, 2 * 2048 * 2 * 128 * 2
    assert cost["bytes"] == 28 * (6 * q + 6 * kv)


def test_serve_flops(run, qwen2):
    big = real_cfg(run, "qwen2.5-3b")
    assert qwen2.serve_flops(big, 10, 55) == 2 * qwen2.matmul_params(big) * 10 + 4 * 36 * 16 * 128 * 55
