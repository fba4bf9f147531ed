"""The operation and byte counts, against hand-worked calls."""

import math

import pytest

from bench.lib import device, work

V5E = device.PEAKS["TPU v5 lite"]
SMALL = {"num_hidden_layers": 2, "hidden_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 32,
         "vocab_size": 100}


def test_gemm_counts_logical_shape():
    # (128 x 512) @ (512 x 256), fp8 operands, bf16 result
    ops, nbytes = work.gemm(128, 256, 512, 1, 1, 2)
    assert ops == 2 * 128 * 256 * 512 == 33_554_432
    assert nbytes == 128 * 512 + 512 * 256 + 128 * 256 * 2 == 262_144
    # bytes bound it on a v5e: 262,144 B / 819 GB/s > 33.5 MFLOP / 197 TF/s
    assert work.least_s(ops, nbytes, V5E) == pytest.approx(262_144 / 819e9)


def test_gemm_compute_bound():
    ops, nbytes = work.gemm(4096, 4096, 4096, 2, 1, 2)
    assert work.least_s(ops, nbytes, V5E) == pytest.approx(
        2 * 4096 ** 3 / 197e12)


def test_matmul_params_and_train_flops():
    per_layer = 16 * 32 + 16 * 16 * 2 + 32 * 16 + 3 * 16 * 32
    assert work.matmul_params(SMALL) == 2 * per_layer + 16 * 100
    fwd = 2 * work.matmul_params(SMALL) + 4 * 2 * 4 * 8 * (64 + 1) / 2
    assert work.train_flops_per_token(SMALL, 64) == pytest.approx(3 * fwd)


def test_olmo_7b_two_layers_per_token():
    conf = dict(num_hidden_layers=2, hidden_size=4096, num_attention_heads=32,
                num_key_value_heads=32, head_dim=128,
                intermediate_size=11008, vocab_size=50304)
    assert work.matmul_params(conf) == 610_795_520
    assert work.train_flops_per_token(conf, 2048) == pytest.approx(
        3 * (2 * 610_795_520 + 4 * 2 * 32 * 128 * 1024.5))
    assert math.isclose(work.train_flops_per_token(conf, 2048) / 1e9, 3.7655,
                        rel_tol=1e-4)


def test_train_gemms_cover_forward_and_both_gradients():
    g = work.train_gemms(SMALL, 10)
    assert len(g) == 3 * (7 * 2 + 1)
    ops = sum(o for o, _ in g)
    assert ops == pytest.approx(3 * 2 * 10 * work.matmul_params(SMALL))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


class _Chip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 10, "bytes_in_use": 9}


def test_memory_peak_counts_the_programs_temporaries():
    assert device.describe([_Chip()])["memory_peak_bytes"] == 10
    # 9 bytes in use, 8 of them the program's arguments; while it runs
    # the program peaks at 13 bytes, the 1 other byte beside it
    assert device.describe([_Chip()], (13, 8))["memory_peak_bytes"] == 14
    assert device.describe([_Chip()], (5, 8))["memory_peak_bytes"] == 10
