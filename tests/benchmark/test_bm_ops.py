"""The operation and byte counts against hand counts for the
configuration, and the table of peaks."""
import pytest

import bm_util  # noqa: F401
from benchmark import harness, ops, peaks

GPT2M = harness.load_json(harness.HERE, "configs", "gpt2-medium.json")


def test_parameter_counts_match_the_published_sizes():
    # GPT-2-medium: 354.8M published with a learned 1024x1024 position
    # table (1.05M) and q/k/v/o biases (24 * 4 * 1024) this model lacks
    assert ops.param_count(GPT2M["model"]) == (
        50257 * 1024 + 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096 + 4096 + 1024
                             + 4 * 1024) + 2 * 1024)
    assert 353.6e6 < ops.param_count(GPT2M["model"]) < 353.8e6


def test_matmul_operations_per_token_by_hand():
    # GPT-2-medium: 24 layers x (4 x 1024^2 + 2 x 1024 x 4096) + head
    assert ops.matmul_flops_per_token(GPT2M["model"]) == 2 * (
        24 * 12582912 + 50257 * 1024)


def test_training_operations_per_sequence_by_hand():
    m = GPT2M["model"]
    fwd = 1024 * ops.matmul_flops_per_token(m) + 4 * 1024 * 24 * (
        1024 * 1025 // 2)
    assert ops.train_flops_per_sequence(m, 1024) == 3 * fwd
    per_token = ops.train_flops_per_sequence(m, 1024) / 1024
    assert 2.2e9 < per_token < 2.4e9       # ~2.3 GFLOP a token


def test_flash_training_counts_by_hand():
    m = GPT2M["model"]
    flops, nbytes = ops.flash_train_ops_bytes(m, 8, 1024)
    pairs = 8 * 1024 * 1025 // 2
    assert flops == 7 * 2 * 1024 * pairs * 24
    assert nbytes == 12 * (8 * 1024 * 1024 * 4) * 24


def test_roofline_bound_says_which_side_binds():
    pk = peaks.peaks_for("TPU v5 lite")
    assert ops.roofline_seconds(197e12, 1, pk) == (1.0, "flops")
    assert ops.roofline_seconds(1, 819e9, pk) == (1.0, "bytes")
    # one step's attention at the cell's shapes, float32 operands: 2.9 TFLOP
    # (14.7 ms at the peak) against 19.3 GB (23.6 ms): the bytes bind
    flops, nbytes = ops.flash_train_ops_bytes(GPT2M["model"], 16, 1024)
    least, side = ops.roofline_seconds(flops, nbytes, pk)
    assert side == "bytes" and least == pytest.approx(0.02359, rel=1e-3)


def test_an_unknown_device_has_no_peak():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
