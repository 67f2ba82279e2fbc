"""Operation and byte counts of the fused steps against hand-worked shapes,
and the peak table."""
from __future__ import annotations

import pytest

from bench.harness import roofline


def test_apnc_step_counts_at_imagenet_block():
    ops, nbytes = roofline.apnc_step(4096, 900, 300, 200, 164)
    # 2bdl + 2blm + 2bmk + bm
    assert ops == 2_211_840_000 + 491_520_000 + 268_697_600 + 819_200
    # 4 * (X block + L + R + C + Z + g + labels + cost)
    assert nbytes == 4 * (3_686_400 + 270_000 + 60_000 + 32_800 + 32_800 + 164 + 4_096 + 1)


def test_rff_step_counts_at_covtype_block():
    ops, nbytes = roofline.rff_step(4096, 54, 200, 7)
    # 2bdh + 2bmk + bm with m = 2h = 400
    assert ops == 88_473_600 + 22_937_600 + 1_638_400
    assert nbytes == 4 * (221_184 + 10_800 + 2_800 + 2_800 + 7 + 4_096 + 1)


def test_imagenet_step_is_memory_bound_on_v5e():
    peak = roofline.peaks("TPU v5 lite")
    least, bound = roofline.least_seconds(*roofline.apnc_step(4096, 900, 300, 200, 164), peak)
    assert bound == "memory"
    assert least == pytest.approx(16_345_044 / 819e9)


def test_covtype_step_is_memory_bound_on_v5e():
    peak = roofline.peaks("TPU v5 lite")
    _, bound = roofline.least_seconds(*roofline.rff_step(4096, 54, 200, 7), peak)
    assert bound == "memory"


def test_compute_bound_when_operations_dominate():
    peak = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1e12}
    assert roofline.least_seconds(10.0, 10.0, peak) == (10.0, "compute")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
