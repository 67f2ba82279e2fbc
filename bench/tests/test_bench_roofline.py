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


def test_assign_step_counts_at_imagenet_resident():
    ops, nbytes = roofline.assign_step(524288, 200, 164)
    # 2bmk + bm over all n embedded rows
    assert ops == 34_393_292_800 + 104_857_600
    # 4 * (Y + C + Z + g + labels + cost)
    assert nbytes == 4 * (104_857_600 + 32_800 + 32_800 + 164 + 524_288 + 1)


def test_assign_step_roofline_reads_the_resident_kernel_and_nothing_else():
    """Each `_assign_padded` kernel event is one pass over the resident
    cell's n rows; with no such event, or in a cell that streams blocks, the
    reader is silent."""
    import types

    from bench.harness import spec, trace

    reader = spec.metric_reader("assign_step_roofline")
    peak = roofline.peaks("TPU v5 lite")
    least, bound = roofline.least_seconds(*roofline.assign_step(524288, 200, 164), peak)
    assert bound == "memory"
    text = ('%_assign_padded.8 = (f32[168,256], f32[168,1], s32[524288,1]) '
            'custom-call(%pad.48, %copy.3), custom_call_target="tpu_custom_call"')
    kernel = [trace.Op(text, i * 1e7, 2 * least * 1e9, text) for i in range(3)]
    other = [trace.Op("%fusion.1 = f32[8] fusion(%x)", 5e8, 1e6, "fusion")]

    def ctx(cell, ops):
        return types.SimpleNamespace(
            kind="fit", cell=spec.load_cell(cell), device={"kind": "TPU v5 lite"},
            trace=trace.Trace(window=(0.0, 1e9), devices={"/device:TPU:0": ops}))

    assert reader.read(ctx("imagenet-nystrom.fit-resident", kernel + other)) \
        == pytest.approx(50.0)
    assert reader.read(ctx("imagenet-nystrom.fit-resident", other)) is None
    assert reader.read(ctx("imagenet-nystrom.fit", kernel + other)) is None
