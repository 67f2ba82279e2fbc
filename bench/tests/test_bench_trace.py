"""The trace-to-metrics reduction, on a small trace recorded on a TPU v5e
(one covtype-rff fit of four blocks, 13,288 rows, inside a `bench.window`
annotation: bench/fixtures/tiny_trace.xplane.pb) and on hand-made intervals."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.harness import trace

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" / "tiny_trace.xplane.pb"


def op(start, dur, name="op"):
    return trace.Op(name, float(start), float(dur), name)


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = [op(0, 10), op(5, 10), op(30, 5), op(90, 20)]
    assert trace._union_ns(ops, (0, 100)) == 15 + 5 + 10
    assert trace._union_ns(ops, (8, 32)) == 7 + 2


def test_busy_idle_gaps_and_kernel_on_hand_made_trace():
    t = trace.Trace(window=(0.0, 100e9), devices={
        "/device:TPU:0": [op(0, 10e9, "a"), op(50e9, 10e9, "%k.3 = custom-call() tpu_custom_call")],
        "/device:TPU:1": [op(0, 30e9, "b")],
    })
    assert t.window_s == 100.0
    assert t.busy_s() == pytest.approx(25.0)  # (20 + 30) / 2 devices
    assert t.kernel(("k.3", "tpu_custom_call")) == (1, 10.0)
    assert t.kernel(("k.3", "fusion")) == (0, 0.0)
    assert [g for g in t.gaps()] == [(30e9, 70e9)]  # the busier device
    assert [name for name, _ in t.top_ops(2)] == ["b", "a"]
    assert [secs for _, secs in t.top_ops(2)] == pytest.approx([30.0, 10.0])
    assert trace.short_name("%fusion.12 = f32[8] fusion(x)") == "fusion"


@pytest.fixture(scope="module")
def recorded():
    return trace.read(str(FIXTURE))


def test_recorded_trace_has_a_window_and_a_device(recorded):
    assert 0 < recorded.window_s < 60
    assert any(name.startswith("/device:TPU:") for name in recorded.devices)


def test_recorded_busy_time_matches_a_brute_force_union(recorded):
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(FIXTURE))
    lo, hi = recorded.window
    busy = []
    for plane in prof.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        cover = set()
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:  # 10 ns cells, brute force
                a = max(ev.start_ns, lo)
                b = min(ev.start_ns + ev.duration_ns, hi)
                cover.update(range(int(a // 10), int(-(-b // 10))) if b > a else ())
        if cover:
            busy.append(len(cover) * 1e-8)
    assert busy
    assert recorded.busy_s() == pytest.approx(sum(busy) / len(busy), rel=0.05, abs=2e-5)
    assert 0 < recorded.busy_s() < recorded.window_s


def test_recorded_fused_step_events_are_found(recorded):
    events, secs = recorded.kernel(("_fused_rff_step_padded", "tpu_custom_call"))
    assert events >= 4  # at least one per block of a Lloyd pass
    assert 0 < secs < recorded.busy_s()
    assert recorded.kernel(("_fused_apnc_step_padded",)) == (0, 0.0)


def test_describe_outlines_planes_and_lines():
    text = "\n".join(trace.describe(str(FIXTURE), per_line=1))
    assert "plane '/device:TPU:0'" in text and "line 'XLA Ops'" in text
