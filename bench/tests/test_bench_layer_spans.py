"""The readers of the program's block, flush and stall spans and of the
serving wait counters, on hand-made runs: what each reads, and that each
reads nothing from a program that records none of it."""
from __future__ import annotations

import types

import pytest

from bench.harness import spec


def span(name, dur, t0=0.0):
    return types.SimpleNamespace(name=name, dur=dur, t0=t0)


def ctx(kind, spans=(), counters=None, window_s=10.0):
    return types.SimpleNamespace(kind=kind, spans=list(spans),
                                 counters=counters or {}, window_s=window_s)


FIT_SPANS = [span("block.consume", 0.004), span("block.map", 0.001),
             span("block.emit", 0.002), span("block.combine", 0.0005),
             span("block.consume", 0.002), span("block.emit", 0.0005),
             span("lloyd.fused_step", 0.0009), span("pass.map_reduce", 1.0)]
SERVE_SPANS = [span("serve.flush", 0.003), span("serve.stack", 0.0002),
               span("serve.device", 0.0024), span("serve.deliver", 0.0003),
               span("serve.flush", 0.005), span("serve.device", 0.0036)]
SERVE_COUNTERS = {"serve.intake_wait_s": 0.05, "serve.batch_wait_s": 0.15,
                  "serve.batch_size": {"count": 2, "sum": 100.0}}
WATCHED = {"process.watchdog_wakes": 1900.0}
STALLS = [span("process.stall", 0.115, t0=3.0), span("process.stall", 0.085),
          span("gc.collect", 0.5)]

CASES = [
    ("block_host_ms", ctx("fit", FIT_SPANS), 3.0),
    ("block_sync_share", ctx("fit", FIT_SPANS), 100.0 * 0.0025 / 0.006),
    ("serve_queue_wait_ms", ctx("serve", counters=SERVE_COUNTERS), 2.0),
    ("serve_flush_ms", ctx("serve", SERVE_SPANS), 4.0),
    ("serve_device_share", ctx("serve", SERVE_SPANS), 75.0),
    ("host_stall_share.fit", ctx("fit", STALLS, WATCHED, window_s=40.0), 0.5),
    ("host_stall_share.serve", ctx("serve", STALLS, WATCHED, window_s=20.0), 1.0),
]


@pytest.mark.parametrize("name,run,expected", CASES, ids=[c[0] for c in CASES])
def test_reader_on_a_hand_made_run(name, run, expected):
    assert spec.metric_reader(name).read(run) == pytest.approx(expected)


@pytest.mark.parametrize("name,run,_", CASES, ids=[c[0] for c in CASES])
def test_reader_is_silent_without_the_programs_spans(name, run, _):
    """A program without these spans and counters (the parent of the change
    that added them) gives every reader nothing to read, in its own kind of
    run: the line leaves the metric out."""
    older = ctx(run.kind, [span("pass.map_reduce", 1.0)],
                {"serve.batch_size": {"count": 2, "sum": 100.0}})
    assert spec.metric_reader(name).read(older) is None


@pytest.mark.parametrize("kind", ["fit", "serve"])
def test_a_watched_window_without_stalls_reads_zero(kind):
    run = ctx(kind, [span("gc.collect", 0.01)], WATCHED)
    assert spec.metric_reader(f"host_stall_share.{kind}").read(run) == 0.0
