"""Tests for the observability subsystem (repro.obs).

The load-bearing claims:
  * tracing costs nothing when disabled — `span()` returns the shared
    NULL_SPAN singleton (no allocation, no recording) and the math of an
    instrumented fit is untouched either way;
  * the Chrome trace-event export is structurally valid (the same invariants
    benchmarks/check_bench.py --trace enforces in CI): every complete event
    lives in a named lane;
  * the metrics registry survives concurrent writers (the sharded executor's
    D producer threads all inc the same counters);
  * every backend's fit returns a populated FitReport whose per-iteration
    inertia trajectory ends at the model's reported inertia, and the exact
    backends (local / stream / stream_shard) report the SAME trajectory from
    the same key — observability must describe one underlying computation;
  * every span has an id and its parent's, is mirrored on the profiler's
    host plane, and the engine, batcher, gc hook and watchdog record the
    spans the benchmark's per-layer metrics read;
  * the roofline join reports measured/modeled fractions from a synthetic
    dry-run record.
"""
from __future__ import annotations

import json
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import KernelKMeans
from repro.core.kernels_fn import Kernel
from repro.data.synthetic import gaussian_blobs_blocks


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts from disabled tracing and an empty span buffer
    (metrics are deliberately NOT wiped: production code holds instrument
    references, and tests below scope their own reads via snapshot/delta)."""
    obs.disable_tracing()
    obs.clear_trace()
    yield
    obs.disable_tracing()
    obs.clear_trace()


# ------------------------------------------------------------------- tracer


def _own_spans():
    """The spans a test made: an enabled tracer also records `gc.collect`
    and `process.stall` (cat "process") whenever the collector runs or the
    watchdog wakes late, which a busy test machine makes happen at will."""
    return [s for s in obs.TRACER.spans() if s.cat != "process"]


def test_disabled_span_is_the_null_singleton():
    assert not obs.tracing_enabled()
    s = obs.span("anything", cat="x", attr=1)
    assert s is obs.NULL_SPAN  # no per-call allocation on the disabled path
    with s as inner:
        inner.set(more="attrs ignored")
    assert obs.TRACER.spans() == []


def test_enabled_span_records_duration_and_lane():
    obs.enable_tracing()
    with obs.span("work", cat="test", block=3) as s:
        s.set(rows=100)
    spans = _own_spans()
    assert len(spans) == 1
    (sp,) = spans
    assert sp.name == "work" and sp.cat == "test"
    assert sp.dur >= 0.0 and sp.t0 > 0.0
    assert sp.attrs == {"block": 3, "rows": 100}
    assert sp.lane == "main"  # the main thread's default lane


def test_lanes_are_thread_local():
    obs.enable_tracing()

    def worker(lane):
        obs.set_lane(lane)
        with obs.span("w"):
            pass

    threads = [threading.Thread(target=worker, args=(f"producer:{i}",))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(s.lane for s in _own_spans()) == [
        "producer:0", "producer:1", "producer:2"]


def test_chrome_trace_export_structure(tmp_path):
    obs.enable_tracing()
    with obs.span("outer", cat="pass"):
        with obs.span("inner", cat="ingest", block=0):
            pass
    path = obs.write_chrome_trace(tmp_path / "t.json")
    d = json.loads(path.read_text())
    events = d["traceEvents"]
    meta = [e for e in events if e["ph"] == "M" and e["name"] == "thread_name"]
    complete = [e for e in events if e["ph"] == "X" and e["cat"] != "process"]
    assert len(complete) == 2
    named = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta}
    for e in complete:
        assert named[(e["pid"], e["tid"])] == "main"
        assert e["ts"] >= 0 and e["dur"] >= 0
    inner = next(e for e in complete if e["name"] == "inner")
    assert inner["args"]["block"] == 0

    # the CI schema gate must accept what the exporter writes
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
    try:
        import check_bench
        lanes = check_bench.check_trace(path, min_lanes=1)
    finally:
        sys.path.pop(0)
    assert "main" in lanes and lanes <= {"main", "watchdog"}


def test_write_trace_jsonl_suffix(tmp_path):
    obs.enable_tracing()
    with obs.span("a", cat="c", x=1):
        pass
    path = obs.write_trace(tmp_path / "t.jsonl")
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 1
    assert lines[0]["name"] == "a" and lines[0]["lane"] == "main"
    assert lines[0]["x"] == 1


def test_disabled_span_enters_no_annotation(monkeypatch):
    from repro.obs import tracer as tracer_mod

    entered = []

    class Probe:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Probe)
    with obs.span("off") as s:
        pass
    assert s is obs.NULL_SPAN and entered == []
    obs.enable_tracing()
    with obs.span("on"):
        pass
    assert entered == ["on"]  # enabled, the span enters its annotation


def test_span_shows_on_the_profilers_host_plane(tmp_path):
    """Under jax.profiler an obs span is a TraceAnnotation of its name: it
    lands on the host plane inside the window annotation, on the profile's
    clock, and its duration agrees with the span's own."""
    import glob
    import os

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        obs.enable_tracing()
        with jax.profiler.TraceAnnotation("probe.window"):
            with obs.span("probe.outer"):
                with obs.span("probe.inner"):
                    jax.block_until_ready(jax.numpy.ones(8) + 1)
        obs.disable_tracing()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    by_name = {ev.name: ev for ev in host}
    win, outer, inner = (by_name[n] for n in
                         ("probe.window", "probe.outer", "probe.inner"))
    for ev in (outer, inner):
        assert win.start_ns <= ev.start_ns
        assert ev.start_ns + ev.duration_ns <= win.start_ns + win.duration_ns
    assert outer.start_ns <= inner.start_ns
    spans = {s.name: s for s in obs.TRACER.spans()}
    assert inner.duration_ns * 1e-9 == pytest.approx(spans["probe.inner"].dur,
                                                      abs=1e-3)


def test_span_ids_and_parents_nest_per_thread():
    obs.enable_tracing()
    ready = threading.Barrier(3)

    def worker(tag):
        obs.set_lane(tag)
        with obs.span(f"{tag}.outer"):
            ready.wait(timeout=10)  # every thread has its outer span open
            with obs.span(f"{tag}.inner"):
                pass

    with obs.span("main.outer"):
        threads = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
        for t in threads:
            t.start()
        ready.wait(timeout=10)
        with obs.span("main.inner"):
            pass
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
    spans = {s.name: s for s in _own_spans()}
    assert len({s.id for s in spans.values()}) == 6
    for tag in ("main", "a", "b"):
        assert spans[f"{tag}.outer"].parent is None  # not the other threads'
        assert spans[f"{tag}.inner"].parent == spans[f"{tag}.outer"].id
    with obs.span("after"):
        pass
    assert obs.TRACER.spans()[-1].parent is None  # the stacks emptied


def test_map_reduce_records_block_spans():
    import jax.numpy as jnp

    from repro.stream import engine

    store = gaussian_blobs_blocks(0, 512, 4, 2, block_rows=128)[0]
    emitted = []
    obs.enable_tracing()
    total = engine.map_reduce(store, lambda x: x.sum(), lambda a, b: a + b,
                              jnp.asarray(0.0), label="probe",
                              emit=lambda i, out: emitted.append(float(out)))
    spans = obs.TRACER.spans()
    consume = [s for s in spans if s.name == "block.consume"]
    assert [s.attrs for s in consume] == [
        {"block": i, "label": "probe"} for i in range(store.num_blocks)]
    (pass_span,) = [s for s in spans if s.name == "pass.probe"]
    # Deferred emit: block i is emitted inside the consume of block
    # i + prefetch (default 2), and the last consume drains the rest.
    emitted_in = {}
    for c in consume:
        assert c.parent == pass_span.id
        kids = [s for s in spans if s.parent == c.id]
        assert sorted(s.name for s in kids if s.name != "block.emit") == [
            "block.combine", "block.map"]
        emitted_in[c.attrs["block"]] = [
            s.attrs["block"] for s in kids if s.name == "block.emit"]
        assert sum(s.dur for s in kids) <= c.dur
    assert emitted_in == {0: [], 1: [], 2: [0], 3: [1, 2, 3]}
    emits = [s for s in spans if s.name == "block.emit"]
    assert sorted(s.attrs["block"] for s in emits) == list(range(store.num_blocks))
    assert {s.parent for s in emits} <= {c.id for c in consume}
    waits = [s for s in spans if s.name == "stall.queue_empty"]
    assert waits and all(s.parent == pass_span.id for s in waits)
    assert float(total) == pytest.approx(sum(emitted), rel=1e-5)


def test_serving_flush_spans_and_wait_counters():
    from repro.serving import ModelRegistry, ServingTier
    from repro.stream.microbatch import MicroBatcher

    obs.enable_tracing()
    before = obs.snapshot("serve.")
    mb = MicroBatcher(lambda X: np.zeros(X.shape[0], np.int32), max_batch=4,
                      name="probe")
    for i in range(10):
        mb.submit(i, np.zeros(3, np.float32))
    mb.drain()
    spans = obs.TRACER.spans()
    flushes = [s for s in spans if s.name == "serve.flush"]
    assert [s.attrs["flush"] for s in flushes] == [1, 2, 3]
    assert sum(s.attrs["rows"] for s in flushes) == 10
    assert {s.attrs["model"] for s in flushes} == {"probe"}
    for f in flushes:
        kids = sorted(s.name for s in spans if s.parent == f.id)
        assert kids == ["serve.deliver", "serve.device", "serve.stack"]

    obs.clear_trace()
    reg = ModelRegistry(max_batch=4)
    reg.register("echo", lambda X: np.zeros(len(X), np.int32), d=3)
    with ServingTier(reg) as tier:
        futs = [tier.submit(i, np.zeros(3, np.float32), model="echo")
                for i in range(10)]
        assert all(f.result(timeout=10).ok for f in futs)
    rows = [s.attrs["rows"] for s in obs.TRACER.spans() if s.name == "serve.flush"]
    assert sum(rows) == 10
    seen = obs.delta(before, obs.snapshot("serve."))
    assert seen["serve.batch_wait_s"] > 0
    assert seen["serve.intake_wait_s"] > 0


def test_gc_hook_records_collections_and_leaves_callbacks_as_found():
    import gc

    found = list(gc.callbacks)
    before = obs.counter("process.gc_pause_s").value
    obs.enable_tracing()
    assert len(gc.callbacks) == len(found) + 1
    with obs.span("outer") as outer:
        gc.collect()
    obs.disable_tracing()
    assert gc.callbacks == found
    (col,) = [s for s in obs.TRACER.spans()
              if s.name == "gc.collect" and s.attrs["generation"] == 2]
    assert col.parent == outer.id and col.attrs["collected"] >= 0
    assert obs.counter("process.gc_pause_s").value - before >= col.dur > 0
    recorded = len(obs.TRACER.spans())
    gc.collect()  # with tracing off, the hook is gone
    assert len(obs.TRACER.spans()) == recorded


def test_watchdog_records_an_injected_late_wakeup():
    from repro.obs.tracer import WATCHDOG_PERIOD_S, ProcessWatch, Tracer

    tracer = Tracer()
    tracer.enabled = True  # spans on, no real watchdog or gc hook
    now = [100.0]
    calls = []

    def sleep(s):
        calls.append(s)
        now[0] += s + (0.050 if len(calls) == 3 else 0.001)
        if len(calls) == 6:
            watch._halt.set()

    watch = ProcessWatch(tracer, clock=lambda: now[0], sleep=sleep)
    watch.run()
    (stall,) = tracer.spans()
    assert stall.name == "process.stall" and stall.lane == "watchdog"
    assert stall.attrs["late_ms"] == pytest.approx(50.0)
    assert stall.dur == pytest.approx(0.050)
    # from its due time: two on-time wake-ups of period + 1 ms before it
    assert stall.t0 == pytest.approx(100.0 + 3 * WATCHDOG_PERIOD_S + 0.002)


# ------------------------------------------------------------------ metrics


def test_counter_gauge_histogram_basics():
    obs.reset_metrics("t0.")
    c = obs.counter("t0.c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = obs.gauge("t0.g")
    g.set(7)
    g.set(3)
    assert g.value == 3 and g.hwm == 7
    h = obs.histogram("t0.h")
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100
    assert h.percentile(50) == pytest.approx(49.5, abs=1.0)
    stats = h.stats()
    assert stats["min"] == 0.0 and stats["max"] == 99.0
    assert stats["p99"] >= stats["p90"] >= stats["p50"]


def test_snapshot_reset_and_delta_are_prefix_scoped():
    obs.reset_metrics("t1.")
    obs.counter("t1.a").inc(5)
    before = obs.snapshot("t1.")
    obs.counter("t1.a").inc(2)
    after = obs.snapshot("t1.")
    assert obs.delta(before, after)["t1.a"] == 2
    c = obs.counter("t1.a")
    obs.reset_metrics("t1.")
    assert obs.snapshot("t1.")["t1.a"] == 0
    c.inc()  # held references keep working across reset
    assert obs.counter("t1.a").value == 1


def test_scoped_metrics_context():
    obs.reset_metrics("t2.")
    obs.counter("t2.n").inc(10)
    with obs.scoped("t2.") as seen:
        obs.counter("t2.n").inc(4)
    assert seen["t2.n"] == 4


def test_counter_thread_safety():
    obs.reset_metrics("t3.")
    c = obs.counter("t3.hits")
    N, T = 10_000, 8

    def worker():
        for _ in range(N):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T  # no lost updates under concurrent writers


# ---------------------------------------------------------------- FitReport


def _fit(backend, **kw):
    X = gaussian_blobs_blocks(0, 1024, 8, 3, block_rows=256)[0]
    est = KernelKMeans(3, kernel=Kernel("rbf", gamma=0.1), method="rff", m=32,
                       backend=backend, iters=5, n_init=1, random_state=7, **kw)
    est.fit(X, key=jax.random.PRNGKey(7))
    return est


@pytest.mark.parametrize("backend", ["local", "stream", "stream_shard",
                                     "minibatch", "shard_map"])
def test_every_backend_returns_populated_fit_report(backend):
    est = _fit(backend)
    r = est.fit_report_
    assert isinstance(r, obs.FitReport)
    assert r.backend == backend
    assert r.iters >= 1 and r.rows_seen > 0
    assert len(r.inertia_trajectory) == r.iters + 1
    # the trajectory must END at the model's reported inertia (acceptance)
    assert r.inertia_trajectory[-1] == pytest.approx(est.inertia_, rel=1e-6)
    assert set(r.phases) >= {"reservoir", "embed_fit", "seed", "lloyd"}
    assert all(v >= 0 for v in r.phases.values())
    # the report is the model's report — one object, two access paths
    assert est.model_.report is r
    if backend in ("stream", "stream_shard", "minibatch"):
        assert r.blocks_read > 0 and r.bytes_h2d > 0
        assert sum(r.pass_counts.values()) > 0
        assert sum(r.per_device_blocks.values()) == r.blocks_read


def test_pool_scheduler_fit_report_accounts_blocks():
    """The pool control plane's workers bump the same engine counters as the
    lockstep producers, so FitReport parity holds for scheduler="pool" too:
    the per-device breakdown sums to blocks_read exactly (stale speculative
    workers are drained before the fit returns), and the fault-free pool
    accounting identity pool.tasks_completed == blocks x (iters + 1) is
    visible in the metrics registry."""
    from repro.data.synthetic import gaussian_blobs_blocks

    store = gaussian_blobs_blocks(0, 1024, 8, 3, block_rows=256)[0]
    before = obs.snapshot("pool.")
    est = _fit("stream_shard", scheduler="pool")
    seen = obs.delta(before, obs.snapshot("pool."))
    r = est.fit_report_
    assert r.blocks_read > 0 and r.bytes_h2d > 0
    assert sum(r.per_device_blocks.values()) == r.blocks_read
    assert r.inertia_trajectory[-1] == pytest.approx(est.inertia_, rel=1e-6)
    assert seen["pool.tasks_completed"] == store.num_blocks * (est.n_iter_ + 1)


def test_exact_backends_report_identical_trajectories():
    """local / stream / stream_shard run the SAME math from the same key, so
    their FitReports must agree on shape AND trajectory — the keystone label
    identity, visible through the observability layer."""
    reports = {b: _fit(b).fit_report_
               for b in ("local", "stream", "stream_shard")}
    ref = reports["local"]
    assert ref.iters >= 1
    for name, r in reports.items():
        assert r.iters == ref.iters, name
        assert len(r.inertia_trajectory) == len(ref.inertia_trajectory), name
        np.testing.assert_allclose(
            r.inertia_trajectory, ref.inertia_trajectory, rtol=1e-4,
            err_msg=name)
        np.testing.assert_allclose(r.centroid_shifts, ref.centroid_shifts,
                                   rtol=1e-3, atol=1e-5, err_msg=name)


def test_fit_report_serializes(tmp_path):
    est = _fit("stream")
    out = tmp_path / "report.json"
    est.fit_report_.to_json(out)
    d = json.loads(out.read_text())
    assert d["backend"] == "stream"
    assert d["inertia_trajectory"] == est.fit_report_.inertia_trajectory
    assert "lloyd" in d["phases"]
    assert "lloyd=" in est.fit_report_.summary()


def test_sweep_attaches_report():
    X = gaussian_blobs_blocks(0, 1024, 8, 3, block_rows=256)[0]
    est = KernelKMeans(3, kernel=Kernel("rbf", gamma=0.1), method="rff", m=32,
                       backend="stream", iters=4, random_state=7)
    result = est.sweep(X, [2, 3], restarts=2, key=jax.random.PRNGKey(7))
    r = result.report
    assert isinstance(r, obs.FitReport)
    assert r is est.fit_report_
    assert r.extra["sweep"] is True
    assert r.extra["k_grid"] == [2, 3] and r.extra["candidates"] == 4
    assert r.extra["resumed"] is False
    assert "embed_cache" in r.phases and "lloyd" in r.phases
    assert r.blocks_read > 0


# ------------------------------------------------------------ roofline join


def test_roofline_join_synthetic_record():
    from repro.roofline.analysis import HBM_BW, PEAK_FLOPS

    # a synthetic pass that would take exactly 1ms at peak compute and is
    # compute-bound; measured at 2ms -> model_fraction 0.5
    rec = {"flops": PEAK_FLOPS * 1e-3, "hbm_bytes": HBM_BW * 1e-4,
           "collective_bytes": 0.0}
    out = obs.roofline_join(2e-3, rec)
    assert out["bottleneck"] == "compute"
    assert out["modeled_s"] == pytest.approx(1e-3)
    assert out["model_fraction"] == pytest.approx(0.5)

    report = obs.FitReport(backend="stream", phases={"lloyd": 8e-3},
                           pass_counts={"map_reduce": 4}, iters=3)
    joined = obs.join_fit_roofline(report, rec)
    assert joined["passes"] == 4
    assert joined["measured_s"] == pytest.approx(2e-3)  # 8ms over 4 passes
    assert joined["model_fraction"] == pytest.approx(0.5)


# ------------------------------------------------------------ serve metrics


def test_microbatcher_feeds_serve_metrics():
    from repro.stream.microbatch import MicroBatcher

    obs.reset_metrics("serve.")
    mb = MicroBatcher(lambda X: np.zeros(X.shape[0], np.int32), max_batch=4)
    for i in range(10):
        mb.submit(i, np.zeros(3, np.float32))
    mb.drain()
    snap = obs.snapshot("serve.")
    assert snap["serve.batch_size"]["count"] == 3  # 4 + 4 + 2
    assert snap["serve.batch_size"]["sum"] == 10
    assert snap["serve.batch_size"]["max"] == 4
    assert snap["serve.batch_wait_s"] > 0
    assert "serve.latency_ms" not in snap and "serve.queue_depth" not in snap
