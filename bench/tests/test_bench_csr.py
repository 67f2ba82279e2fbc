"""The rcv1-nystrom.fit cell at a small size on the CPU: its row format
(`harness/rows_csr.py`), the comparison that decides `correct` (sound on
every seed; the bf16 control and each planted fault fail), and the readers
of its two per-layer metrics. Only the row count shrinks: d = 47,236 and
every other width are the configuration's own."""
from __future__ import annotations

import dataclasses
import time
import types

import numpy as np
import pytest

from bench.harness import data, faults, roofline, roofline_csr, rows_csr, runner, spec, trace

CELL = "rcv1-nystrom.fit"
SMALL_N = 4096 + 904  # one full block and a short one


def small_cell():
    cell = spec.load_cell(CELL)
    return dataclasses.replace(cell, config=dict(cell.config, n=SMALL_N))


def run(seed, **kw):
    return runner.run_cell(small_cell(), seed, 600.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           compile_cache=False, window_fits=1, **kw)


def failed(r):
    return {k for k, c in r["checks"].items() if c["value"] > c["limit"]}


# ------------------------------------------------------------ row format


def test_the_cell_loads_with_its_row_format_and_metrics():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "fit"
    assert cell.traffic["backend"] == "stream"
    assert spec.rows_module(cell.config["rows"]) is rows_csr
    names = {m["name"] for m in cell.per_layer}
    assert {"sparse_embed_roofline", "csr_block_fill", "device_idle.fit",
            "phase1_share", "block_host_ms", "engine_wait_share"} <= names
    assert "fused_step_roofline" not in names
    cfg = cell.config
    assert (cfg["n"], cfg["d"], cfg["k"]) == (193844, 47236, 103)
    assert cfg["gamma_times_d"] / cfg["d"] == 0.5


@pytest.fixture(scope="module")
def rows():
    return rows_csr.make(2**31 + 3, small_cell().config, 20000)


def test_csr_rows_are_deterministic_by_seed(rows):
    cfg = small_cell().config
    again = rows_csr.make(2**31 + 3, cfg, 20000)
    other = rows_csr.make(2**31 + 4, cfg, 20000)
    for f in ("indptr", "indices", "data"):
        assert np.array_equal(getattr(rows, f), getattr(again, f))
    assert not np.array_equal(rows.indptr, other.indptr)
    held = rows_csr.make(2**31 + 3, cfg, 20000, data.STREAM_HELD_OUT)
    assert not np.array_equal(rows.indptr, held.indptr)


def test_csr_rows_have_rcv1s_shape(rows):
    n, d = rows.shape
    lens = np.diff(rows.indptr)
    assert abs(lens.mean() - 75) < 3 and lens.min() >= 1 and lens.max() <= 1000
    norms = np.sqrt(np.bincount(np.repeat(np.arange(n), lens),
                                weights=rows.data.astype(np.float64) ** 2))
    np.testing.assert_allclose(norms, 1.0, atol=1e-6)
    assert rows.indices.min() >= 0 and rows.indices.max() < d
    assert np.all(rows.data > 0)
    for r in range(0, n, 997):  # distinct, sorted ids in a row
        assert np.all(np.diff(rows.indices[rows.indptr[r]:rows.indptr[r + 1]]) > 0)


def test_ref_chunks_densify_to_the_csr(rows):
    sp = pytest.importorskip("scipy.sparse")
    head = rows_csr.head(rows, 2 * rows_csr.REF_CHUNK_ROWS + 300)
    X = sp.csr_matrix((head.data, head.indices, head.indptr), shape=head.shape)
    chunks = [np.asarray(c) for c in rows_csr.ref_chunks(head)]
    assert [c.shape[0] for c in chunks] == [1024, 1024, 300]
    assert np.array_equal(np.concatenate(chunks), X.toarray())


def test_program_input_never_densifies(rows, monkeypatch):
    from repro.stream.blockstore import CSRBlock, CSRBlockStore

    def refuse(*a, **k):
        raise AssertionError("densified")

    monkeypatch.setattr(CSRBlock, "toarray", refuse)
    monkeypatch.setattr(CSRBlockStore, "materialize", refuse)
    rows = dataclasses.replace(rows)
    store = rows_csr.program_input(rows, 4096, "host", [])
    assert isinstance(store, CSRBlockStore) and rows.capacity == store.nnz_capacity
    assert all(isinstance(store.get(i), CSRBlock) for i in range(store.num_blocks))
    # the first rows' store gets the full store's block shape
    warm = rows_csr.program_input(rows_csr.head(rows, 5000), 4096, "host", [])
    assert warm.get(0).slots == store.nnz_capacity
    with pytest.raises(ValueError):
        rows_csr.program_input(rows, 4096, "device", [])


# ------------------------------------------------------------ correct


@pytest.mark.parametrize("seed", [0, 810412167, 2**31 + 5])
def test_sound_csr_fit_is_correct(seed):
    r = run(seed)
    assert r["correct"], r["checks"]
    assert r["checks"]["landmark_miss"]["value"] == 0
    assert r["metrics"]["fit_rows_per_s"]["value"] > 0


def test_csr_bf16_control_fails():
    from repro.policy import ComputePolicy

    r = run(11, policy=ComputePolicy(pallas=False, precision="bf16"))
    assert not r["correct"], r["checks"]
    assert failed(r) & {"assign_gap", "inertia_gap", "centroid_gap"}, r["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FIT))
def test_csr_fit_fault_is_caught(monkeypatch, fault):
    faults.FIT[fault](monkeypatch)
    r = run(21)
    assert not r["correct"], (fault, r["checks"])
    assert failed(r), r["checks"]


# ------------------------------------------------------------ readers


def test_sparse_embed_counts_at_an_rcv1_block():
    ops, nbytes = roofline_csr.sparse_embed(307200, 4096, 300, 200)
    assert ops == 2 * 307200 * 300 + 2 * 4096 * 300 * 200
    # CSR block + landmarks as CSR rows (75 a row) + R + Y out
    assert nbytes == 4 * ((2 * 307200 + 4096 + 1) + (2 * 300 * 75 + 300 + 1)
                          + 200 * 300 + 4096 * 200)
    least, bound = roofline.least_seconds(ops, nbytes, roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def _ctx(counters, cell=CELL):
    return types.SimpleNamespace(
        kind="fit", cell=spec.load_cell(cell), device={"kind": "TPU v5 lite"},
        counters=counters, trace=trace.Trace(window=(0.0, 1e9), devices={}))


def test_sparse_embed_roofline_reads_its_module_and_nothing_else(monkeypatch):
    reader = spec.metric_reader("sparse_embed_roofline")
    cfg = spec.load_cell(CELL).config
    b = cfg["n"] / 48
    nnz = 300000.0
    least, _ = roofline.least_seconds(
        *roofline_csr.sparse_embed(nnz, b, 300, 200), roofline.peaks("TPU v5 lite"))
    events = [(f"jit__sparse_embed_block({i})", i * 1e7, 4 * least * 1e9)
              for i in range(96)]
    events += [("jit__assign_stats_cost_y(1)", 5e8, 1e6),
               ("jit__sparse_embed_block(9)", 2e9, 1e6)]  # outside the window
    monkeypatch.setattr(reader, "trace_file", lambda ctx: "x.xplane.pb")
    monkeypatch.setattr(reader, "module_events", lambda path: events)
    counters = {"sparse.blocks": 96.0, "sparse.nnz": 96 * nnz}
    assert reader.read(_ctx(counters)) == pytest.approx(25.0)
    assert reader.read(_ctx({})) is None
    monkeypatch.setattr(reader, "module_events", lambda path: events[96:97])
    assert reader.read(_ctx(counters)) is None


def test_module_events_parse_a_recorded_v5e_trace():
    reader = spec.metric_reader("sparse_embed_roofline")
    events = reader.module_events(str(spec.BENCH_DIR / "fixtures" / "tiny_trace.xplane.pb"))
    assert events and all(dur > 0 for _, _, dur in events)
    assert any(name.startswith("jit_") for name, _, _ in events)


def test_csr_block_fill_reads_the_counters():
    reader = spec.metric_reader("csr_block_fill")
    ctx = _ctx({"sparse.nnz": 300.0, "sparse.slots": 400.0})
    assert reader.read(ctx) == pytest.approx(75.0)
    assert reader.read(_ctx({"sparse.slots": 0.0})) is None
    assert reader.read(_ctx({}, "imagenet-nystrom.fit")) is None
