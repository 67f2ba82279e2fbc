"""Row formats: the mixture format makes the rows `data.make_rows` makes, the
reference reads them as chunks exactly as it read slices of a dense X, and a
format whose rows are not an ndarray runs a fit cell with nothing but its own
module (the resolver is the only thing patched)."""
from __future__ import annotations

import dataclasses
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import data, reference, rows_mixture, runner, spec
from bench.tests.cells import small_cell


@pytest.mark.parametrize("name", ["covtype-rff.fit", "imagenet-nystrom.fit"])
def test_mixture_rows_are_make_rows_bit_for_bit(name):
    cfg = small_cell(name).config
    fmt = spec.rows_module(cfg.get("rows", "mixture"))
    assert fmt is rows_mixture
    for stream in (data.STREAM_FIT, data.STREAM_HELD_OUT):
        got = fmt.make(2**31 + 3, cfg, 5000, stream)
        want = data.make_rows(2**31 + 3, cfg, 5000, stream)
        assert got.dtype == want.dtype and np.array_equal(got, want)
    rows = fmt.make(4, cfg, 5000)
    assert np.array_equal(np.concatenate(list(fmt.ref_chunks(rows))), rows)
    assert np.array_equal(fmt.head(rows, 123), rows[:123])


def _dense_slice_score_rows(X, embed, ref_params, centroids, labels, discrepancy):
    """score_rows as it read a dense X before row formats: fixed slices of
    reference.REF_CHUNK rows, the last padded."""
    chunk = reference.REF_CHUNK
    n, k = X.shape[0], centroids.shape[0]
    C = jnp.asarray(centroids, jnp.float32)
    sum_dmin = sum_dL = worst = 0.0
    Z = g = None
    for lo in range(0, n, chunk):
        rows = min(chunk, n - lo)
        part = X[lo:lo + rows]
        lab = labels[lo:lo + rows].astype(np.int32)
        valid = np.ones(chunk, np.float32)
        if rows < chunk:
            part = np.pad(part, ((0, chunk - rows), (0, 0)))
            lab = np.pad(lab, (0, chunk - rows))
            valid[rows:] = 0.0
        dmin, dL, Zc, gc = reference._score_chunk(
            embed, discrepancy, ref_params, jnp.asarray(part), C,
            jnp.asarray(lab), jnp.asarray(valid))
        dmin = np.asarray(dmin, np.float64)
        dL = np.asarray(dL, np.float64)
        sum_dmin += float(dmin.sum())
        sum_dL += float(dL.sum())
        worst = max(worst, float(np.max(dL - dmin)))
        Zc, gc = np.asarray(Zc, np.float64), np.asarray(gc, np.float64)
        Z = Zc if Z is None else Z + Zc
        g = gc if g is None else g + gc
    assert Z.shape[0] == k
    return {"sum_dmin": sum_dmin, "sum_dL": sum_dL, "worst": worst, "Z": Z,
            "g": g, "n": n}


def test_score_rows_over_chunks_equals_dense_slices():
    cell = small_cell("covtype-rff.fit")
    cfg = cell.config
    n = 2 * rows_mixture.REF_CHUNK_ROWS + 1000  # two whole chunks and a short one
    X = rows_mixture.make(9, cfg, n)
    rng = np.random.default_rng(9)
    C = 0.05 * rng.standard_normal((cfg["k"], 2 * cfg["m"])).astype(np.float32)
    labels = rng.integers(0, cfg["k"], n).astype(np.int32)
    ref_params, _ = cell.reference.reference_params(
        cfg, data.base_key(9), {"W": np.zeros((cfg["d"], cfg["m"]))}, X)
    got = reference.score_rows(rows_mixture.ref_chunks(X), rows_mixture.REF_CHUNK_ROWS,
                               cell.reference.embed, ref_params, C, labels, "l2")
    want = _dense_slice_score_rows(X, cell.reference.embed, ref_params, C, labels, "l2")
    assert set(got) == set(want)
    for key in ("sum_dmin", "sum_dL", "worst", "n"):
        assert got[key] == want[key], key
    assert np.array_equal(got["Z"], want["Z"]) and np.array_equal(got["g"], want["g"])


def _block_rows_format():
    """A row format whose rows are a list of (block_rows, d) host blocks,
    streamed to the program through a generator-backed BlockStore."""
    seen = {}

    def make(seed, config, n, stream=data.STREAM_FIT):
        X = data.make_rows(seed, config, n, stream)
        return [X[lo:lo + config["block_rows"]] for lo in range(0, n, config["block_rows"])]

    def head(rows, n):
        out, left = [], n
        for b in rows:
            if left <= 0:
                break
            out.append(b[:left])
            left -= b.shape[0]
        return out

    def program_input(rows, block_rows, hold, devices):
        from repro.stream.blockstore import BlockStore

        assert hold == "host"
        store = BlockStore.from_generator(
            lambda i: rows[i], n=sum(b.shape[0] for b in rows),
            d=rows[0].shape[1], block_rows=block_rows)
        seen.setdefault("inputs", []).append(store)
        return store

    def ref_chunks(rows):
        seen["chunked"] = seen.get("chunked", 0) + 1
        yield from rows

    return types.SimpleNamespace(make=make, head=head, program_input=program_input,
                                 ref_chunks=ref_chunks, REF_CHUNK_ROWS=4096,
                                 seen=seen)


def test_a_row_format_that_is_not_an_ndarray_runs_a_fit_cell(monkeypatch):
    fmt = _block_rows_format()
    real = spec.rows_module
    monkeypatch.setattr(spec, "rows_module",
                        lambda name: fmt if name == "blocks" else real(name))
    cell = small_cell("covtype-rff.fit")
    cell = dataclasses.replace(cell, config=dict(cell.config, rows="blocks"))
    r = runner.run_cell(cell, 17, 600.0, False, t_process=time.perf_counter(),
                        require_tpu=False, compile_cache=False, window_fits=2)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 and r["metrics"]["fit_rows_per_s"]["value"] > 0
    assert len(fmt.seen["inputs"]) == 2  # the warm-up store and the window's
    assert fmt.seen["chunked"] == len(r["run"]["compared"]) == 2
