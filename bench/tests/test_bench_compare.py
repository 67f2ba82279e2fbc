"""The comparison that decides `correct`, at a small size on the CPU.

Sound fits pass on every seed, wherever Lloyd stopped; a fit run in the
program's own bfloat16 path (the control: one precision below the float32
the configurations state) fails. A fit stopped at the `iters` cap is held to
its labels and inertia, and the test shows why it cannot be held to the
fixed point it never reached: its update is checked by the program's next
one.
"""
from __future__ import annotations

import dataclasses
import time

import pytest

from bench.harness import faults, fit_traffic, reference, runner
from bench.tests.cells import small_cell

SEEDS = [0, 7, 810412167, 2**31 + 5]


def run(cell, seed, **kw):
    kw.setdefault("window_fits", 1)
    return runner.run_cell(cell, seed, 600.0, False, t_process=time.perf_counter(),
                           require_tpu=False, compile_cache=False, **kw)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_fit_is_correct_on_every_seed(seed):
    r = run(small_cell("covtype-rff.fit"), seed)
    assert r["correct"], r["checks"]
    assert r["metrics"]["fit_rows_per_s"]["value"] > 0


def test_sound_nystrom_fit_is_correct():
    r = run(small_cell("imagenet-nystrom.fit"), 3)
    assert r["correct"], r["checks"]
    assert r["checks"]["landmark_miss"]["value"] == 0


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 810412167])
def test_sound_resident_fit_is_correct(seed):
    """X held in device memory, fitted by the local driver."""
    r = run(small_cell("imagenet-nystrom.fit-resident"), seed)
    assert r["correct"], r["checks"]
    assert r["checks"]["landmark_miss"]["value"] == 0
    assert r["metrics"]["fit_rows_per_s"]["value"] > 0


@pytest.mark.parametrize("name", ["covtype-rff.fit", "imagenet-nystrom.fit",
                                  "imagenet-nystrom.fit-resident"])
def test_bf16_control_fails(name):
    from repro.policy import ComputePolicy

    r = run(small_cell(name), 11, policy=ComputePolicy(pallas=False, precision="bf16"))
    assert not r["correct"], r["checks"]
    failed = [k for k, c in r["checks"].items() if c["value"] > c["limit"]]
    assert set(failed) & {"assign_gap", "inertia_gap", "centroid_gap"}, r["checks"]


def _capped_cell(name="covtype-rff.fit", iters=2):
    cell = small_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, iters=iters))


def test_capped_fit_is_held_to_what_it_returned():
    cell = _capped_cell()
    s = fit_traffic.setup(cell, 5)
    w = fit_traffic.run_window(s, 600.0, max_fits=1)
    (a,) = w.answers
    assert a.n_iter == 2 and not a.converged
    records, numbers = fit_traffic.compare(cell, s, w, 1)
    limits = runner._limits("covtype-rff.fit")
    ok, checks = runner.judge(numbers, limits)
    assert ok, checks
    assert set(checks) == set(limits)
    # The centroids of a capped fit are one update behind its labels: held
    # to the mean of its own labels (a fixed point it never reached) they
    # miss the limit by orders of magnitude. This is the comparison that
    # refused the first benchmark at seed 810412167. Its centroid_gap is
    # read from the program's next update instead.
    gap = _gap_to_returned_centroids(cell, s, a)
    assert gap > 10 * limits["centroid_gap"]["limit"]
    assert checks["centroid_gap"]["value"] < limits["centroid_gap"]["limit"]


def _gap_to_returned_centroids(cell, s, a):
    """centroid_gap of a fit's returned centroids against the mean of its
    returned labels (what a fixed point would have to read)."""
    ref_params = cell.reference.reference_params(
        cell.config, fit_traffic.fit_key(s.seed, a.key_seed), a.params, s.rows)[0]
    scored = reference.score_rows(s.fmt.ref_chunks(s.rows), s.fmt.REF_CHUNK_ROWS,
                                  cell.reference.embed, ref_params, a.centroids,
                                  a.labels, cell.config["discrepancy"])
    return reference.fit_numbers(a, scored)["centroid_gap"]


def test_capped_resident_fit_is_held_to_the_local_update():
    """A resident fit stopped at the cap is held by the local driver's next
    update (one `core.lloyd.lloyd` iteration), not the stream driver's. The
    well-separated small cell is at a fixed point after two iterations, so
    the cap is one."""
    name = "imagenet-nystrom.fit-resident"
    cell = _capped_cell(name, iters=1)
    s = fit_traffic.setup(cell, 5)
    w = fit_traffic.run_window(s, 600.0, max_fits=1)
    (a,) = w.answers
    assert a.model.meta.backend == "local"
    assert a.n_iter == 1 and not a.converged
    _, numbers = fit_traffic.compare(cell, s, w, 1)
    limits = runner._limits(name)
    ok, checks = runner.judge(numbers, limits)
    assert ok, checks
    assert _gap_to_returned_centroids(cell, s, a) > 10 * limits["centroid_gap"]["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_capped_fit_fault_is_caught(monkeypatch, fault):
    """A wrong centroid update is caught in a fit that stopped at the cap,
    where the centroids are not compared as they were returned."""
    faults.FIT[fault](monkeypatch)
    _assert_capped_and_caught(run(_capped_cell(), 5))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_capped_resident_fit_fault_is_caught(monkeypatch, fault):
    faults.FIT[fault](monkeypatch)
    _assert_capped_and_caught(run(_capped_cell("imagenet-nystrom.fit-resident", 1), 5))


def _assert_capped_and_caught(r):
    assert r["run"]["compared"][0]["converged"] is False
    assert not r["correct"], r["checks"]
    assert r["checks"]["centroid_gap"]["value"] > r["checks"]["centroid_gap"]["limit"]


def test_judge_requires_every_unconditional_number():
    limits = {"a": {"limit": 1.0}, "b": {"limit": 1.0}}
    assert runner.judge([{"a": 0.5}, {"b": 0.5}], limits)[0]
    assert not runner.judge([{"b": 0.5}], limits)[0]
    assert not runner.judge([{"a": 2.0}, {"b": 0.5}], limits)[0]
