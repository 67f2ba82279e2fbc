"""A whole run, minus the look for a chip, with the timed path broken
underneath: `correct` must come out false for each fault a cell can have.

The faults (bench/harness/faults.py) are planted in the program from outside:
a Lloyd update that returns its state unchanged, the mean taken over half of
each block, and an answer altered where it is produced (a fit's final labels,
a served label). The exchange between chips is left out in
test_bench_shard.py, which needs four devices.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench.harness import faults, runner, serve_traffic
from bench.tests.cells import small_cell


def run(name, seed=21, seconds=600.0, **kw):
    cell = small_cell(name)
    kw.setdefault("window_fits", 1 if cell.traffic["kind"] == "fit" else None)
    return runner.run_cell(cell, seed, seconds, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           compile_cache=False, **kw)


def failed(r):
    return {k for k, c in r["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("name", ["covtype-rff.fit", "imagenet-nystrom.fit",
                                  "imagenet-nystrom.fit-resident"])
@pytest.mark.parametrize("fault", sorted(faults.FIT))
def test_fit_fault_is_caught(monkeypatch, name, fault):
    faults.FIT[fault](monkeypatch)
    r = run(name)
    assert not r["correct"], (fault, r["checks"])
    assert failed(r), r["checks"]


def test_sound_serve_is_correct():
    r = run("imagenet-nystrom.serve", seconds=1.0)
    assert r["correct"], r["checks"]
    assert {"inertia_gap", "centroid_gap", "whiten_gap"} <= set(r["checks"])
    assert r["failed"] == 0 and r["attempted"] == 1000  # 1,000/s for 1 s
    assert r["run"]["latency_ms"]["p99"] >= r["metrics"]["assign_p50_ms"]["value"] > 0


def test_served_label_altered_is_caught(monkeypatch):
    faults.altered_served(monkeypatch)
    r = run("imagenet-nystrom.serve", seconds=1.0)
    assert not r["correct"] and "assign_gap" in failed(r), r["checks"]


def test_serve_bf16_control_fails():
    from repro.policy import ComputePolicy

    r = run("imagenet-nystrom.serve", seconds=1.0,
            policy=ComputePolicy(pallas=False, precision="bf16"))
    assert not r["correct"] and "inertia_gap" in failed(r), r["checks"]


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_serve_bf16_control_fails_on_every_seed(seed):
    """The served model is fitted under the control's policy too, so the
    control fails the model's numbers whatever rows the window serves."""
    from repro.policy import ComputePolicy

    r = run("imagenet-nystrom.serve", seed=seed, seconds=0.3,
            policy=ComputePolicy(pallas=False, precision="bf16"))
    assert not r["correct"] and "inertia_gap" in failed(r), r["checks"]


def test_quantile_is_nearest_rank():
    v = jnp.arange(1, 101, dtype=jnp.float32)
    import numpy as np

    v = np.asarray(v)
    assert serve_traffic.quantile(v, 0.5) == 50.0
    assert serve_traffic.quantile(v, 0.99) == 99.0
    assert serve_traffic.quantile(v[:10], 0.99) == 10.0
