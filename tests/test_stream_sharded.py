"""Tests for the multi-device sharded out-of-core backend (repro.stream.sharded).

The load-bearing claims:
  * the sharded executor's per-device accumulators + cross_device_sum equal
    the monolithic reduction;
  * KEYSTONE: backend="stream_shard" reaches labels IDENTICAL to the
    single-device backend="stream" from the same key, for every registered
    embedding member, through the public API;
  * the staged-Y path (a sharded WritableBlockStore) reaches the same labels
    as the fused embed+assign path;
  * backend="auto" prefers stream_shard exactly when a BlockStore input and a
    mesh with >1 data-axis device coexist;
  * sharded mini-batch clusters no worse than single-device mini-batch
    (its per-round update is a different — approximate — trajectory).

Device count adapts to the running process: the CI tier-1 matrix entry (and
any local run with XLA_FLAGS=--xla_force_host_platform_device_count=8) makes
every in-process test genuinely multi-device; a single-device process runs
the same code paths with D=1. One subprocess test forces 8 devices regardless,
so the multi-device seams are exercised on every tier-1 run.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelKMeans
from repro.core.kernels_fn import Kernel
from repro.core.metrics import nmi
from repro.data.synthetic import gaussian_blobs_blocks
from repro.launch.mesh import make_mesh
from repro.stream import (
    BlockStore,
    cross_device_sum,
    minibatch_lloyd,
    ooc_lloyd,
    shard_devices,
    sharded_map_reduce,
    stream_embed,
)

HERE = Path(__file__).resolve().parent
DEVICES = jax.local_devices()
D = len(DEVICES)

multi_device = pytest.mark.skipif(
    D < 2, reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)"
)


def _mesh(data=D, model=1):
    return make_mesh((data, model), ("data", "model"))


# ----------------------------------------------------------------- executor


def test_shard_devices_default_and_mesh():
    assert shard_devices(None) == DEVICES
    assert shard_devices(_mesh()) == DEVICES


@multi_device
def test_shard_devices_skips_model_axis():
    # one stream per DATA coordinate: the model axis carries no rows
    mesh = _mesh(data=D // 2, model=2)
    devs = shard_devices(mesh)
    assert len(devs) == D // 2
    assert len(set(devs)) == len(devs)


def test_sharded_map_reduce_matches_monolithic_sum():
    store, _ = gaussian_blobs_blocks(2, 1000, 5, 3, block_rows=128)
    shards = [store.shard(d, D) for d in range(D)]
    fn = jax.jit(lambda x: jnp.sum(x, axis=0))
    inits = [jax.device_put(jnp.zeros(5), dev) for dev in DEVICES]
    seen = [[] for _ in range(D)]
    accs = sharded_map_reduce(
        shards, [fn] * D, lambda a, b: a + b, inits, devices=DEVICES,
        emits=[lambda i, _, s=s: s.append(i) for s in seen],
    )
    assert len(accs) == D
    for d in range(D):  # each device saw its own round-robin shard, in order
        assert seen[d] == list(range(shards[d].num_blocks))
    total = cross_device_sum(accs, DEVICES)
    np.testing.assert_allclose(
        np.asarray(total), store.materialize().sum(axis=0), rtol=1e-5
    )


def test_sharded_map_reduce_propagates_worker_errors():
    bad = BlockStore.from_generator(
        lambda i: (_ for _ in ()).throw(RuntimeError("shard boom")),
        n=100 * D, d=2, block_rows=50,
    )
    shards = [bad.shard(d, D) for d in range(D)]
    with pytest.raises(RuntimeError, match="shard boom"):
        sharded_map_reduce(
            shards, [lambda x: x] * D, lambda a, b: b, [None] * D,
            devices=DEVICES,
        )


# ----------------------------------------------------------------- keystone


from _sharded_setups import SETUPS  # one table with tests/sharded_checks.py


@pytest.mark.parametrize("method", sorted(SETUPS))
def test_stream_shard_labels_identical_to_stream(method):
    """The keystone claim, via the public API: sharding the block stream
    across the mesh must not change the answer — identical labels to the
    single-device stream backend from the same key, for every member."""
    kernel_name, kernel_params, kw = SETUPS[method]
    store, y = gaussian_blobs_blocks(0, 1200, 8, 4, block_rows=128, separation=4.0)
    common = dict(kernel=Kernel(kernel_name, **kernel_params), method=method,
                  iters=12, n_init=1, block_rows=128, **kw)
    key = jax.random.PRNGKey(7)
    a = KernelKMeans(4, backend="stream", **common).fit(store, key=key)
    b = KernelKMeans(4, backend="stream_shard", mesh=_mesh(), **common).fit(
        store, key=key)
    assert b.backend_ == "stream_shard"
    assert np.array_equal(a.labels_, b.labels_), method
    assert b.inertia_ == pytest.approx(a.inertia_, rel=1e-4)
    assert b.n_iter_ == a.n_iter_
    assert b.model_.meta.rows_seen == a.model_.meta.rows_seen
    # sanity floor only (n_init=1 can land in a local optimum); the claim
    # under test is the label identity above, not clustering quality
    truth = np.concatenate([np.asarray(blk).ravel() for blk in y])
    assert nmi(b.labels_, truth) > 0.6, method


def test_stream_shard_forced_8_devices_subprocess():
    """Run the keystone equality under a FORCED 8-device process, so every
    tier-1 run exercises the genuinely multi-device seams (cross-device
    reduction, per-device producers) even when this pytest process sees one
    device. The full four-member sweep runs in the CI 8-device matrix entry."""
    proc = subprocess.run(
        [sys.executable, str(HERE / "sharded_checks.py"), "nystrom,rff"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["devices"] == 8, report
    for method in ("nystrom", "rff"):
        assert report[f"{method}_backend"] == "stream_shard"
        assert report[f"{method}_labels_equal"], report
        assert report[f"{method}_inertia_rel_err"] < 1e-4
    # observability under 8 real producer threads (see sharded_checks.py)
    assert report["obs_blocks_read"] > 0
    assert report["obs_device_counters"] == 8, report
    assert report["obs_per_device_sum_matches"], report
    assert report["obs_producer_lanes"] == 8, report


def test_stream_shard_label_identity_under_pallas_policy():
    """Regression: the sharded FINAL pass must assign through the same
    policy-routed kernel as the single-device stream backend — under a
    Pallas-enabled policy (interpret mode on CPU) the label identity must
    still hold."""
    from repro.api import ComputePolicy

    store, _ = gaussian_blobs_blocks(0, 600, 8, 3, block_rows=128, separation=4.0)
    pol = ComputePolicy(pallas=True)
    common = dict(kernel=Kernel("rbf", gamma=0.1), l=48, m=32, iters=8,
                  n_init=1, block_rows=128, policy=pol)
    key = jax.random.PRNGKey(7)
    a = KernelKMeans(3, backend="stream", **common).fit(store, key=key)
    b = KernelKMeans(3, backend="stream_shard", mesh=_mesh(), **common).fit(
        store, key=key)
    assert np.array_equal(a.labels_, b.labels_)
    assert b.inertia_ == pytest.approx(a.inertia_, rel=1e-4)


# ------------------------------------------------------------ driver seams


def _fit_blob_coeffs(store, l=48, m=32):
    from repro.core.kkmeans import APNCConfig, fit_coefficients
    from repro.stream.reservoir import reservoir_sample

    sample = jnp.asarray(reservoir_sample(store, 1024, seed=3))
    return fit_coefficients(
        jax.random.PRNGKey(1), sample, Kernel("rbf", gamma=0.1),
        APNCConfig(l=l, m=m),
    )


def test_sharded_staged_y_store_matches_fused_path():
    """ooc_lloyd(devices=...) over a staged WritableBlockStore of Y blocks
    (sharded internally — the guard-preserving shard() is load-bearing here)
    must reach the labels of the fused embed+assign path."""
    store, _ = gaussian_blobs_blocks(0, 1000, 6, 3, block_rows=128)
    coeffs = _fit_blob_coeffs(store)
    from repro.core.lloyd import kmeanspp_init

    pool = jnp.asarray(stream_embed(store, coeffs).materialize()[:512])
    init = kmeanspp_init(jax.random.PRNGKey(2), pool, 3, coeffs.discrepancy)
    fused = ooc_lloyd(store, 3, coeffs=coeffs, iters=15, init=init,
                      devices=DEVICES)
    ystore = stream_embed(store, coeffs)
    staged = ooc_lloyd(ystore, 3, discrepancy=coeffs.discrepancy, iters=15,
                       init=init, devices=DEVICES)
    assert np.array_equal(fused.labels, staged.labels)
    assert (fused.labels >= 0).all(), "every row must be assigned"
    # and both agree with the single-device driver from the same init
    single = ooc_lloyd(store, 3, coeffs=coeffs, iters=15, init=init)
    assert np.array_equal(fused.labels, single.labels)


def test_ooc_lloyd_mesh_kwarg_and_arg_validation():
    store, _ = gaussian_blobs_blocks(0, 600, 6, 3, block_rows=128)
    coeffs = _fit_blob_coeffs(store)
    from repro.core.lloyd import kmeanspp_init

    pool = jnp.asarray(stream_embed(store, coeffs).materialize()[:256])
    init = kmeanspp_init(jax.random.PRNGKey(2), pool, 3, coeffs.discrepancy)
    via_mesh = ooc_lloyd(store, 3, coeffs=coeffs, iters=10, init=init, mesh=_mesh())
    via_devs = ooc_lloyd(store, 3, coeffs=coeffs, iters=10, init=init,
                         devices=DEVICES)
    assert np.array_equal(via_mesh.labels, via_devs.labels)
    with pytest.raises(ValueError, match="at most one of devices= and mesh="):
        ooc_lloyd(store, 3, coeffs=coeffs, iters=1, init=init,
                  devices=DEVICES, mesh=_mesh())


def test_sharded_lockstep_fit_unchanged_by_deferred_emit():
    """The lockstep driver's label emits, deferred (prefetch 2) or
    synchronous (prefetch 0), give bitwise the same fit and the same early
    stop."""
    store, _ = gaussian_blobs_blocks(6, 1500, 6, 3, block_rows=128)
    coeffs = _fit_blob_coeffs(store)
    from repro.core.lloyd import kmeanspp_init

    pool = jnp.asarray(stream_embed(store, coeffs).materialize()[:512])
    init = kmeanspp_init(jax.random.PRNGKey(2), pool, 3, coeffs.discrepancy)
    fits = [ooc_lloyd(store, 3, coeffs=coeffs, iters=40, init=init,
                      prefetch=p, devices=DEVICES) for p in (0, 2)]
    a, b = fits
    assert np.array_equal(a.labels, b.labels)
    assert np.array_equal(np.asarray(a.centroids), np.asarray(b.centroids))
    assert a.iters == b.iters < 40
    assert a.inertia == b.inertia and a.trajectory == b.trajectory


def test_minibatch_sharded_quality_and_coverage():
    """Sharded mini-batch applies one decayed update per round of D blocks —
    a different (approximate) trajectory than the single-device driver, so
    the claim is quality, not identity."""
    store, y = gaussian_blobs_blocks(1, 2000, 8, 4, block_rows=128, separation=4.0)
    coeffs = _fit_blob_coeffs(store)
    from repro.core.lloyd import kmeanspp_init

    pool = jnp.asarray(stream_embed(store, coeffs).materialize()[:512])
    init = kmeanspp_init(jax.random.PRNGKey(4), pool, 4, coeffs.discrepancy)
    truth = np.concatenate([np.asarray(blk).ravel() for blk in y])
    common = dict(coeffs=coeffs, decay=0.9, epochs=4, init=init)
    single = minibatch_lloyd(store, 4, **common)
    sharded = minibatch_lloyd(store, 4, devices=DEVICES, **common)
    assert (sharded.labels >= 0).all()
    assert sharded.rows_seen == single.rows_seen
    # D blocks per round -> D x fewer (but D x larger) centroid moves per
    # epoch, so allow a modest quality gap vs the per-block trajectory
    assert nmi(sharded.labels, truth) >= nmi(single.labels, truth) - 0.15


# ------------------------------------------------------------ auto dispatch


# ------------------------------------------------------------ observability


def test_sharded_metrics_account_for_every_block():
    """Metrics-registry thread safety under the executor's D concurrent
    producer threads: the engine counters must account for EVERY block exactly
    (no lost updates), and the per-device breakdown must sum to the total."""
    from repro import obs

    store, _ = gaussian_blobs_blocks(0, 2048, 8, 4, block_rows=128)
    shards = [store.shard(d, D) for d in range(D)]
    fn = jax.jit(lambda x: x.sum())
    before = obs.snapshot("engine.")
    out = sharded_map_reduce(
        shards, [fn] * D, lambda a, b: a + b,
        [jnp.zeros(())] * D, devices=DEVICES,
    )
    seen = obs.delta(before, obs.snapshot("engine."))
    total = sum(s.num_blocks for s in shards)
    assert seen["engine.blocks_read"] == total == store.num_blocks
    per_dev = {k: v for k, v in seen.items()
               if k.startswith("engine.device_blocks.") and v}
    assert len(per_dev) == D  # one active lane counter per producer
    assert sum(per_dev.values()) == total
    assert seen["engine.bytes_h2d"] == store.n * store.d * 4
    assert seen["engine.map_dispatches"] == total
    assert len(out) == D


@multi_device
def test_traced_stream_shard_fit_emits_device_lanes(tmp_path):
    """Acceptance: a tracing-enabled KernelKMeans.fit on stream_shard writes a
    Chrome trace-event file that the CI schema gate accepts with DISTINCT
    lanes for >= 2 device producers."""
    from repro import obs

    store, _ = gaussian_blobs_blocks(0, 1200, 8, 4, block_rows=128, separation=4.0)
    obs.clear_trace()
    obs.enable_tracing()
    try:
        est = KernelKMeans(4, kernel=Kernel("rbf", gamma=0.1), method="rff",
                           m=64, iters=6, n_init=1, block_rows=128,
                           backend="stream_shard", mesh=_mesh())
        est.fit(store, key=jax.random.PRNGKey(7))
        path = obs.write_chrome_trace(tmp_path / "shard_trace.json")
    finally:
        obs.disable_tracing()
        obs.clear_trace()

    sys.path.insert(0, str(HERE.parent / "benchmarks"))
    try:
        import check_bench
        lanes = check_bench.check_trace(path, min_lanes=2)
    finally:
        sys.path.pop(0)
    producers = {l for l in lanes if l.startswith("producer:")}
    assert len(producers) >= 2, lanes  # one lane per device producer
    assert "main" in lanes  # the driver lane carries pass./lloyd. spans


def test_auto_prefers_stream_shard_only_with_multi_device_mesh():
    store, _ = gaussian_blobs_blocks(0, 800, 8, 4, block_rows=128, separation=4.0)

    def auto_backend(mesh):
        return KernelKMeans(4, backend="auto", mesh=mesh)._choose_backend(store)

    assert auto_backend(None) == "stream"
    assert auto_backend(_mesh(data=1)) == "stream"  # 1 data device: no sharding
    if D > 1:
        assert auto_backend(_mesh()) == "stream_shard"
    est = KernelKMeans(4, kernel=Kernel("rbf", gamma=0.1), l=48, m=32, iters=8,
                       backend="auto", mesh=_mesh()).fit(store)
    assert est.backend_ == ("stream_shard" if D > 1 else "stream")
    assert est.model_.meta.backend == est.backend_
