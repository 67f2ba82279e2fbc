#!/usr/bin/env python3
"""Smoke test of the clustering system on a TPU: fit, check, serve.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: stream_shard vs stream only

Everything runs in this one process (a chip belongs to one process at a
time) through the entry points a user calls: `KernelKMeans`,
`ModelRegistry` and `ServingTier`, under the default `ComputePolicy`. Data
comes from `--seed` via `gaussian_blobs_blocks` at the sizes of the paper's
Table 1 (`repro.configs.paper_datasets`):

  1. device   the devices must be TPUs; any other platform is an error.
  2. wide     imagenet-50k (n=50,000, d=900, k=164), nystrom, stream backend;
              the fused Pallas step must serve the Lloyd passes, compiled.
  3. full-n   covtype (n=581,012, d=54, k=7), rff and sd, stream backend.
  4. check    every fit against a plain f32 reference kept in this file
              (embed, then argmin to the fitted centroids; every matmul at
              precision HIGHEST): labels agree on >= 0.999 of rows and the
              reported inertia is within 1e-3 relative of the reference cost.
  5. routes   the imagenet-50k fit again under ComputePolicy(pallas=False):
              the jnp route must give the Pallas route's labels on >= 0.999
              of rows, and pass the same reference check.
  6. serve    the imagenet-50k model behind ModelRegistry(max_batch=256) and
              ServingTier: 2,048 held-out rows, each answered once, no error,
              labels agree with the reference on >= 0.999.

`--chips 4` fits covtype with rff on a 4-device mesh (`stream_shard`,
lockstep scheduler) and on one device from the same key, and runs nothing
else.

Any failed check exits nonzero. The last line of standard output, printed
only when every phase passed, is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BLOCK_ROWS = 4096
SERVE_ROWS = 2048
MIN_AGREEMENT = 0.999
MAX_COST_GAP = 1e-3
MAX_SHARD_INERTIA_GAP = 1e-4
HIGHEST = jax.lax.Precision.HIGHEST


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------- reference
# Plain float32 jnp, independent of repro.kernels: the same semantics as the
# fitted model (embedding, then nearest centroid under its discrepancy).


def _ref_embed(params, X):
    from repro.core.apnc import APNCCoefficients

    if isinstance(params, APNCCoefficients):
        check(params.q == 1 and params.kernel.name == "rbf",
              "reference covers q=1 rbf APNC only")
        L, R = params.landmarks[0], params.R[0]
        cross = jnp.dot(X, L.T, precision=HIGHEST)
        sq = (jnp.sum(X * X, axis=1, keepdims=True) - 2.0 * cross
              + jnp.sum(L * L, axis=1)[None, :])
        K = jnp.exp(-params.kernel.gamma * jnp.maximum(sq, 0.0))
        return jnp.dot(K, R.T, precision=HIGHEST)
    proj = jnp.dot(X, params.W, precision=HIGHEST)  # RFF
    return params.scale * jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=1)


def _ref_nearest(Y, C, discrepancy):
    if discrepancy == "l2":
        cross = jnp.dot(Y, C.T, precision=HIGHEST)
        D = jnp.sqrt(jnp.maximum(
            jnp.sum(Y * Y, axis=1, keepdims=True) - 2.0 * cross
            + jnp.sum(C * C, axis=1)[None, :], 0.0))
    else:  # l1
        D = jax.lax.map(lambda c: jnp.sum(jnp.abs(Y - c[None, :]), axis=1), C).T
    return jnp.argmin(D, axis=1).astype(jnp.int32), jnp.min(D, axis=1)


_ref_chunk = jax.jit(
    lambda params, X, C: _ref_nearest(_ref_embed(params, X), C, params.discrepancy)
)


def reference(model, X: np.ndarray, chunk: int = 32768):
    """(labels (n,), cost) of the model's centroids on X, in fixed-size
    chunks (zero-padded tail) so each model compiles one program."""
    labels, cost = [], 0.0
    for lo in range(0, X.shape[0], chunk):
        part = X[lo:lo + chunk]
        rows = part.shape[0]
        if rows < chunk:
            part = np.pad(part, ((0, chunk - rows), (0, 0)))
        lab, mind = _ref_chunk(model.params, jnp.asarray(part), model.centroids)
        labels.append(np.asarray(lab)[:rows])
        cost += float(np.sum(np.asarray(mind, np.float64)[:rows]))
    return np.concatenate(labels), cost


# ------------------------------------------------------------------ phases


def device_phase(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)}")
    check(d0.platform == "tpu", f"JAX found no TPU (platform {d0.platform!r})")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)} device(s)")
    from repro.kernels import ops

    check(not ops._auto_interpret(None), "Pallas would run in interpret mode")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def make_data(name: str, seed: int, n: int | None = None):
    """(store, X, y, dataset) for a Table 1 entry at its full n (or `n`)."""
    from repro.configs.paper_datasets import PAPER_DATASETS
    from repro.data.synthetic import gaussian_blobs_blocks

    ds = PAPER_DATASETS[name]
    n = n or ds.n
    store, ystore = gaussian_blobs_blocks(
        seed, n, ds.d, ds.k, block_rows=BLOCK_ROWS, separation=ds.separation
    )
    return store, store.materialize(), ystore.materialize()[:, 0], ds


def fit(store, ds, method: str, seed: int, **kw):
    """One `KernelKMeans` fit; returns (estimator, fused dispatches, seconds)."""
    from repro import obs
    from repro.api import KernelKMeans

    fused = obs.counter("engine.fused_dispatches")
    before = fused.value
    est = KernelKMeans(
        ds.k, kernel="rbf", kernel_params={"gamma": 1.0 / ds.d},
        method=method, l=300, m=200, **kw,
    )
    t0 = time.perf_counter()
    est.fit(store, key=jax.random.PRNGKey(seed + 1))
    return est, int(fused.value - before), time.perf_counter() - t0


def check_fit(tag: str, est, X, y) -> np.ndarray:
    """Hold one fit to the reference; returns the reference labels."""
    from repro.core.metrics import nmi

    ref_labels, ref_cost = reference(est.model_, X)
    agree = float(np.mean(est.labels_ == ref_labels))
    gap = abs(est.inertia_ - ref_cost) / max(abs(ref_cost), 1e-30)
    log(f"{tag}: agreement {agree:.6f} with the f32 reference, inertia "
        f"{est.inertia_!r} vs reference {ref_cost!r} (gap {gap:.3e}), "
        f"NMI vs generator {nmi(est.labels_, y):.4f}, {est.n_iter_} iters")
    check(agree >= MIN_AGREEMENT, f"{tag}: label agreement {agree} < {MIN_AGREEMENT}")
    check(gap <= MAX_COST_GAP, f"{tag}: inertia gap {gap} > {MAX_COST_GAP}")
    return ref_labels


def serve_phase(model, X_held: np.ndarray) -> None:
    from repro import obs
    from repro.api import ModelRegistry, ServingTier

    errors = obs.counter("serve.errors")
    errors_before = errors.value
    registry = ModelRegistry(max_batch=256)
    registry.register("imagenet-50k", model)  # warms: compiles off the clock
    tier = ServingTier(registry).start()
    try:
        futs = [tier.submit_wait(i, X_held[i], model="imagenet-50k")
                for i in range(X_held.shape[0])]
        responses = [f.result(timeout=600) for f in futs]
    finally:
        tier.stop()
    ids = sorted(r.request_id for r in responses)
    check(ids == list(range(X_held.shape[0])), "a request was lost or answered twice")
    n_errors = int(errors.value - errors_before)
    failed = [r for r in responses if r.error is not None]
    check(n_errors == 0 and not failed,
          f"serve.errors={n_errors}, first error: {failed[0].error if failed else None}")
    served = np.asarray([r.label for r in responses])
    ref_labels, _ = reference(model, X_held)
    agree = float(np.mean(served == ref_labels))
    lat_ms = np.asarray([r.latency_s for r in responses]) * 1e3
    log(f"serve: {len(responses)}/{X_held.shape[0]} answered once, "
        f"serve.errors={n_errors}, agreement {agree:.6f} with the f32 reference")
    log(f"serve latency (smoke reading, not a benchmark): "
        f"p50 {float(np.percentile(lat_ms, 50))!r} ms, "
        f"p99 {float(np.percentile(lat_ms, 99))!r} ms")
    check(agree >= MIN_AGREEMENT, f"serve: label agreement {agree} < {MIN_AGREEMENT}")


def one_chip(seed: int) -> None:
    from repro.data.synthetic import gaussian_blobs_blocks
    from repro.policy import ComputePolicy

    # 2. wide fit + 4. its reference check
    store, X, y, ds = make_data("imagenet-50k", seed)
    est, fused, secs = fit(store, ds, "nystrom", seed, backend="stream")
    log(f"imagenet-50k nystrom: n={X.shape[0]} d={ds.d} k={ds.k}, fit {secs!r} s, "
        f"{fused} fused Pallas dispatches")
    check(fused > 0, "the fused Pallas step served no Lloyd pass")
    check_fit("imagenet-50k nystrom", est, X, y)

    # 5. the jnp route from the same key
    jnp_est, jnp_fused, _ = fit(store, ds, "nystrom", seed, backend="stream",
                                policy=ComputePolicy(pallas=False))
    check(jnp_fused == 0, "pallas=False still dispatched the fused kernel")
    same = float(np.mean(jnp_est.labels_ == est.labels_))
    log(f"routes: pallas=True vs pallas=False label agreement {same:.6f}, "
        f"inertia {est.inertia_!r} vs {jnp_est.inertia_!r}")
    check(same >= MIN_AGREEMENT, f"routes: label agreement {same} < {MIN_AGREEMENT}")
    check_fit("imagenet-50k nystrom pallas=False", jnp_est, X, y)

    # 6. serve 2,048 held-out rows: block 13 of the same mixture (the fit
    # store holds blocks 0-12 only)
    held_store, _ = gaussian_blobs_blocks(
        seed, 14 * BLOCK_ROWS, ds.d, ds.k, block_rows=BLOCK_ROWS,
        separation=ds.separation,
    )
    serve_phase(est.model_, held_store.get(13)[:SERVE_ROWS])
    del store, X, y, est, jnp_est

    # 3. full-n fits + 4. their reference checks
    store, X, y, ds = make_data("covtype", seed)
    for method in ("rff", "sd"):
        est, fused, secs = fit(store, ds, method, seed, backend="stream")
        log(f"covtype {method}: n={X.shape[0]} d={ds.d} k={ds.k}, fit {secs!r} s, "
            f"{fused} fused Pallas dispatches")
        check(fused > 0, f"covtype {method}: the fused Pallas step served no pass")
        check_fit(f"covtype {method}", est, X, y)


def four_chips(seed: int, chips: int) -> None:
    from repro.launch.mesh import make_mesh

    store, X, y, ds = make_data("covtype", seed)
    one, _, secs1 = fit(store, ds, "rff", seed, backend="stream")
    log(f"covtype rff on one device: fit {secs1!r} s, inertia {one.inertia_!r}")
    mesh = make_mesh((chips, 1), ("data", "model"))
    shard, _, secs4 = fit(store, ds, "rff", seed, backend="stream_shard", mesh=mesh)
    log(f"covtype rff stream_shard on {chips} devices (lockstep): fit {secs4!r} s, "
        f"inertia {shard.inertia_!r}")
    # engine.device_blocks.<device>: blocks each device's producer streamed
    per_dev = shard.fit_report_.per_device_blocks
    total = sum(per_dev.values())
    rows = {dev: X.shape[0] * blocks / total for dev, blocks in per_dev.items()}
    log(f"engine.device_blocks over the fit: {per_dev} "
        f"(rows per device: {rows})")
    want = {str(d) for d in mesh.devices.flat}  # TPUs: the device phase checked
    check(set(per_dev) == want and len(want) == chips,
          f"expected blocks on the {chips} mesh devices {sorted(want)}, "
          f"got {sorted(per_dev)}")
    share = X.shape[0] / chips
    check(all(abs(r - share) <= BLOCK_ROWS for r in rows.values()),
          f"uneven shards: {rows} vs n/{chips} = {share}")
    agree = float(np.mean(one.labels_ == shard.labels_))
    gap = abs(shard.inertia_ - one.inertia_) / abs(one.inertia_)
    log(f"stream_shard vs stream: label agreement {agree:.6f}, inertia gap {gap:.3e}")
    check(agree >= MIN_AGREEMENT, f"label agreement {agree} < {MIN_AGREEMENT}")
    check(gap <= MAX_SHARD_INERTIA_GAP, f"inertia gap {gap} > {MAX_SHARD_INERTIA_GAP}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        device = device_phase(args.chips)
        if args.chips == 1:
            one_chip(args.seed)
        else:
            four_chips(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"[chip-smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
