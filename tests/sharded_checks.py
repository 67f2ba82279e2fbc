"""Keystone check for the sharded stream backend under FORCED 8 host devices.

Run as a SUBPROCESS (tests/test_stream_sharded.py, and directly in the CI
tier-1 matrix smoke) so the 8-device XLA flag never leaks into the parent
pytest process: for each embedding member given in argv[1] (comma-separated,
default "nystrom,rff"), fit the same BlockStore through the public API with
backend="stream" and backend="stream_shard" on an 8-device mesh from the same
key, and report whether the labels are identical. Prints ONE JSON line.
"""
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _sharded_setups import SETUPS  # noqa: E402  (pure data, no jax)

# Force EXACTLY 8 devices, replacing any inherited count — the caller asserts
# report["devices"] == 8, so a leaked 4-device flag must not win.
flags = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count")
)
os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # CPU-only: never take a chip
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402  (after the device forcing)
import numpy as np  # noqa: E402

from repro.api import KernelKMeans  # noqa: E402
from repro.core.kernels_fn import Kernel  # noqa: E402
from repro.data.synthetic import gaussian_blobs_blocks  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

def main():
    members = (sys.argv[1] if len(sys.argv) > 1 else "nystrom,rff").split(",")
    report = {"devices": jax.local_device_count()}
    store, _ = gaussian_blobs_blocks(0, 1200, 8, 4, block_rows=128, separation=4.0)
    mesh = make_mesh((jax.local_device_count(), 1), ("data", "model"))
    key = jax.random.PRNGKey(7)
    for method in members:
        kernel_name, kernel_params, kw = SETUPS[method]
        common = dict(kernel=Kernel(kernel_name, **kernel_params),
                      method=method, iters=12, n_init=1, block_rows=128, **kw)
        a = KernelKMeans(4, backend="stream", **common).fit(store, key=key)
        b = KernelKMeans(4, backend="stream_shard", mesh=mesh, **common).fit(
            store, key=key)
        report[f"{method}_backend"] = b.backend_
        report[f"{method}_labels_equal"] = bool(np.array_equal(a.labels_, b.labels_))
        report[f"{method}_inertia_rel_err"] = abs(b.inertia_ - a.inertia_) / max(
            abs(a.inertia_), 1e-9)

    # Observability under genuinely-8 producer threads: a traced stream_shard
    # fit must land one trace lane + one device_blocks counter per producer,
    # and the concurrently-bumped block counters must account exactly.
    from repro import obs

    obs.reset_metrics("engine.")
    obs.clear_trace()
    obs.enable_tracing()
    kernel_name, kernel_params, kw = SETUPS["rff"]
    est = KernelKMeans(4, kernel=Kernel(kernel_name, **kernel_params),
                       method="rff", iters=6, n_init=1, block_rows=128,
                       backend="stream_shard", mesh=mesh, **kw)
    est.fit(store, key=key)
    obs.disable_tracing()
    snap = obs.snapshot("engine.")
    per_dev = {k: v for k, v in snap.items()
               if k.startswith("engine.device_blocks.")}
    report["obs_blocks_read"] = snap.get("engine.blocks_read", 0)
    # the fit's reservoir/seed passes stream on the "default" (driver) lane;
    # the Lloyd passes add one device lane per producer
    report["obs_device_counters"] = len(
        [k for k in per_dev if not k.endswith(".default")])
    report["obs_per_device_sum_matches"] = (
        sum(per_dev.values()) == snap.get("engine.blocks_read", -1))
    report["obs_producer_lanes"] = len(
        {s.lane for s in obs.TRACER.spans()
         if s.lane.startswith("producer:") and s.lane != "producer:default"})
    obs.clear_trace()
    print(json.dumps(report))


if __name__ == "__main__":
    main()
