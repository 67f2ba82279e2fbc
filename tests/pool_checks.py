"""Chaos keystone for the repro.pool control plane under FORCED 8 devices.

Run as a SUBPROCESS (tests/test_pool.py, and directly in the CI tier-1
matrix) so the 8-device XLA flag never leaks into the parent pytest process.
For each embedding member in argv[1] (comma-separated, default "nystrom,rff")
the UNCHANGED public API fits the same BlockStore with
backend="stream_shard", scheduler="pool" on an 8-device mesh:

  fault_free   no chaos plan installed (also compared against backend="stream")
  killed_1     worker 0 dies mid-first-iteration (chaos kill after 1 block)
  killed_2     workers 0 and 3 die mid-fit
  straggler    worker 0 sleeps on every block read; idle workers steal

The load-bearing assertion: every chaos fit returns labels IDENTICAL to the
fault-free pool fit from the same key (the duplicate-drop block-id-ordered
merge makes the answer schedule-independent). Prints ONE JSON line.
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

from repro import obs  # noqa: E402
from repro import pool as pool_mod  # noqa: E402
from repro.api import KernelKMeans  # noqa: E402
from repro.core.kernels_fn import Kernel  # noqa: E402
from repro.data.synthetic import gaussian_blobs_blocks  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

SCENARIOS = {
    "fault_free": lambda: None,
    "killed_1": lambda: pool_mod.ChaosPlan().kill(0, after_blocks=1),
    "killed_2": lambda: (pool_mod.ChaosPlan()
                         .kill(0, after_blocks=1).kill(3, after_blocks=2)),
    "straggler": lambda: pool_mod.ChaosPlan().delay(0, 0.05),
}


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
        stream = KernelKMeans(4, backend="stream", **common).fit(store, key=key)
        est = KernelKMeans(4, backend="stream_shard", scheduler="pool",
                           mesh=mesh, **common)
        fits, deltas = {}, {}
        for name, make_plan in SCENARIOS.items():
            plan = make_plan()
            before = obs.snapshot("pool.")
            if plan is None:
                fits[name] = est.fit(store, key=key)
            else:
                with pool_mod.inject(plan):
                    fits[name] = est.fit(store, key=key)
            deltas[name] = obs.delta(before, obs.snapshot("pool."))
        base = fits["fault_free"]
        report[f"{method}_backend"] = base.backend_
        report[f"{method}_pool_equals_stream"] = bool(
            np.array_equal(base.labels_, stream.labels_))
        # num_blocks x (iterations + final assign): every block executed
        # exactly once per pass on the fault-free run
        report[f"{method}_tasks_completed_exact"] = (
            deltas["fault_free"]["pool.tasks_completed"]
            == store.num_blocks * (base.n_iter_ + 1))
        for name in ("killed_1", "killed_2", "straggler"):
            report[f"{method}_{name}_labels_equal"] = bool(
                np.array_equal(base.labels_, fits[name].labels_))
            report[f"{method}_{name}_inertia_equal"] = bool(
                fits[name].inertia_ == base.inertia_)
        report[f"{method}_killed_1_deaths"] = deltas["killed_1"][
            "pool.worker_deaths"]
        report[f"{method}_killed_2_deaths"] = deltas["killed_2"][
            "pool.worker_deaths"]
        report[f"{method}_killed_requeued"] = deltas["killed_2"][
            "pool.tasks_requeued"]
        report[f"{method}_straggler_stolen"] = deltas["straggler"][
            "pool.tasks_stolen"]
    print(json.dumps(report))


if __name__ == "__main__":
    main()
