"""The comparison of a stream_shard fit on four virtual CPU devices (the
traffic of a four-chip covtype-rff cell, `traffic/fit_shard.json`): a sound
fit is correct, and one whose cross-device reduction is left out (each
centroid update sees one device's rows) is not.

Runs in a child process: the device count is fixed when JAX starts.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import dataclasses, json, sys, time
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
from bench.harness import runner, spec
from bench.tests.cells import small_cell

# covtype-rff on four devices: the sharded traffic file, the one-chip
# cell's limits, the configuration cut to a CPU test's size
traffic = json.loads((spec.BENCH_DIR / "traffic" / "fit_shard.json").read_text())
cell = dataclasses.replace(small_cell("covtype-rff.fit"), chips=4, traffic=traffic)

def run():
    return runner.run_cell(cell, 31, 600.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           compile_cache=False, window_fits=1)

out = {"sound": run()}
from bench.harness import faults
patch = faults.Patcher()
faults.no_exchange(patch)
out["no_exchange"] = run()
print(json.dumps({k: {"correct": v["correct"], "checks": v["checks"],
                      "blocks": v["attempted"]} for k, v in out.items()}))
"""


def test_cross_device_reduction_is_compared():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert not out["no_exchange"]["correct"], out["no_exchange"]["checks"]
