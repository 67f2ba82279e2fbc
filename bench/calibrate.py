#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds, for the program and
for its control, in one process (set-up compiles once).

    python3 bench/calibrate.py --workload covtype-rff.fit \
        --seeds 1,2,3 --control-seeds 4,5,6 --out calib.jsonl

Each seed is one run of the cell's traffic at its own size: a fit cell runs
one whole fit and compares it; a serving cell runs a `--seconds` window at
the cell's rate. The control is the program's own lower-precision path,
`ComputePolicy(pallas=False, precision="bf16")`: one precision below the
float32 the configurations state. `--faults <name,...> --fault-seeds ...` runs the
program with a fault of bench/harness/faults.py planted. One JSON line per
run goes to `--out`; the limits in bench/limits/ are set between the
program's largest reading and the smallest of the control and the faults.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="", help="names in bench/harness/faults.py")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=600.0)
    ap.add_argument("--fits", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench.harness import faults, runner, spec
    from repro.policy import ComputePolicy

    cell = spec.load_cell(args.workload)
    fits = args.fits
    plan = [("program", int(s), None) for s in args.seeds.split(",") if s]
    plan += [("control", int(s), ComputePolicy(pallas=False, precision="bf16"))
             for s in args.control_seeds.split(",") if s]
    plan += [("fault:" + f, int(s), None) for f in args.faults.split(",") if f
             for s in args.fault_seeds.split(",") if s]
    with open(args.out, "a") as out:
        for role, seed, policy in plan:
            t0 = time.perf_counter()
            patch = faults.Patcher()
            if role.startswith("fault:"):
                faults.ALL[role[len("fault:"):]](patch)
            try:
                r = runner.run_cell(cell, seed, args.seconds, False, t_process=t0,
                                    policy=policy, window_fits=fits, max_fits=fits,
                                    reference_control=role == "control")
                line = {"role": role, "seed": seed, "correct": r["correct"],
                        "checks": r["checks"], "run": r["run"],
                        "metrics": r["metrics"]}
            except Exception as e:  # a control that crashes has failed
                line = {"role": role, "seed": seed, "error": repr(e)}
            finally:
                patch.undo()
            line["seconds"] = time.perf_counter() - t0
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps({k: line.get(k) for k in ("role", "seed", "correct", "checks", "error")}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
