#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metric readers are found by name
from BENCHMARK.json (see bench/harness/spec.py). With --trace 0 the result
holds the cell's end-to-end metrics; with --trace 1 its per-layer metrics,
read from a profiler trace of the window and from the program's counters and
spans. The last line of standard output is one JSON object; the compared
numbers and their limits are also the last lines of standard error. The run
exits nonzero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import runner

    return runner.main(args.workload, args.seed, args.seconds, bool(args.trace),
                       t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
