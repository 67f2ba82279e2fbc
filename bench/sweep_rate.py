#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate with
no shed request and no growing backlog.

    python3 bench/sweep_rate.py --workload imagenet-nystrom.serve \
        --seed 1 --rates 2000,4000,8000 --seconds 8

One set-up, then a window of `--seconds` at each rate. Per rate it prints
one JSON line: requests offered and shed, p50/p99 latency from the due time,
how late the generator ran, and `growth`, the median latency of the last
quarter of the window over that of the first (a backlog that grows reads
well above 1). A cell runs below the knee at a rate fixed in its traffic
file; this tool is how that rate was found.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    import numpy as np

    from bench.harness import runner, serve_traffic, spec

    cell = spec.load_cell(args.workload)
    runner.device_info(cell.chips, require_tpu=True)
    runner.enable_compile_cache()
    s = serve_traffic.setup(cell, args.seed)
    gc.collect()
    gc.freeze()  # as bench/harness/runner.py does after set-up
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            s.traffic = dict(s.traffic, rate_per_s=rate)
            w = serve_traffic.offer(s, args.seconds, tag=100 + i)
            serve_traffic.wait_answers(s, serve_traffic.DRAIN_S, int((~w.shed).sum()))
            lat, failed = serve_traffic.latencies_ms(s, w, serve_traffic.DRAIN_S)
            q = max(1, lat.size // 4)
            late = (w.submitted - w.due) * 1e3
            print(json.dumps({
                "rate_per_s": rate, "offered": int(lat.size),
                "shed": int(w.shed.sum()), "failed": failed,
                "p50_ms": serve_traffic.quantile(lat, 0.5),
                "p99_ms": serve_traffic.quantile(lat, 0.99),
                "growth": float(np.median(lat[-q:]) / np.median(lat[:q])),
                "late_p99_ms": serve_traffic.quantile(late, 0.99),
            }), flush=True)
    finally:
        s.tier.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
