"""Serving-tier benchmark: sustained open-loop load, admission shedding, and
the hot-swap blip.

    PYTHONPATH=src python benchmarks/serve_bench.py                 # full
    PYTHONPATH=src python benchmarks/serve_bench.py --smoke         # CI smoke

Three measurements over a real fitted model served through `repro.serving`:

  1. **Sustained levels** — an open-loop Poisson arrival process at each
     target QPS (arrivals come from a clock, not from responses: no
     coordinated omission). Per level: p50/p99 end-to-end latency, achieved
     rows/s, shed rate (expected 0 below saturation), and a mid-run hot swap
     to a second checkpointed model — every response is verified against
     `core.kkmeans.predict` under the model VERSION that answered it, so the
     zero-dropped / zero-incorrect / no-torn-batch claims are measured, not
     assumed. The swap wall time (build+warm+flip, off the hot path) is the
     "blip": requests keep flowing throughout.
  2. **Saturation** — offered load far past the service rate with a tight
     admission bound: the tier must SHED (typed rejections, shed_rate > 0)
     while every admitted request still completes with finite latency —
     graceful degradation, not queue collapse.
  3. **Metrics** — the `serve.*` snapshot (admission counters, per-model
     counters, swap count, latency/batch histograms) goes to
     `<out>.metrics.json` for the schema job (`check_bench --metrics
     --require-metric serve.shed_total ...`).

Results go to BENCH_serve.json; `check_bench.py`'s serve family gates the
SLO (p99 <= config.slo_p99_ms, zero errors, zero dropped, both swap versions
served, saturation demonstrably shedding).
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import ComputePolicy, KernelKMeans
from repro.core.kkmeans import predict
from repro.data.synthetic import gaussian_blobs_blocks
from repro.serving import ModelRegistry, ServingTier, run_open_loop


def fit_models(args, policy):
    """Fit the served model and a second 'freshly swept' variant to swap to
    (same params pytree, different seeding -> different centroids), both
    round-tripped through the checkpoint layer like production pushes."""
    from repro.distributed.checkpoint import load_any_model

    store, _ = gaussian_blobs_blocks(
        args.seed, args.n_fit, args.d, args.k,
        block_rows=args.block_rows, separation=4.0,
    )
    est = KernelKMeans(args.k, kernel="rbf", kernel_params={"gamma": 1.0 / args.d},
                       method=args.method, backend="stream", l=args.l, m=args.m,
                       iters=args.iters, policy=policy)
    est.fit(store, key=jax.random.PRNGKey(args.seed + 1))
    est.save(args.tmp / "ckpt_a")
    est2 = KernelKMeans(args.k, kernel="rbf", kernel_params={"gamma": 1.0 / args.d},
                        method=args.method, backend="stream", l=args.l, m=args.m,
                        iters=args.iters, policy=policy)
    est2.fit(store, key=jax.random.PRNGKey(args.seed + 1234))
    est2.save(args.tmp / "ckpt_b")
    return load_any_model(args.tmp / "ckpt_a"), load_any_model(args.tmp / "ckpt_b")


def run_level(args, model_a, model_b, policy, qps: float, X_req, refs) -> dict:
    """One sustained open-loop level with a mid-run hot swap a->b."""
    registry = ModelRegistry(max_batch=args.micro_batch, policy=policy)
    registry.register("default", model_a)
    n_requests = max(int(qps * args.level_seconds), 4 * args.micro_batch)
    tier = ServingTier(registry, max_delay_s=args.max_delay_ms / 1e3,
                       max_inflight=args.max_inflight).start()
    rep = run_open_loop(
        tier, X_req, qps=qps, n_requests=n_requests, seed=args.seed,
        swap_after=n_requests // 2, swap_source=model_b,
    )
    tier.stop()

    bad = 0
    for r in rep.responses:
        ref = refs[1] if r.version == 1 else refs[2]
        if not r.ok or r.label != int(ref[r.request_id % len(X_req)]):
            bad += 1
    dropped = rep.admitted - len(rep.responses)
    return {
        "target_qps": qps,
        "offered": rep.offered,
        "admitted": rep.admitted,
        "shed": rep.shed,
        "shed_rate": rep.shed_rate,
        "dropped": dropped,
        "errors": rep.errors,
        "incorrect": bad,
        "duration_s": rep.duration_s,
        "rows_per_s": rep.rows_per_s,
        "p50_ms": rep.latency_ms(50),
        "p90_ms": rep.latency_ms(90),
        "p99_ms": rep.latency_ms(99),
        "swap_s": rep.swap_s,
        "responses_old_model": rep.by_version.get(1, 0),
        "responses_new_model": rep.by_version.get(2, 0),
    }


def run_saturation(args, model_a, policy, X_req) -> dict:
    """Offered load far past the service rate, tight admission bound: the
    tier must shed (not queue-collapse) and still answer every admitted
    request with finite latency."""
    registry = ModelRegistry(max_batch=args.micro_batch, policy=policy)
    registry.register("default", model_a)

    # a deliberately slow closure amplifies saturation at smoke scale too:
    # wrap the real model dispatch with a service-time floor per batch
    base = registry.resolve("default").process
    floor_s = args.saturation_floor_ms / 1e3

    def throttled(X):
        t0 = time.perf_counter()
        out = base(X)
        dt = time.perf_counter() - t0
        if dt < floor_s:
            time.sleep(floor_s - dt)
        return out

    registry.swap("default", throttled, d=model_a.params.d)

    qps = args.saturation_qps
    n_requests = max(int(qps * args.saturation_seconds), 8 * args.micro_batch)
    tier = ServingTier(registry, max_delay_s=args.max_delay_ms / 1e3,
                       max_inflight=args.saturation_inflight).start()
    rep = run_open_loop(tier, X_req, qps=qps, n_requests=n_requests,
                        seed=args.seed + 1)
    tier.stop()
    return {
        "target_qps": qps,
        "offered": rep.offered,
        "admitted": rep.admitted,
        "shed": rep.shed,
        "shed_rate": rep.shed_rate,
        "dropped": rep.admitted - len(rep.responses),
        "errors": rep.errors,
        "p99_ms": rep.latency_ms(99),
        "rows_per_s": rep.rows_per_s,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: small fit, one short level")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--n-fit", type=int, default=50_000)
    ap.add_argument("--block-rows", type=int, default=4096)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--method", default="nystrom")
    ap.add_argument("--l", type=int, default=96)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--micro-batch", type=int, default=128)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--max-inflight", type=int, default=4096)
    ap.add_argument("--qps-levels", default="")
    ap.add_argument("--level-seconds", type=float, default=4.0)
    ap.add_argument("--requests-pool", type=int, default=8192,
                    help="distinct request rows (cycled by the loadgen)")
    ap.add_argument("--saturation-qps", type=float, default=20_000.0)
    ap.add_argument("--saturation-seconds", type=float, default=1.5)
    ap.add_argument("--saturation-inflight", type=int, default=256)
    ap.add_argument("--saturation-floor-ms", type=float, default=4.0,
                    help="per-batch service-time floor in the saturation run")
    ap.add_argument("--slo-p99-ms", type=float, default=250.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="",
                    help="serve.* metric snapshot path "
                         "(default: <out> with .metrics.json)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.n_fit = 6000
        args.level_seconds = 2.0
        args.saturation_seconds = 1.0
        args.requests_pool = 2048
    levels = ([float(v) for v in args.qps_levels.split(",")]
              if args.qps_levels else ([300.0] if args.smoke else [500.0, 1500.0]))

    policy = ComputePolicy()
    with tempfile.TemporaryDirectory() as tmp:
        args.tmp = Path(tmp)
        t0 = time.perf_counter()
        model_a, model_b = fit_models(args, policy)
        fit_s = time.perf_counter() - t0
        print(f"[serve-bench] fitted + checkpoint-roundtripped 2 models "
              f"in {fit_s:.1f}s (n={args.n_fit}, {args.method})")

    req_store, _ = gaussian_blobs_blocks(
        args.seed + 7919, args.requests_pool, args.d, args.k,
        block_rows=args.requests_pool, separation=4.0,
    )
    X_req = req_store.get(0)
    refs = {
        1: np.asarray(predict(jnp.asarray(X_req), model_a.params,
                              model_a.centroids, policy=policy)),
        2: np.asarray(predict(jnp.asarray(X_req), model_b.params,
                              model_b.centroids, policy=policy)),
    }

    obs.reset_metrics("serve.")
    out_levels = {}
    for qps in levels:
        lv = run_level(args, model_a, model_b, policy, qps, X_req, refs)
        out_levels[str(int(qps))] = lv
        print(f"[serve-bench] level {qps:.0f} qps: "
              f"{lv['rows_per_s']:.0f} rows/s, p50 {lv['p50_ms']:.2f}ms "
              f"p99 {lv['p99_ms']:.2f}ms, shed {lv['shed']} "
              f"({100 * lv['shed_rate']:.1f}%), swap {lv['swap_s'] * 1e3:.0f}ms "
              f"(v1 {lv['responses_old_model']} / v2 {lv['responses_new_model']}), "
              f"dropped {lv['dropped']}, incorrect {lv['incorrect']}")

    sat = run_saturation(args, model_a, policy, X_req)
    print(f"[serve-bench] saturation {sat['target_qps']:.0f} qps offered: "
          f"shed {100 * sat['shed_rate']:.1f}%, admitted p99 "
          f"{sat['p99_ms']:.1f}ms, dropped {sat['dropped']}")

    result = {
        "config": {
            "smoke": bool(args.smoke), "n_fit": args.n_fit, "d": args.d,
            "k": args.k, "method": args.method, "l": args.l, "m": args.m,
            "micro_batch": args.micro_batch,
            "max_delay_ms": args.max_delay_ms,
            "max_inflight": args.max_inflight,
            "level_seconds": args.level_seconds,
            "qps_levels": levels,
            "saturation_qps": args.saturation_qps,
            "saturation_inflight": args.saturation_inflight,
            "saturation_floor_ms": args.saturation_floor_ms,
            "slo_p99_ms": args.slo_p99_ms,
            "seed": args.seed,
        },
        "levels": out_levels,
        "saturation": sat,
        "swap_performed": True,
        "zero_errors": all(
            lv["errors"] == 0 and lv["incorrect"] == 0 and lv["dropped"] == 0
            for lv in out_levels.values()
        ) and sat["errors"] == 0 and sat["dropped"] == 0,
    }
    out = Path(args.out)
    out.write_text(json.dumps(result, indent=2, sort_keys=True) + "\n")
    print(f"[serve-bench] wrote {out}")

    metrics_out = (Path(args.metrics_out) if args.metrics_out
                   else out.with_name(out.stem + ".metrics.json"))
    metrics_out.write_text(
        json.dumps(obs.snapshot("serve."), indent=2, sort_keys=True) + "\n"
    )
    print(f"[serve-bench] wrote {metrics_out}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
