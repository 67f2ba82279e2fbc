"""Out-of-core scale benchmark for repro.stream, driven through repro.api.

    PYTHONPATH=src python benchmarks/stream_bench.py --n 1000000 --d 54

Clusters a blocked synthetic dataset far larger than any single resident
array: n rows streamed in `block_rows`-row blocks (the only device-resident
arrays are one block of X, one of Y, and the (k, m)/(k,) statistics). Reports:

  * streaming embed rows/s, synchronous one-block-at-a-time baseline vs the
    double-buffered engine (prefetch=2) — the overlap speedup is the point of
    the engine: block i+1's ingest + H2D transfer hides behind block i's
    device compute;
  * exact out-of-core Lloyd rows/s per iteration, via the public
    `KernelKMeans(backend="stream")` facade;
  * single-pass mini-batch Lloyd rows/s, via `backend="minibatch"`;
  * facade dispatch overhead: the same exact fit through
    `KernelKMeans.fit` vs calling `stream_fit_predict` directly — recorded to
    BENCH_api.json; the facade must cost <1% (in practice it is cheaper: its
    k-means++ seeding reuses the landmark sample instead of streaming a
    second reservoir pass).

Ingest model: in the paper's setting mappers pull blocks from HDFS over the
network; `--ingest-delay-ms` models that per-block storage/network latency
(default 60ms ~ a 14MB block at ~235MB/s). It is SIMULATED latency — this
CPU-only container has a single-core cgroup quota, so CPU-bound generator
work cannot physically overlap XLA compute here (on a real TPU host the
device computes while the host generates; the same engine hides both). Set
--ingest-delay-ms 0 to benchmark raw generator throughput instead.

Sharded sweep: `--sharded --force-devices 8` forces an 8-device CPU mesh
(the flag must reach XLA before jax imports, hence the module-top handling),
then times `backend="stream_shard"` at each device count — D producers each
streaming a round-robin block shard, so the modeled per-block ingest latency
parallelizes across mappers exactly as the paper's HDFS reads do. Results go
to BENCH_stream_shard.json; `--sharded-only` skips the single-device benches.

Results go to BENCH_stream.json / BENCH_api.json next to this file's parent.

Observability: `--trace trace.json` enables `repro.obs` span tracing for the
whole run and writes a Chrome trace-event file (load it at ui.perfetto.dev —
one lane per producer thread) plus the engine metric snapshot at
trace.metrics.json. `--smoke` additionally asserts the tracing-DISABLED
overhead gate: the null-span fast path must cost <=2% of an engine pass.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# Must precede the jax import: XLA reads the flag at backend initialization.
# Handles both `--force-devices 8` and `--force-devices=8`; argparse still
# owns validation/usage errors for the flag later.
for _i, _a in enumerate(sys.argv):
    _n = None
    if _a == "--force-devices" and _i + 1 < len(sys.argv):
        _n = sys.argv[_i + 1]
    elif _a.startswith("--force-devices="):
        _n = _a.split("=", 1)[1]
    # only export well-formed positive counts; malformed values fall through
    # to argparse, which reports the usage error instead of an XLA abort
    if _n is not None and _n.isdigit() and int(_n) > 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n}"
        )
        break

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import ComputePolicy, KernelKMeans
from repro.core.kernels_fn import Kernel
from repro.core.kkmeans import APNCConfig, fit_coefficients
from repro.data.synthetic import gaussian_blobs_blocks
from repro.kernels import ops
from repro.stream.blockstore import BlockStore
from repro.stream.engine import map_reduce
from repro.stream.lloyd import stream_fit_predict
from repro.stream.reservoir import reservoir_sample


def bench_stream_embed(store: BlockStore, coeffs, *, prefetch: int) -> float:
    """rows/s of one full streaming-embed pass (discarding Y: pure map)."""
    map_fn = jax.jit(lambda x: ops.embed_block_map(x, coeffs))
    # warm the compile on both block shapes outside the timed pass
    jax.block_until_ready(map_fn(jnp.asarray(store.get(0))))
    if store.rows_of(store.num_blocks - 1) != store.rows_of(0):
        jax.block_until_ready(map_fn(jnp.asarray(store.get(store.num_blocks - 1))))
    t0 = time.perf_counter()
    out = map_reduce(
        store, map_fn, lambda acc, y: y.sum(), jnp.asarray(0.0), prefetch=prefetch
    )
    jax.block_until_ready(out)
    return store.n / (time.perf_counter() - t0)


def bench_fused_step(store, coeffs, k: int, policy) -> dict:
    """Fused-vs-unfused Lloyd block step on ONE device-resident block.

    fused   = `ops.lloyd_step_plan(...).step`: embed + assign + (Z, g) + cost
              in a single dispatch, Y never leaves the step;
    unfused = the pre-plan chain: embed_block_map materializing Y, then
              assign_stats, then block_cost (a second full distance matrix).

    Measured on a 4096-row step: the chain's fixed overhead (two extra
    dispatches + the Y round-trip) is per-block, so the fusion win is
    largest in the small-block regime (sharded tail blocks, serving
    micro-batches) and asymptotes toward the duplicate-distance flops ratio
    as blocks grow. check_bench gates fused_step_speedup >= 1.15x on
    full-size (non-smoke) BENCH_stream.json runs; the roofline join reports
    what fraction of the analytically modeled step time the fused
    measurement achieves."""
    from repro.core.lloyd import assign_stats, block_cost
    from repro.obs import roofline_join
    from repro.roofline.analysis import lloyd_step_record

    x = jnp.asarray(store.get(0))[:4096]
    n, d = x.shape
    l, m = coeffs.landmarks.shape[0], coeffs.m
    C = ops.embed_block_map(x[:k], coeffs, policy=policy)
    plan = ops.lloyd_step_plan(params=coeffs, policy=policy)

    def unfused(x, C):
        y = ops.embed_block_map(x, coeffs, policy=policy)
        Z, g, labels = assign_stats(y, C, k, coeffs.discrepancy, policy=policy)
        return Z, g, labels, block_cost(y, C, coeffs.discrepancy)

    def timed(fn, reps=7):
        jax.block_until_ready(fn(x, C))  # compile + warm
        best = float("inf")
        for _ in range(reps):  # best-of: robust to the container's CPU quota
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, C))
            best = min(best, time.perf_counter() - t0)
        return best

    t_fused = timed(lambda x, C: plan.step(x, C))
    t_unfused = timed(unfused)
    joined = roofline_join(t_fused, lloyd_step_record(n=n, d=d, l=l, m=m, k=k))
    out = {
        "fused_step_rows_per_s": n / t_fused,
        "unfused_step_rows_per_s": n / t_unfused,
        "fused_step_speedup": t_unfused / t_fused,
        "fused_step_model_fraction": joined["model_fraction"],
    }
    print(f"[stream-bench] fused Lloyd step {out['fused_step_rows_per_s']/1e6:.2f}M "
          f"rows/s vs unfused {out['unfused_step_rows_per_s']/1e6:.2f}M "
          f"({out['fused_step_speedup']:.2f}x, model_fraction "
          f"{out['fused_step_model_fraction']:.3f}; gate: >=1.15x non-smoke)")
    return out


def bench_sstep(args, store, kern, policy, devs, base_entry):
    """The communication-avoiding s-step variant on the full mesh: same fit
    with ComputePolicy(sstep=3) — device-local (Z, g) updates between global
    reduces, every 3rd iteration (and always the last) synced. Records the
    wall-clock ratio and the label agreement vs the exact s=1 fit (deferred
    syncs can move through different intermediate centroids, so agreement is
    gated, not identity)."""
    from jax.sharding import Mesh

    D = len(devs)
    mesh = Mesh(np.array(devs).reshape(D, 1), ("data", "model"))
    key = jax.random.PRNGKey(3)
    pol_s = ComputePolicy(prefetch=policy.prefetch, sstep=3)
    est = KernelKMeans(
        args.k, kernel=kern, backend="stream_shard", l=args.l, m=args.m,
        iters=args.iters, n_init=1, policy=pol_s, mesh=mesh,
    )
    est.fit(store, key=key)  # warm the per-device compiles
    dt = float("inf")
    for _ in range(2):  # best-of-2: the container's CPU quota is noisy
        t0 = time.perf_counter()
        est.fit(store, key=key)
        dt = min(dt, time.perf_counter() - t0)
    agree = float(np.mean(est.labels_ == base_entry["labels"]))
    out = {
        "sstep": 3,
        "devices": D,
        "fit_s": dt,
        "rows_per_s": args.n * (est.n_iter_ + 1) / dt,
        "speedup_vs_sstep1": base_entry["fit_s"] / dt,
        "label_agreement_vs_sstep1": agree,
        "inertia": est.inertia_,
        "inertia_sstep1": base_entry["inertia"],
        "note": "on this single-core-quota CPU container all forced devices "
                "share one core, so the deferred cross-device reduce cannot "
                "buy wall-clock (the ratio is compute-bound noise); the "
                "recorded value validates the s-step path end-to-end and the "
                "agreement gate — the reduce saving materializes when the "
                "sum crosses real interconnect",
    }
    print(f"[stream-bench] stream_shard D={D} sstep=3: {est.n_iter_} iters in "
          f"{dt:.1f}s ({out['speedup_vs_sstep1']:.2f}x vs sstep=1, label "
          f"agreement {agree:.4f})")
    return out


def bench_sharded(args, store, kern, policy, config):
    """Per-device-count stream_shard throughput (and the keystone equality at
    benchmark scale: every device count must produce identical labels)."""
    from jax.sharding import Mesh

    devs = jax.local_devices()
    counts = [c for c in (1, 2, 4, 8) if c <= len(devs)]
    key = jax.random.PRNGKey(3)
    per_count = {}
    base_labels = None
    agreements = {}
    for c in counts:
        mesh = Mesh(np.array(devs[:c]).reshape(c, 1), ("data", "model"))
        est = KernelKMeans(
            args.k, kernel=kern, backend="stream_shard", l=args.l, m=args.m,
            iters=args.iters, n_init=1, policy=policy, mesh=mesh,
        )
        est.fit(store, key=key)  # warm the per-device compiles
        t0 = time.perf_counter()
        est.fit(store, key=key)
        dt = time.perf_counter() - t0
        rows = args.n * (est.n_iter_ + 1) / dt
        if base_labels is None:
            base_labels = est.labels_
            agree = 1.0
        else:
            # The keystone equality is exact at convergence (asserted at test
            # scale through the public API); at n=1M under a CAPPED iteration
            # budget, the different float-summation grouping of (Z, g) can
            # flip O(1) boundary rows — so the bench records agreement and
            # gates it at 1e-4.
            agree = float(np.mean(est.labels_ == base_labels))
            if agree <= 0.9999:  # explicit raise: must survive python -O
                raise AssertionError(
                    f"{c}-device labels diverged from 1-device: agreement {agree}"
                )
        agreements[str(c)] = agree
        per_count[str(c)] = {
            "fit_s": dt, "rows_per_s": rows, "iters": est.n_iter_,
            "inertia": est.inertia_, "label_agreement_vs_1dev": agree,
        }
        last_fit = {"labels": est.labels_, "fit_s": dt, "inertia": est.inertia_}
        print(f"[stream-bench] stream_shard D={c}: {est.n_iter_} iters in "
              f"{dt:.1f}s ({rows/1e6:.2f}M rows/s, speedup vs D=1 "
              f"{per_count[str(c)]['rows_per_s']/per_count[str(counts[0])]['rows_per_s']:.2f}x)")
    result = {
        "config": config | {"devices_available": len(devs)},
        "per_device_count": per_count,
        "min_label_agreement_vs_1dev": min(agreements.values()),
        "note": "rows/s = n * (iters + 1) / wall over the full sharded fit "
                "(warm, second run); the modeled per-block ingest latency "
                "parallelizes across the per-device producers — on this "
                "single-core-quota container that, not XLA compute, is the "
                "scalable part",
    }
    if counts[-1] > 1:  # s-step needs >1 device: one device is always synced
        result["sstep"] = bench_sstep(
            args, store, kern, policy, devs[:counts[-1]], last_fit)
    Path(args.shard_out).write_text(json.dumps(result, indent=2))
    print(f"[stream-bench] wrote {args.shard_out}")
    return result


def bench_chaos(args, store, kern, policy, config):
    """BENCH_pool.json: pool-scheduler fits under injected faults.

    Every scenario runs the UNCHANGED public estimator (backend=stream_shard,
    scheduler="pool") under an ambient ChaosPlan and must return labels
    bitwise identical to the fault-free pool fit — the deterministic
    duplicate-drop merge, at benchmark scale. The throughput claim: a 10x
    per-block straggler on one device loses < 30% of fault-free throughput,
    because idle workers steal its unread blocks and speculatively re-execute
    its in-flight one (gated when not --smoke)."""
    from jax.sharding import Mesh

    from repro import pool as pool_mod

    devs = jax.local_devices()
    D = len(devs)
    if D < 2:
        raise SystemExit(
            "--chaos needs >1 device for a surviving worker: pass "
            "--force-devices 8 (or run under a multi-device runtime)")
    mesh = Mesh(np.array(devs).reshape(D, 1), ("data", "model"))
    est = KernelKMeans(
        args.k, kernel=kern, backend="stream_shard", scheduler="pool",
        l=args.l, m=args.m, iters=args.iters, n_init=1, policy=policy,
        mesh=mesh,
    )
    key = jax.random.PRNGKey(3)
    est.fit(store, key=key)  # warm the per-device compiles, fault-free

    delay_s = 10.0 * args.ingest_delay_ms / 1e3 or 0.03
    scenarios = {
        "fault_free": None,
        "killed_1": lambda: pool_mod.ChaosPlan().kill(0, after_blocks=2),
        "killed_2": lambda: (pool_mod.ChaosPlan()
                             .kill(0, after_blocks=2)
                             .kill(D // 2, after_blocks=3)),
        "straggler": lambda: pool_mod.ChaosPlan().delay(0, delay_s),
    }
    per = {}
    base_labels = None
    for name, make_plan in scenarios.items():
        before = obs.snapshot("pool.")
        t0 = time.perf_counter()
        if make_plan is None:
            fit = est.fit(store, key=key)
        else:
            with pool_mod.inject(make_plan()):
                fit = est.fit(store, key=key)
        dt = time.perf_counter() - t0
        seen = obs.delta(before, obs.snapshot("pool."))
        rows = args.n * (fit.n_iter_ + 1) / dt
        if base_labels is None:
            base_labels = fit.labels_
        identical = bool(np.array_equal(fit.labels_, base_labels))
        if not identical:  # explicit raise: must survive python -O
            raise AssertionError(
                f"pool/{name}: labels diverged from the fault-free pool fit")
        per[name] = {
            "fit_s": dt, "rows_per_s": rows, "iters": fit.n_iter_,
            "inertia": fit.inertia_,
            "labels_identical_to_fault_free": identical,
            "tasks_completed": seen.get("pool.tasks_completed", 0),
            "tasks_requeued": seen.get("pool.tasks_requeued", 0),
            "tasks_stolen": seen.get("pool.tasks_stolen", 0),
            "tasks_speculated": seen.get("pool.tasks_speculated", 0),
            "duplicates_dropped": seen.get("pool.duplicates_dropped", 0),
            "worker_deaths": seen.get("pool.worker_deaths", 0),
        }
        print(f"[stream-bench] pool/{name}: {fit.n_iter_} iters in {dt:.1f}s "
              f"({rows/1e6:.2f}M rows/s, deaths "
              f"{per[name]['worker_deaths']:.0f}, stolen "
              f"{per[name]['tasks_stolen']:.0f}, speculated "
              f"{per[name]['tasks_speculated']:.0f})")
    ff = per["fault_free"]["rows_per_s"]
    straggler_ratio = per["straggler"]["rows_per_s"] / ff
    killed_ratio = per["killed_1"]["rows_per_s"] / ff
    print(f"[stream-bench] pool throughput vs fault-free: straggler "
          f"{straggler_ratio:.2f}x, killed-1 {killed_ratio:.2f}x "
          f"(gate: straggler >= 0.7)")
    if not args.smoke and straggler_ratio < 0.7:  # must survive python -O
        raise AssertionError(
            f"straggler throughput ratio {straggler_ratio:.2f} below the 0.7 "
            "gate: stealing/speculation is not absorbing the slow device")
    result = {
        "config": config | {"devices": D, "scheduler": "pool",
                            "straggler_delay_s": delay_s,
                            "smoke": bool(args.smoke)},
        "scenarios": per,
        "labels_identical": True,
        "straggler_throughput_ratio": straggler_ratio,
        "killed_1_throughput_ratio": killed_ratio,
        "note": "rows/s = n * (iters + 1) / wall over the full pool-scheduled "
                "fit (warm; includes the identical seeding phase). Chaos "
                "plans are injected around the UNCHANGED public estimator; "
                "labels_identical asserts the duplicate-drop block-ordered "
                "merge returns the fault-free answer under every scenario",
    }
    Path(args.chaos_out).write_text(json.dumps(result, indent=2))
    print(f"[stream-bench] wrote {args.chaos_out}")
    return result


def measure_disabled_overhead(blocks: int, pass_s: float) -> float:
    """The tracing-disabled overhead gate (ISSUE 6 acceptance): the per-call
    cost of a DISABLED span times the spans one engine pass issues must stay
    <= 2% of the measured pass wall time. Measured, not assumed — the whole
    point of the NULL_SPAN fast path."""
    was = obs.tracing_enabled()
    obs.disable_tracing()
    try:
        reps = 100_000
        t0 = time.perf_counter()
        for _ in range(reps):
            with obs.span("overhead.probe"):
                pass
        per_span_s = (time.perf_counter() - t0) / reps
    finally:
        if was:
            obs.enable_tracing()
    # instrumented sites per block on the engine path: block.get + h2d spans
    # in the producer, the stall-span check in the consumer, plus one
    # pass-level span — call it 4 spans/block to stay conservative.
    overhead_pct = 100.0 * per_span_s * 4 * blocks / max(pass_s, 1e-9)
    print(f"[stream-bench] tracing-disabled span cost {per_span_s*1e9:.0f}ns/call "
          f"-> {overhead_pct:.4f}% of one engine pass (gate: <=2%)")
    if overhead_pct > 2.0:  # explicit raise: must survive python -O
        raise AssertionError(
            f"tracing-disabled overhead {overhead_pct:.3f}% exceeds the 2% gate"
        )
    return overhead_pct


def write_trace_outputs(trace_path: str) -> None:
    """Dump the collected spans (Chrome trace-event or JSONL by suffix) plus
    the engine/backend metric snapshot next to it (<trace>.metrics.json)."""
    obs.write_trace(trace_path)
    metrics_path = Path(trace_path).with_suffix(".metrics.json")
    metrics = (obs.snapshot("engine.") | obs.snapshot("backend.")
               | obs.snapshot("pool."))
    metrics_path.write_text(json.dumps(metrics, indent=2, sort_keys=True))
    n_spans = len(obs.TRACER.spans())
    print(f"[stream-bench] wrote {n_spans} spans across "
          f"{len(obs.TRACER.lanes())} lanes to {trace_path}; "
          f"metrics -> {metrics_path}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--d", type=int, default=54)
    ap.add_argument("--k", type=int, default=7)
    ap.add_argument("--block-rows", type=int, default=65536)
    ap.add_argument("--l", type=int, default=128)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--ingest-delay-ms", type=float, default=60.0)
    ap.add_argument("--force-devices", type=int, default=0,
                    help="force N host CPU devices (consumed before jax import)")
    ap.add_argument("--sharded", action="store_true",
                    help="also sweep backend=stream_shard over device counts")
    ap.add_argument("--sharded-only", action="store_true",
                    help="run ONLY the sharded sweep")
    ap.add_argument("--chaos", action="store_true",
                    help="also bench the pool scheduler under injected "
                         "faults (killed producers, straggler) -> BENCH_pool")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run ONLY the chaos bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small n/blocks, no modeled ingest "
                         "latency — keeps the driver exercisable on every PR; "
                         "also asserts the tracing-disabled overhead gate")
    ap.add_argument("--trace", default="",
                    help="enable span tracing and write a Chrome trace-event "
                         "file here (.jsonl suffix for JSONL instead); the "
                         "metric snapshot lands at <trace>.metrics.json")
    ap.add_argument("--out", default=str(Path(__file__).parent.parent / "BENCH_stream.json"))
    ap.add_argument("--api-out", default=str(Path(__file__).parent.parent / "BENCH_api.json"))
    ap.add_argument("--shard-out",
                    default=str(Path(__file__).parent.parent / "BENCH_stream_shard.json"))
    ap.add_argument("--chaos-out",
                    default=str(Path(__file__).parent.parent / "BENCH_pool.json"))
    args = ap.parse_args(argv)
    if args.trace:
        obs.clear_trace()
        obs.enable_tracing()
    if args.smoke:
        args.n = min(args.n, 16384)
        args.block_rows = min(args.block_rows, 2048)
        args.iters = min(args.iters, 1)
        args.ingest_delay_ms = 0.0

    assert args.n >= 4 * args.block_rows, "dataset must dwarf the resident block"
    gen_store, _ = gaussian_blobs_blocks(
        0, args.n, args.d, args.k, block_rows=args.block_rows,
        separation=4.0, warp=True,
    )
    # Stage the dataset to disk once, blockwise (never resident), then stream
    # it back through np.memmap — the data genuinely lives out of core.
    data_path = Path(tempfile.gettempdir()) / f"stream_bench_{args.n}x{args.d}_k{args.k}.bin"
    if not data_path.exists() or data_path.stat().st_size != args.n * args.d * 4:
        t0 = time.perf_counter()
        with data_path.open("wb") as f:
            for i in range(gen_store.num_blocks):
                f.write(np.ascontiguousarray(gen_store.get(i), dtype=np.float32))
        print(f"[stream-bench] staged {data_path.stat().st_size/1e6:.0f}MB to "
              f"{data_path} in {time.perf_counter()-t0:.1f}s")
    disk_store = BlockStore.from_memmap(data_path, d=args.d, block_rows=args.block_rows)
    if args.ingest_delay_ms > 0:  # HDFS-style remote-read latency per block
        def fetch(i, _get=disk_store.get):
            time.sleep(args.ingest_delay_ms / 1e3)
            return _get(i)

        store = BlockStore.from_generator(
            fetch, n=disk_store.n, d=disk_store.d, block_rows=disk_store.block_rows
        )
    else:
        store = disk_store

    kern = Kernel("rbf", gamma=1.0 / args.d)
    policy = ComputePolicy(prefetch=args.prefetch)

    config = {k: getattr(args, k.replace("-", "_"))
              for k in ("n", "d", "k", "l", "m", "iters", "prefetch")} \
             | {"block_rows": args.block_rows,
                "blocks": store.num_blocks,
                "scale_vs_resident": args.n // args.block_rows,
                "ingest_delay_ms_simulated": args.ingest_delay_ms,
                "smoke": bool(args.smoke)}

    if args.sharded or args.sharded_only:
        sharded_result = bench_sharded(args, store, kern, policy, config)
        if args.sharded_only and not (args.chaos or args.chaos_only):
            if args.trace:
                write_trace_outputs(args.trace)
            return sharded_result

    if args.chaos or args.chaos_only:
        chaos_result = bench_chaos(args, store, kern, policy, config)
        if args.chaos_only or args.sharded_only:
            if args.trace:
                write_trace_outputs(args.trace)
            return chaos_result

    # Engine micro-bench: coefficients fit once on a reservoir sample.
    sample = jnp.asarray(reservoir_sample(store, 4096, seed=1))
    coeffs = fit_coefficients(jax.random.PRNGKey(1), sample, kern,
                              APNCConfig(l=args.l, m=args.m))

    block_mb = args.block_rows * args.d * 4 / 1e6
    print(f"[stream-bench] n={args.n} d={args.d} in {store.num_blocks} blocks of "
          f"{args.block_rows} rows / {block_mb:.1f}MB "
          f"({args.n // args.block_rows}x larger than resident); "
          f"modeled ingest latency {args.ingest_delay_ms:.0f}ms/block")

    sync = bench_stream_embed(store, coeffs, prefetch=0)
    print(f"[stream-bench] embed sync   {sync/1e6:.2f}M rows/s")
    asyn = bench_stream_embed(store, coeffs, prefetch=args.prefetch)
    print(f"[stream-bench] embed async  {asyn/1e6:.2f}M rows/s "
          f"(overlap speedup {asyn/sync:.2f}x)")
    # time against the zero-latency store: the fused-step claim is about the
    # per-block device step, not the modeled ingest in front of it
    fused_step = bench_fused_step(disk_store, coeffs, args.k, policy)

    overhead_pct = None
    if args.smoke:
        overhead_pct = measure_disabled_overhead(store.num_blocks, args.n / sync)

    def make_est(backend, **kw):
        return KernelKMeans(
            args.k, kernel=kern, backend=backend, l=args.l, m=args.m,
            iters=args.iters, n_init=1, policy=policy, **kw,
        )

    def timed(fn, repeats=2):
        """Warm once (jit compiles), then best-of-`repeats` wall time."""
        out = fn()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    key = jax.random.PRNGKey(3)

    # Exact out-of-core Lloyd through the public facade.
    t_facade, est = timed(lambda: make_est("stream").fit(store, key=key))
    passes = est.n_iter_ + 1  # +1 for the final assignment pass
    ooc_rows = args.n * passes / t_facade
    print(f"[stream-bench] exact ooc Lloyd (facade): {est.n_iter_} iters in "
          f"{t_facade:.1f}s ({ooc_rows/1e6:.2f}M rows/s/iter, "
          f"inertia {est.inertia_:.0f})")

    # Dispatch overhead: the hand-rolled driver sequence the facade's stream
    # backend performs — same key, bitwise-identical work, no estimator layer.
    def hand_rolled():
        from repro.api.estimator import phase1_keys
        from repro.core.lloyd import kmeanspp_init
        from repro.stream.lloyd import ooc_lloyd

        # the facade's phase 1: independent reservoir / fit / seed keys, taken
        # from the ONE shared split so the mirror can never drift from it
        k_sample, k_fit, k_seed = phase1_keys(key)
        s = jnp.asarray(reservoir_sample(store, 4096, seed=int(k_sample[-1])))
        cf = fit_coefficients(k_fit, s, kern, APNCConfig(l=args.l, m=args.m))
        pool = ops.embed_block_map(s[:1024], cf, policy=policy)
        init = kmeanspp_init(jax.random.fold_in(k_seed, 0), pool, args.k,
                             cf.discrepancy)
        return ooc_lloyd(store, args.k, coeffs=cf, iters=args.iters, init=init,
                         policy=policy)

    t_hand, hand = timed(hand_rolled)
    assert np.array_equal(hand.labels, est.labels_), "facade must replay the drivers"
    dispatch_pct = 100.0 * (t_facade - t_hand) / t_hand
    print(f"[stream-bench] hand-rolled drivers: {hand.iters} iters in "
          f"{t_hand:.1f}s -> facade dispatch overhead {dispatch_pct:+.2f}%")

    # End-to-end vs the legacy one-shot driver (NOT identical work: its
    # k-means++ seeding streams a second reservoir pass, and the different
    # init can change the iteration count).
    t_direct, res = timed(lambda: stream_fit_predict(
        key, store, kern, args.k,
        APNCConfig(l=args.l, m=args.m, iters=args.iters),
        mode="exact", prefetch=args.prefetch,
    ))
    res = res[0]
    e2e_pct = 100.0 * (t_facade - t_direct) / t_direct
    print(f"[stream-bench] direct stream_fit_predict: {res.iters} iters in "
          f"{t_direct:.1f}s -> facade end-to-end {e2e_pct:+.2f}%")

    # Same warm best-of-2 methodology as the exact path above.
    t_mb, mb = timed(lambda: make_est("minibatch", decay=0.95)
                     .fit(store, key=jax.random.PRNGKey(3)))
    mb_rows = 2 * args.n / t_mb  # one clustering pass + one final-assign pass
    print(f"[stream-bench] minibatch Lloyd (facade): 1 pass in {t_mb:.1f}s "
          f"({mb_rows/1e6:.2f}M rows/s, inertia {mb.inertia_:.0f})")

    result = {
        "config": config,
        "embed_sync_rows_per_s": sync,
        "embed_async_rows_per_s": asyn,
        "overlap_speedup": asyn / sync,
        "ooc_lloyd_rows_per_s_per_iter": ooc_rows,
        "ooc_lloyd_inertia": est.inertia_,
        "minibatch_rows_per_s": mb_rows,
        "minibatch_inertia": mb.inertia_,
    } | fused_step
    if overhead_pct is not None:
        result["tracing_disabled_overhead_pct"] = overhead_pct
    Path(args.out).write_text(json.dumps(result, indent=2))
    print(f"[stream-bench] wrote {args.out}")

    api_result = {
        "config": config,
        "facade_fit_s": t_facade,
        "hand_rolled_drivers_s": t_hand,
        "facade_dispatch_overhead_pct": dispatch_pct,
        "direct_stream_fit_predict_s": t_direct,
        "facade_vs_stream_fit_predict_pct": e2e_pct,
        "facade_iters": est.n_iter_,
        "direct_iters": res.iters,
        "facade_inertia": est.inertia_,
        "direct_inertia": res.inertia,
        "note": "dispatch overhead compares the facade against the identical "
                "hand-rolled driver sequence (same key, same init, best-of-2 "
                "warm runs); stream_fit_predict is NOT identical work — its "
                "seeding streams a second reservoir pass and its different "
                "init can change the Lloyd iteration count",
    }
    Path(args.api_out).write_text(json.dumps(api_result, indent=2))
    print(f"[stream-bench] wrote {args.api_out}")
    if args.trace:
        write_trace_outputs(args.trace)
    return result, api_result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
