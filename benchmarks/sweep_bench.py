"""Embed-once sweep benchmark: model selection vs repeated full fits.

    PYTHONPATH=src python benchmarks/sweep_bench.py                 # full
    PYTHONPATH=src python benchmarks/sweep_bench.py --smoke         # CI smoke

The headline claim of the sweep engine: R restarts x a k-grid of candidate
clusterings cost ~ONE embedding pass plus cheap linear k-means, because the
embedding is materialized once into a host-staged Y cache and every Lloyd
iteration's single engine pass feeds every candidate. The baseline is what a
user without `KernelKMeans.sweep` would run — one `fit` per (k, restart), each
paying the fused embed+assign pass (iters+1) times.

Both sides run through the public facade at identical hyperparameters over the
same disk-staged memmap stream (the dataset genuinely lives out of core, as in
stream_bench). The bench also replays the keystone invariant at benchmark
scale: the sweep's (k, restart=r) candidate must reproduce the labels of
`fit(k, n_init=r+1)`'s r-th seeding lineage — checked here for the first grid
entry against a single-restart fit.

Results go to BENCH_sweep.json: per-side wall time, the amortization speedup
(gated >= 3x at full size: embedding dominates per BENCH_embed.json, so
re-embedding R*|k_grid|*(iters+1) times vs once must show up), and the
inertia table with the deterministic selection.

The bench also measures the quantized-cache keystone (DESIGN.md §17): the
same sweep over a `--cache-dtype` compressed staged cache must agree with the
f32-cache sweep on >= 99.9% of labels per candidate while staging >= 2x fewer
bytes (>= 2x the candidates per staged byte). Both numbers ride in the JSON's
"compression" section and are gated by check_bench.py.
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
import numpy as np

from repro.api import ComputePolicy, KernelKMeans
from repro.core.kernels_fn import Kernel
from repro.data.synthetic import gaussian_blobs_blocks
from repro.stream.blockstore import BlockStore


def stage_to_disk(args) -> BlockStore:
    """Generate blockwise, stage to a flat .bin once, stream back via memmap
    (same discipline as stream_bench: the data genuinely lives out of core)."""
    gen_store, _ = gaussian_blobs_blocks(
        0, args.n, args.d, max(args.k_grid), block_rows=args.block_rows,
        separation=4.0, warp=True,
    )
    # cache key covers every generation parameter (k_max changes the blobs)
    path = Path(tempfile.gettempdir()) / (
        f"sweep_bench_{args.n}x{args.d}_k{max(args.k_grid)}"
        f"_b{args.block_rows}.bin"
    )
    if not path.exists() or path.stat().st_size != args.n * args.d * 4:
        with path.open("wb") as f:
            for i in range(gen_store.num_blocks):
                f.write(np.ascontiguousarray(gen_store.get(i), dtype=np.float32))
    return BlockStore.from_memmap(path, d=args.d, block_rows=args.block_rows)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--d", type=int, default=54)
    ap.add_argument("--k-grid", default="5,7,9",
                    help="comma-separated candidate k values")
    ap.add_argument("--restarts", type=int, default=4)
    ap.add_argument("--block-rows", type=int, default=32768)
    ap.add_argument("--l", type=int, default=128)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--backend", default="stream",
                    choices=["stream", "stream_shard", "local"])
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--cache-dtype", default="int8",
                    choices=["bf16", "int8"],
                    help="compressed staged-Y codec for the compression "
                         "section (compared against the f32 cache)")
    ap.add_argument("--trials", type=int, default=2,
                    help="timed repetitions per side; each side reports its "
                         "best (min) wall time, the standard noise-robust "
                         "estimator for a shared machine")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small n/grid, no speedup gate")
    ap.add_argument("--out",
                    default=str(Path(__file__).parent.parent / "BENCH_sweep.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.n = min(args.n, 24576)
        args.block_rows = min(args.block_rows, 4096)
        args.k_grid = "4,6"
        args.restarts = 2
        args.iters = 2
        args.trials = 1
    args.k_grid = tuple(int(v) for v in args.k_grid.split(","))

    store = stage_to_disk(args)
    kern = Kernel("rbf", gamma=1.0 / args.d)
    policy = ComputePolicy(prefetch=args.prefetch)
    key = jax.random.PRNGKey(3)
    n_candidates = len(args.k_grid) * args.restarts

    def make_est(k, **kw):
        return KernelKMeans(
            k, kernel=kern, backend=args.backend, l=args.l, m=args.m,
            iters=args.iters, block_rows=args.block_rows, policy=policy, **kw,
        )

    print(f"[sweep-bench] n={args.n} d={args.d} blocks of {args.block_rows}; "
          f"{len(args.k_grid)} k x {args.restarts} restarts = "
          f"{n_candidates} candidates, backend={args.backend}")

    # Warm the compiles on both sides before timing, over the FULL candidate
    # lattice: each distinct (k, restarts) shape pair compiles its own
    # programs, and leaving those in the timed sections measures jit latency,
    # not amortization (the headline claim is about re-embedding passes).
    for k in args.k_grid:
        make_est(k, n_init=args.restarts).fit(store, key=key)
    make_est(args.k_grid[0]).sweep(
        store, args.k_grid, restarts=args.restarts, key=key)

    from repro import obs

    def staged_bytes_delta(before: dict) -> int:
        after = obs.snapshot("cache.")
        return int(after.get("cache.bytes_staged", 0)
                   - before.get("cache.bytes_staged", 0))

    # --- the sweep: ONE embedding pass feeds every candidate ---------------
    # Both timed sides take the best of --trials runs: the workloads are
    # deterministic (same key), so min wall time is the least-noise estimate
    # on a machine with background load.
    t_sweep = float("inf")
    for _ in range(max(1, args.trials)):
        est_sweep = make_est(args.k_grid[0])
        cache_before = obs.snapshot("cache.")
        t0 = time.perf_counter()
        result = est_sweep.sweep(
            store, args.k_grid, restarts=args.restarts, key=key
        )
        t_sweep = min(t_sweep, time.perf_counter() - t0)
        bytes_f32 = staged_bytes_delta(cache_before)
    print(f"[sweep-bench] sweep: {n_candidates} candidates in {t_sweep:.1f}s "
          f"(best k={result.best_k} restart={result.best_restart}, "
          f"inertia {result.best_inertia:.0f})")

    # --- the baseline: full fits covering the same candidate lattice -------
    # fit(k, n_init=R) evaluates exactly the sweep's R seeding lineages for
    # that k (restart r seeds from fold_in(k_seed, r) in both), re-embedding
    # every block on every Lloyd pass of every restart — the work the sweep
    # replaces with one staged cache.
    t_fits = float("inf")
    for _ in range(max(1, args.trials)):
        t0 = time.perf_counter()
        fit_inertia: dict[str, float] = {}
        for k in args.k_grid:
            est = make_est(k, n_init=args.restarts)
            est.fit(store, key=key)
            fit_inertia[str(k)] = est.inertia_  # best-of-R, same as min(row)
        t_fits = min(t_fits, time.perf_counter() - t0)
    print(f"[sweep-bench] repeated fits: {n_candidates} candidates in "
          f"{t_fits:.1f}s")

    # Single-restart fit at the first grid entry for the label-identity check
    # (outside the timed baseline: it duplicates one of its candidates).
    first_fit_labels = make_est(args.k_grid[0], n_init=1).fit(
        store, key=key
    ).labels_

    speedup = t_fits / t_sweep
    print(f"[sweep-bench] amortization speedup: {speedup:.2f}x")

    # --- the compressed cache: same sweep over a quantized staged Y --------
    # DESIGN.md §17 keystone at bench scale: every candidate's labels over
    # the --cache-dtype cache must agree >= 99.9% with the f32-cache sweep,
    # while the cache stages >= 2x fewer bytes (>= 2x candidates per byte).
    policy_q = ComputePolicy(
        prefetch=args.prefetch, cache_dtype=args.cache_dtype)
    est_q = KernelKMeans(
        args.k_grid[0], kernel=kern, backend=args.backend, l=args.l,
        m=args.m, iters=args.iters, block_rows=args.block_rows,
        policy=policy_q,
    )
    cache_before = obs.snapshot("cache.")
    t0 = time.perf_counter()
    result_q = est_q.sweep(store, args.k_grid, restarts=args.restarts, key=key)
    t_q = time.perf_counter() - t0
    bytes_q = staged_bytes_delta(cache_before)
    agreement = min(
        float(np.mean(result.labels[i][r] == result_q.labels[i][r]))
        for i in range(len(args.k_grid))
        for r in range(args.restarts)
    )
    bytes_ratio = bytes_f32 / max(bytes_q, 1)
    print(f"[sweep-bench] {args.cache_dtype} cache: {t_q:.1f}s, min label "
          f"agreement {agreement:.5f}, staged {bytes_q / 1e6:.1f} MB vs f32 "
          f"{bytes_f32 / 1e6:.1f} MB ({bytes_ratio:.2f}x candidates/byte)")
    if agreement < 0.999:  # explicit raise: must survive python -O
        raise AssertionError(
            f"{args.cache_dtype} cache label agreement {agreement:.5f} "
            "< 0.999 vs the f32 cache"
        )
    if bytes_ratio < 2.0:
        raise AssertionError(
            f"{args.cache_dtype} cache staged only {bytes_ratio:.2f}x fewer "
            "bytes than f32 (< 2x candidates per byte)"
        )

    # Keystone replay at bench scale: candidate (k_grid[0], restart 0) must
    # equal the single-restart fit at that k from the same key.
    identical = bool(np.array_equal(
        result.labels[0][0], first_fit_labels
    ))
    print(f"[sweep-bench] sweep[k={args.k_grid[0]}, r=0] == fit labels: "
          f"{identical}")
    if not identical:  # explicit raise: must survive python -O
        raise AssertionError("sweep candidate diverged from fit labels")
    if not args.smoke and args.n >= 100_000 and speedup < 3.0:
        raise AssertionError(
            f"embed-once amortization regressed: {speedup:.2f}x < 3x"
        )

    out = {
        "config": {
            "n": args.n, "d": args.d, "k_grid": list(args.k_grid),
            "restarts": args.restarts, "l": args.l, "m": args.m,
            "iters": args.iters, "block_rows": args.block_rows,
            "backend": args.backend, "prefetch": args.prefetch,
            "cache_dtype": args.cache_dtype,
            "candidates": n_candidates, "smoke": bool(args.smoke),
            "trials": args.trials,
        },
        "sweep_s": t_sweep,
        "repeated_fit_s": t_fits,
        "speedup": speedup,
        "sweep_inertia_table": {
            str(k): v for k, v in result.inertia_table().items()
        },
        "repeated_fit_inertia": fit_inertia,
        "best": {
            "k": int(result.best_k),
            "restart": int(result.best_restart),
            "inertia": float(result.best_inertia),
        },
        "single_candidate_label_identity": identical,
        "compression": {
            "cache_dtype": args.cache_dtype,
            "sweep_s": t_q,
            "bytes_staged_f32": bytes_f32,
            "bytes_staged_compressed": bytes_q,
            "bytes_ratio": bytes_ratio,
            "min_label_agreement_vs_f32": agreement,
        },
        "note": "speedup = wall(one fit per (k, restart)) / wall(one "
                "embed-once sweep), warm jits, best of --trials runs per "
                "side, same key and hyperparameters; "
                "the sweep pays the embedding pass once while each baseline "
                "fit re-embeds every block on every Lloyd pass",
    }
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"[sweep-bench] wrote {args.out}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
