"""Embedding-layer benchmark: fused vs unfused block transform throughput.

    PYTHONPATH=src python benchmarks/embed_bench.py            # full (n=1M)
    PYTHONPATH=src python benchmarks/embed_bench.py --n 200000 # quick

For each registered member the stream engine can fit (nystrom / sd / rff),
streams n rows in block_rows-sized blocks through the double-buffered engine
twice:

  * unfused — two device dispatches per block: `ops.embed_block_map` (Y) then
    `core.lloyd.assign_stats` (Z, g, labels), with Y round-tripping through
    the dispatch boundary;
  * fused   — ONE dispatch per block: `ops.embed_assign_block`, the jit that
    inlines the member's transform with the assignment so Y never crosses a
    dispatch boundary (what streaming Lloyd and the serving path run).

Reports rows/s for both and the fused speedup, per member, into
BENCH_embed.json. The generic dispatch specializes per params TYPE at trace
time, so the fused path costs the same number of dispatches for every member
— the point of putting the family behind one protocol.

Reading the numbers: fusion exists to keep Y off the dispatch boundary —
on TPU that is an HBM round trip of (block_rows, m) floats per block; on this
CPU container it only changes XLA's program split, so expect sd (l1 assign,
worst dispatch overhead) to gain the most, nystrom to be ~neutral, and rff to
pay a small scheduling penalty (XLA CPU overlaps the two smaller programs
better than the one large one). The JSON records the backend for exactly this
reason.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

import repro.embed as E
from repro.core.kernels_fn import Kernel
from repro.core.lloyd import assign_stats, kmeanspp_init
from repro.data.synthetic import gaussian_blobs_blocks
from repro.kernels import ops
from repro.policy import ComputePolicy
from repro.stream.engine import map_reduce
from repro.stream.reservoir import reservoir_sample

MEMBERS = ("nystrom", "sd", "rff")


def _bench_pass(store, map_fn, prefetch: int) -> float:
    """rows/s of one full streamed pass of map_fn (warm compile first)."""
    first = map_fn(jnp.asarray(store.get(0)))
    jax.block_until_ready(first)
    if store.rows_of(store.num_blocks - 1) != store.rows_of(0):
        jax.block_until_ready(map_fn(jnp.asarray(store.get(store.num_blocks - 1))))
    t0 = time.perf_counter()
    out = map_reduce(  # both paths return (Z, g, labels); fold g[0] so the
        store, map_fn,   # per-block work cannot be dead-code-eliminated
        lambda acc, o: acc + o[1][0],
        jnp.asarray(0.0), prefetch=prefetch,
    )
    jax.block_until_ready(out)
    return store.n / (time.perf_counter() - t0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--block-rows", type=int, default=65536)
    ap.add_argument("--l", type=int, default=128)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small n/blocks, drivers stay exercisable")
    ap.add_argument("--out", default=str(Path(__file__).parent.parent / "BENCH_embed.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.n = min(args.n, 32768)
        args.block_rows = min(args.block_rows, 4096)

    store, _ = gaussian_blobs_blocks(
        0, args.n, args.d, args.k, block_rows=args.block_rows, separation=4.0
    )
    policy = ComputePolicy(prefetch=args.prefetch)
    sample = jnp.asarray(reservoir_sample(store, 2048, seed=1))
    kern = Kernel("rbf", gamma=1.0 / args.d)

    print(f"[embed-bench] n={args.n} d={args.d} in {store.num_blocks} blocks of "
          f"{args.block_rows} rows; members: {', '.join(MEMBERS)}")

    results = {
        "config": {"n": args.n, "d": args.d, "k": args.k,
                   "block_rows": args.block_rows, "l": args.l, "m": args.m,
                   "prefetch": args.prefetch,
                   "backend": jax.default_backend()},
        "members": {},
    }
    for name in MEMBERS:
        emb = E.get_embedding(name)
        params = emb.fit(jax.random.PRNGKey(1), sample, kern,
                         l=args.l, m=args.m)
        pool = ops.embed_block_map(sample[:1024], params, policy=policy)
        centroids = kmeanspp_init(jax.random.PRNGKey(2), pool, args.k,
                                  params.discrepancy)

        @jax.jit
        def unfused_assign(y, c=centroids, disc=params.discrepancy):
            return assign_stats(y, c, c.shape[0], disc, policy=policy)

        def unfused(x):  # two dispatches: embed, then assign
            y = ops.embed_block_map(x, params, policy=policy)
            return unfused_assign(y)

        def fused(x):  # one dispatch: transform inlined with assignment
            return ops.embed_assign_block(x, params, centroids, policy=policy)

        r_unfused = _bench_pass(store, unfused, args.prefetch)
        r_fused = _bench_pass(store, fused, args.prefetch)
        speedup = r_fused / r_unfused
        results["members"][name] = {
            "params_m": params.m,
            "unfused_rows_per_s": r_unfused,
            "fused_rows_per_s": r_fused,
            "fused_speedup": speedup,
        }
        print(f"[embed-bench] {name:12s} unfused {r_unfused/1e6:6.2f}M rows/s | "
              f"fused {r_fused/1e6:6.2f}M rows/s | {speedup:.2f}x")

    Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    print(f"[embed-bench] wrote {args.out}")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
