"""Micro-benchmarks of the APNC hot loops (XLA path wall-clock on this CPU;
the Pallas path is correctness-validated in interpret mode — its perf story is
the structural VMEM/MXU analysis in EXPERIMENTS.md section Kernels).

    PYTHONPATH=src python benchmarks/kernel_bench.py --smoke \
        --out /tmp/BENCH_kernel.json

`run_all()` stays the library entry (benchmarks/run.py builds its table from
it); the CLI wraps it with a CI-sized `--smoke` mode (shrunk shapes, fewer
reps) and a BENCH-schema JSON output for the bench-smoke job.
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

from repro.core.apnc import pairwise_discrepancy, sufficient_stats
from repro.core.kernels_fn import Kernel
from repro.embed.apnc import fit_nystrom


def _time(fn, *args, reps=5):
    fn(*args)  # compile + warm
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_embed(n=8192, d=256, l=512, m=256):
    X = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    kern = Kernel("rbf", gamma=0.05)
    coeffs = fit_nystrom(jax.random.PRNGKey(1), X, kern, l=l, m=m)

    @jax.jit
    def embed(X):
        from repro.core.apnc import embed as _e

        return _e(X, coeffs)

    us = _time(embed, X)
    flops = 2 * n * l * d + 2 * n * l * m  # gram + contraction
    return {"name": "apnc_embed_xla", "us_per_call": us,
            "derived": f"{flops / (us * 1e-6) / 1e9:.2f}GFLOPs n={n} d={d} l={l} m={m}"}


def bench_assign(n=65536, m=256, k=64, disc="l2"):
    Y = jax.random.normal(jax.random.PRNGKey(0), (n, m))
    C = jax.random.normal(jax.random.PRNGKey(1), (k, m))

    @jax.jit
    def assign(Y, C):
        D = pairwise_discrepancy(Y, C, disc)
        labels = jnp.argmin(D, axis=-1)
        return sufficient_stats(Y, labels, k)

    us = _time(assign, Y, C)
    return {"name": f"apnc_assign_{disc}_xla", "us_per_call": us,
            "derived": f"{n / (us * 1e-6) / 1e6:.2f}Mrows/s n={n} m={m} k={k}"}


def bench_lloyd_iteration(n=65536, m=256, k=64):
    from repro.core.lloyd import lloyd

    Y = jax.random.normal(jax.random.PRNGKey(0), (n, m))

    @jax.jit
    def one(Y):
        return lloyd(Y, k, discrepancy="l2", iters=1,
                     init=Y[:k]).centroids

    us = _time(one, Y)
    return {"name": "lloyd_iteration_xla", "us_per_call": us,
            "derived": f"{n / (us * 1e-6) / 1e6:.2f}Mrows/s/iter"}


def bench_fused_step(n=65536, d=64, l=256, m=128, k=16):
    """One plan-fused Lloyd block step (embed + assign + (Z, g) + cost in ONE
    dispatch, Y never materialized) against the pre-plan chain (embed dispatch
    materializing Y, then assign_stats, then block_cost — which recomputes the
    full distance matrix). The ratio is the fused_step_speedup family that
    check_bench gates at >= 1.15x on full-size BENCH_stream.json runs."""
    from repro.core.lloyd import assign_stats, block_cost
    from repro.kernels import ops
    from repro.policy import ComputePolicy

    X = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    kern = Kernel("rbf", gamma=1.0 / d)
    coeffs = fit_nystrom(jax.random.PRNGKey(1), X[:4 * l], kern, l=l, m=m)
    pol = ComputePolicy(pallas=False)
    C = ops.embed_block_map(X[:k], coeffs, policy=pol)
    plan = ops.lloyd_step_plan(params=coeffs, policy=pol)

    def unfused(X, C):
        y = ops.embed_block_map(X, coeffs, policy=pol)
        Z, g, labels = assign_stats(y, C, k, coeffs.discrepancy, policy=pol)
        return Z, g, labels, block_cost(y, C, coeffs.discrepancy)

    us_fused = _time(lambda X, C: plan.step(X, C), X, C)
    us_unfused = _time(unfused, X, C)
    speedup = us_unfused / us_fused
    return {"name": "lloyd_fused_step", "us_per_call": us_fused,
            "us_per_call_unfused": us_unfused, "fused_speedup": speedup,
            "derived": f"{n / (us_fused * 1e-6) / 1e6:.2f}Mrows/s fused, "
                       f"{speedup:.2f}x vs embed+assign+cost chain "
                       f"n={n} d={d} l={l} m={m} k={k}"}


def bench_flash_attention(B=1, S=1024, H=4, Dh=64):
    """XLA-path wall clock of the attention shape the Pallas kernel targets
    (the kernel itself is interpret-validated; see EXPERIMENTS §Kernels)."""
    from repro.kernels import ref

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, Dh))
               for i in range(3))
    fn = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, 0))
    us = _time(fn, q, k, v)
    flops = 4 * B * H * S * S * Dh
    return {"name": "attention_oracle_xla", "us_per_call": us,
            "derived": f"{flops / (us * 1e-6) / 1e9:.2f}GFLOPs B={B} S={S} H={H} Dh={Dh}"}


def run_all(*, smoke: bool = False):
    if smoke:  # CI-sized shapes: same code paths, seconds not minutes
        return [
            bench_embed(n=1024, d=64, l=128, m=64),
            bench_assign(n=4096, m=64, k=16, disc="l2"),
            bench_assign(n=2048, m=64, k=16, disc="l1"),
            bench_lloyd_iteration(n=4096, m=64, k=16),
            bench_fused_step(n=8192, d=32, l=64, m=32, k=8),
            bench_flash_attention(B=1, S=256, H=2, Dh=32),
        ]
    return [bench_embed(), bench_assign(disc="l2"), bench_assign(disc="l1", n=16384),
            bench_lloyd_iteration(), bench_fused_step(), bench_flash_attention()]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes so the drivers stay exercisable on "
                         "every PR")
    ap.add_argument("--out", default="",
                    help="write rows as BENCH-schema JSON ({config, rows}) here")
    args = ap.parse_args(argv)
    rows = run_all(smoke=args.smoke)
    for row in rows:
        print(f"[kernel-bench] {row['name']}: {row['us_per_call']:.0f}us/call "
              f"({row['derived']})")
    if args.out:
        result = {"config": {"smoke": args.smoke}, "rows": rows}
        Path(args.out).write_text(json.dumps(result, indent=2))
        print(f"[kernel-bench] wrote {args.out}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
