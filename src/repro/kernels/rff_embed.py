"""Pallas TPU kernel: fused random-Fourier-feature map  Y = s [cos(XW), sin(XW)].

The RFF member's hot loop (Rahimi-Recht features for shift-invariant kernels):
one (n, d) x (d, m) matmul followed by elementwise cos/sin and a concat. The
fused kernel tiles the matmul through VMEM and applies the trig on the VPU
while the projection tile is still resident, so the (n, m) projection never
round-trips to HBM between the MXU and the nonlinearity:

    grid = (n/bn, m/bm, d/bd)           # d innermost: accumulate S = X W
    S_acc[bn, bm] += X[i,kd] @ W[kd,j]       (MXU, f32 accumulate)
    at kd == last:  Yc[i,j] = s * cos(S_acc)  (VPU)
                    Ys[i,j] = s * sin(S_acc)

cos and sin land in two separate (n, m) outputs; the wrapper in ops.py
concatenates after unpadding (the [cos, sin] layout of core.baselines).
Unlike the APNC kernel there is no revisited output block: (i, j) is written
exactly once, so both leading grid dims are parallel.

VMEM at defaults (bn=256, bm=256, bd=512, f32):
    X 512KB + W 512KB + S 256KB + Yc 256KB + Ys 256KB  ~=  1.8MB << 16MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.policy import MATMUL_PRECISION

Array = jax.Array

DEFAULT_BN = 256
DEFAULT_BM = 256
DEFAULT_BD = 512


def _rff_kernel(x_ref, w_ref, yc_ref, ys_ref, s_acc, *, scale: float, nd: int):
    kd = pl.program_id(2)  # feature-tile index (innermost)

    @pl.when(kd == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    w = w_ref[...].astype(jnp.float32)  # (bd, bm)
    s_acc[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )

    @pl.when(kd == nd - 1)
    def _nonlin():
        proj = s_acc[...]
        yc_ref[...] = scale * jnp.cos(proj)
        ys_ref[...] = scale * jnp.sin(proj)


def rff_embed_block(
    X: Array,
    W: Array,
    *,
    scale: float,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    bd: int = DEFAULT_BD,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """X (n, d), W (d, m) -> (cos, sin) each (n, m) f32, scaled by `scale`.

    Caller (ops.py) pads n/d/m to tile multiples; padded d rows of W are zero
    so they contribute nothing to the projection, and padded n/m regions are
    sliced off by the caller before the concat.
    """
    n, d = X.shape
    _, m = W.shape
    assert n % bn == 0 and m % bm == 0 and d % bd == 0, (n, m, d, bn, bm, bd)
    grid = (n // bn, m // bm, d // bd)

    return pl.pallas_call(
        functools.partial(_rff_kernel, scale=scale, nd=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bd, bm), lambda i, j, kd: (kd, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, kd: (i, j)),
            pl.BlockSpec((bn, bm), lambda i, j, kd: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), jnp.float32),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(X, W)
