"""Pallas TPU kernel: fused APNC embedding  Y = kappa(X, L) @ R^T.

The paper's dominant FLOPs (Algorithm 1): the pairwise kernel block K_{L,B} followed
by the coefficient contraction. A 2013 Hadoop mapper streams rows; the TPU-native
rethink tiles both matmuls through VMEM so the (bn x bl) kernel-matrix tile is
consumed by the MXU immediately and K NEVER materializes in HBM:

    grid = (n/bn, l/bl, d/bd)           # d innermost: accumulate S = X L^T
    S_acc[bn, bl] += X[i,kd] @ L[j,kd]^T     (MXU, f32 accumulate)
    rbf row/col norms accumulated alongside in the same pass
    at kd == last:  K = nonlin(S_acc)        (VPU)
                    Y[i] (+)= K @ R[:, j]^T  (MXU, revisited output block)

All tiles are 128-aligned (MXU/VREG lanes); f32 accumulation; bf16/f32 inputs.
VMEM budget at defaults (bn=256, bl=256, bd=512, m<=1024, f32):
    X 512KB + L 512KB + R 1MB + S 256KB + Y 1MB + norms ~2KB  ~=  3.3MB << 16MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_fn import Kernel
from repro.policy import MATMUL_PRECISION

Array = jax.Array

DEFAULT_BN = 256
DEFAULT_BL = 256
DEFAULT_BD = 512


def _apply_kernel_nonlin(kernel: Kernel, S, xx, ll):
    """Elementwise kernel nonlinearity on the accumulated cross-products tile."""
    if kernel.name == "rbf":
        d2 = jnp.maximum(xx + ll - 2.0 * S, 0.0)
        return jnp.exp(-kernel.gamma * d2)
    if kernel.name == "poly":
        return (S + kernel.coef0) ** kernel.degree
    if kernel.name == "tanh":
        return jnp.tanh(kernel.scale * S + kernel.coef0)
    if kernel.name == "linear":
        return S
    raise ValueError(f"unknown kernel {kernel.name!r}")


def _embed_kernel(x_ref, l_ref, r_ref, y_ref, s_acc, xx_acc, ll_acc, *, kernel: Kernel, nd: int):
    j = pl.program_id(1)  # landmark-tile index
    kd = pl.program_id(2)  # feature-tile index (innermost)

    @pl.when(kd == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        xx_acc[...] = jnp.zeros_like(xx_acc)
        ll_acc[...] = jnp.zeros_like(ll_acc)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    l = l_ref[...].astype(jnp.float32)  # (bl, bd)
    s_acc[...] += jax.lax.dot_general(
        x, l, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )
    if kernel.name == "rbf":  # norms ride along in the same d-pass
        xx_acc[...] += jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
        ll_acc[...] += jnp.sum(l * l, axis=1, keepdims=True).T  # (1, bl)

    @pl.when(kd == nd - 1)
    def _contract():
        K = _apply_kernel_nonlin(kernel, s_acc[...], xx_acc[...], ll_acc[...])
        r = r_ref[...].astype(jnp.float32)  # (m, bl)
        contrib = jax.lax.dot_general(
            K, r, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32,
        )  # (bn, m)

        @pl.when(j == 0)
        def _set():
            y_ref[...] = contrib

        @pl.when(j > 0)
        def _add():
            y_ref[...] += contrib


def apnc_embed_block(
    X: Array,
    landmarks: Array,
    R: Array,
    kernel: Kernel,
    *,
    bn: int = DEFAULT_BN,
    bl: int = DEFAULT_BL,
    bd: int = DEFAULT_BD,
    interpret: bool = False,
) -> Array:
    """One APNC block: X (n, d), landmarks (l, d), R (m, l) -> Y (n, m) f32.

    Caller (ops.py) is responsible for padding n/l/d/m to tile multiples; padded
    landmark columns must come with zero R columns so they contribute nothing.
    """
    n, d = X.shape
    l, _ = landmarks.shape
    m, _ = R.shape
    assert n % bn == 0 and l % bl == 0 and d % bd == 0, (n, l, d, bn, bl, bd)
    grid = (n // bn, l // bl, d // bd)

    return pl.pallas_call(
        functools.partial(_embed_kernel, kernel=kernel, nd=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bl, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((m, bl), lambda i, j, kd: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, m), lambda i, j, kd: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, bl), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((1, bl), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(X, landmarks, R)
