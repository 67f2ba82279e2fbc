"""Pallas TPU kernel: ONE fused Lloyd step — embed + assign + reduce in VMEM.

The communication-avoiding form of the per-block Lloyd map (following
*Communication-Avoiding Linear Algebraic Kernel K-Means on GPUs*, PAPERS.md):
the raw (bn, d) row block is embedded, assigned, and reduced to the (Z, g)
sufficient stats and its inertia contribution without the embedded Y ever
leaving VMEM. The un-fused chain (`apnc_embed` / `rff_embed` then
`apnc_assign`) round-trips Y (n, m) through HBM once per Lloyd iteration —
this kernel eliminates that traffic entirely and halves the dispatch count.

    grid = (n/bn,)                       # everything else resident whole
    [apnc, q=1]  S = X L^T ; K = nonlin(S) ; Y = K R^T          (MXU+VPU)
    [rff]        S = X W   ; Y = s [cos(S), sin(S)]             (MXU+VPU)
    [dequant]    Y = Yq * scale          # quantized staged cache (Y-mode)
    shared epilogue (same math as apnc_assign + core.lloyd.block_cost):
        labels, min e = nearest(Y, C)    # l2 squared (same argmin) or l1
                                         -> (bn, 1) i32 / f32 tiles
        Z (+)= onehot^T @ Y              (MXU, revisited output block)
        g (+)= colsum onehot
        cost (+)= sum_valid min e        # sqrt'd for l2: block_cost's units

Fusable members hold ALL operands whole in VMEM, so this kernel only applies
at paper scales (l, m, k <= ~1024); ops.lloyd_step_plan falls back to the
un-fused chain for anything bigger, for q > 1 APNC, and for non-fusable
members (TensorSketch's FFT). Padded rows (>= n_actual) are masked out of
(Z, g, cost); padded centroid rows carry +BIG sentinels upstream; padded RFF
projection columns are re-zeroed in-kernel (cos(0) = 1 would otherwise leak
`scale` into every padded lane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_fn import Kernel
from repro.kernels.apnc_assign import _nearest
from repro.kernels.apnc_embed import _apply_kernel_nonlin
from repro.policy import MATMUL_PRECISION

Array = jax.Array

DEFAULT_BN = 256


def _assign_reduce(
    i, y, c_ref, z_ref, g_ref, lab_ref, cost_ref,
    *, discrepancy: str, n_actual: int, bn: int,
):
    """Shared fused epilogue: nearest centroid, masked (Z, g) and cost tiles."""
    k = c_ref.shape[0]
    labels, mind = _nearest(y, c_ref, discrepancy)  # (bn, 1); l2 min SQUARED

    row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)  # global row ids
    valid = (row < n_actual).astype(jnp.float32)  # (bn, 1)

    onehot = (labels == jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1))
    onehot = onehot.astype(jnp.float32) * valid  # masked (bn, k)

    z_contrib = jax.lax.dot_general(
        onehot, y, (((0,), (0,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (k, m)
    g_contrib = jnp.sum(onehot, axis=0, keepdims=True).T  # (k, 1)

    if discrepancy == "l2":  # block_cost reports sqrt'd l2 — match its units
        mind = jnp.sqrt(jnp.maximum(mind, 0.0))
    cost_contrib = jnp.sum(mind * valid).reshape(1, 1)

    @pl.when(i == 0)
    def _init():
        z_ref[...] = z_contrib
        g_ref[...] = g_contrib
        cost_ref[...] = cost_contrib

    @pl.when(i > 0)
    def _acc():
        z_ref[...] += z_contrib
        g_ref[...] += g_contrib
        cost_ref[...] += cost_contrib

    lab_ref[...] = labels


def _apnc_step_kernel(
    x_ref, l_ref, r_ref, c_ref, z_ref, g_ref, lab_ref, cost_ref,
    *, kernel: Kernel, discrepancy: str, n_actual: int, bn: int,
):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    lm = l_ref[...].astype(jnp.float32)  # (l, d)
    S = jax.lax.dot_general(
        x, lm, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (bn, l)
    if kernel.name == "rbf":
        xx = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
        ll = jnp.sum(lm * lm, axis=1, keepdims=True).T  # (1, l)
    else:
        xx = ll = jnp.zeros((1, 1), jnp.float32)
    K = _apply_kernel_nonlin(kernel, S, xx, ll)
    r = r_ref[...].astype(jnp.float32)  # (m, l)
    y = jax.lax.dot_general(
        K, r, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (bn, m): padded R rows are zero -> padded Y columns are exactly 0
    _assign_reduce(
        i, y, c_ref, z_ref, g_ref, lab_ref, cost_ref,
        discrepancy=discrepancy, n_actual=n_actual, bn=bn,
    )


def fused_apnc_step(
    X: Array,
    landmarks: Array,
    R: Array,
    C: Array,
    kernel: Kernel,
    discrepancy: str,
    n_actual: int,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[Array, Array, Array, Array]:
    """X (n, d), landmarks (l, d), R (m, l), C (k, m) ->
    Z (k, m) f32, g (k, 1) f32, labels (n, 1) i32, cost (1, 1) f32.

    Caller (ops.py) pads n/l/d/m/k to tile multiples: zero R columns for padded
    landmarks, zero R rows for padded embedding dims (so C's padded columns can
    be zero too), +BIG sentinel rows for padded centroids.
    """
    n, d = X.shape
    l, _ = landmarks.shape
    m, _ = R.shape
    k, _ = C.shape
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)

    return pl.pallas_call(
        functools.partial(
            _apnc_step_kernel,
            kernel=kernel, discrepancy=discrepancy, n_actual=n_actual, bn=bn,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((l, d), lambda i: (0, 0)),
            pl.BlockSpec((m, l), lambda i: (0, 0)),
            pl.BlockSpec((k, m), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, m), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(X, landmarks, R, C)


def _dequant_step_kernel(
    yq_ref, s_ref, c_ref, z_ref, g_ref, lab_ref, cost_ref,
    *, discrepancy: str, n_actual: int, bn: int,
):
    i = pl.program_id(0)
    # Dequantize IN VMEM: the quantized tile (int8 / bf16) is what crossed
    # HBM; the f32 block exists only here. The (1, m) scale row carries each
    # feature's own dequant factor (int8's per-column symmetric scaling) and
    # broadcasts over the row axis. Zero payload rows/cols dequantize
    # to exactly 0, so the caller's zero padding matches zero-padded C.
    y = yq_ref[...].astype(jnp.float32) * s_ref[...]
    _assign_reduce(
        i, y, c_ref, z_ref, g_ref, lab_ref, cost_ref,
        discrepancy=discrepancy, n_actual=n_actual, bn=bn,
    )


def fused_dequant_step(
    Yq: Array,
    scale: Array,
    C: Array,
    discrepancy: str,
    n_actual: int,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[Array, Array, Array, Array]:
    """The Y-mode Lloyd step over a QUANTIZED staged block (DESIGN.md §17):
    Yq (n, m) int8/bf16, scale (1, m) f32 per-column dequant row,
    C (k, m) -> Z (k, m) f32, g (k, 1) f32, labels (n, 1) i32,
    cost (1, 1) f32.

    Same epilogue as the fused X-mode kernels (`_assign_reduce`), with the
    embed stage replaced by the dequantization `Yq * scale` — so the decoded
    f32 Y never materializes outside VMEM. Caller (ops.py) zero-pads Yq/C and
    gives padded centroid rows +BIG sentinels.
    """
    n, m = Yq.shape
    k, _ = C.shape
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)

    return pl.pallas_call(
        functools.partial(
            _dequant_step_kernel,
            discrepancy=discrepancy, n_actual=n_actual, bn=bn,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((k, m), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, m), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(Yq, scale, C)


def _rff_step_kernel(
    x_ref, w_ref, c_ref, z_ref, g_ref, lab_ref, cost_ref,
    *, scale: float, discrepancy: str, n_actual: int, m_half: int, bn: int,
):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    w = w_ref[...].astype(jnp.float32)  # (d, mh_pad)
    S = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (bn, mh_pad)
    # Padded W columns project to 0, but cos(0) = 1: re-zero those lanes so the
    # padded Y columns stay exactly 0 (matching the zero-padded centroids).
    col = jax.lax.broadcasted_iota(jnp.int32, S.shape, 1)
    keep = (col < m_half).astype(jnp.float32)
    y = jnp.concatenate(
        [scale * jnp.cos(S) * keep, scale * jnp.sin(S) * keep], axis=1
    )  # (bn, 2*mh_pad): the wrapper lays C out in the same padded [cos|sin]
    _assign_reduce(
        i, y, c_ref, z_ref, g_ref, lab_ref, cost_ref,
        discrepancy=discrepancy, n_actual=n_actual, bn=bn,
    )


def fused_rff_step(
    X: Array,
    W: Array,
    C: Array,
    discrepancy: str,
    n_actual: int,
    *,
    scale: float,
    m_half: int,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[Array, Array, Array, Array]:
    """X (n, d), W (d, mh_pad), C (k, 2*mh_pad) ->
    Z (k, 2*mh_pad) f32, g (k, 1) f32, labels (n, 1) i32, cost (1, 1) f32.

    Caller (ops.py) pads and lays C out as [cos_real | 0 | sin_real | 0] so
    padded projection lanes (re-zeroed in-kernel) contribute nothing; `m_half`
    is the REAL half-width before padding.
    """
    n, d = X.shape
    _, mh = W.shape
    k, m2 = C.shape
    assert m2 == 2 * mh, (m2, mh)
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)

    return pl.pallas_call(
        functools.partial(
            _rff_step_kernel,
            scale=scale, discrepancy=discrepancy,
            n_actual=n_actual, m_half=m_half, bn=bn,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d, mh), lambda i: (0, 0)),
            pl.BlockSpec((k, m2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, m2), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m2), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(X, W, C)
