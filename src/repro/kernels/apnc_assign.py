"""Pallas TPU kernel: fused APNC assignment step (Algorithm 2 map + combiner).

Per Lloyd iteration, for each embedding row: distance to every centroid under the
declared discrepancy (l2 for APNC-Nys, l1 for APNC-SD), argmin, and in-VMEM
accumulation of the sufficient statistics (Z, g) — the paper's in-mapper combiner.
Fusing all three means each row of Y is read from HBM exactly ONCE per iteration;
the un-fused XLA path reads it for the distance and again for the one-hot matmul.

    grid = (n/bn,)
    centroids (k, m) live whole in VMEM (k*m <= ~256K elements at paper scales)
    l2: D = yy - 2 Y C^T + cc          (MXU); labels = argmin D
    l1: fori over centroid rows C[c] (read from the ref): running min and
        argmin of sum |Y - C[c]|       (VPU; no (bn, k) matrix is built)
    labels                              -> (bn, 1) i32 tile
    Z (+)= onehot^T @ Y                 (MXU, revisited output block)
    g (+)= colsum onehot

Padded rows (global index >= n_actual) are masked out of (Z, g); padded centroid
rows carry +BIG sentinel coordinates upstream so they never win the argmin.
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


def _nearest(y, c_ref, discrepancy: str):
    """Nearest centroid of each row of y (bn, m) among the rows of c_ref
    (k, m): (labels (bn, 1) i32, min discrepancy (bn, 1) f32). l2's minimum
    is SQUARED (same argmin); ties go to the lowest centroid index, as
    jnp.argmin does."""
    if discrepancy == "l2":
        c = c_ref[...].astype(jnp.float32)
        yy = jnp.sum(y * y, axis=1, keepdims=True)
        cc = jnp.sum(c * c, axis=1, keepdims=True).T
        cross = jax.lax.dot_general(
            y, c, (((1,), (1,)), ((), ())), precision=MATMUL_PRECISION,
            preferred_element_type=jnp.float32,
        )
        D = jnp.maximum(yy - 2.0 * cross + cc, 0.0)  # (bn, k)
        labels = jnp.argmin(D, axis=1).astype(jnp.int32)
        return labels[:, None], jnp.min(D, axis=1)[:, None]
    if discrepancy == "l1":
        # One centroid row per step, read from the ref with a sublane slice:
        # Mosaic has no dynamic lane slice, so D is never built column-wise.
        def dist(ci):
            row = c_ref[pl.ds(ci, 1), :].astype(jnp.float32)  # (1, m)
            return jnp.sum(jnp.abs(y - row), axis=1, keepdims=True)  # (bn, 1)

        def body(ci, carry):
            best, arg = carry
            d = dist(ci)
            closer = d < best  # strict: the first minimum wins ties
            return jnp.where(closer, d, best), jnp.where(closer, ci, arg)

        init = (dist(0), jnp.zeros((y.shape[0], 1), jnp.int32))
        best, arg = jax.lax.fori_loop(1, c_ref.shape[0], body, init)
        return arg, best
    raise ValueError(f"unknown discrepancy {discrepancy!r}")


def _assign_kernel(
    y_ref, c_ref, z_ref, g_ref, lab_ref, *, discrepancy: str, n_actual: int, bn: int
):
    i = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)  # (bn, m)
    k = c_ref.shape[0]
    labels, _ = _nearest(y, c_ref, discrepancy)  # (bn, 1)

    row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)  # global row ids
    valid = (row < n_actual).astype(jnp.float32)  # (bn, 1)

    onehot = (labels == jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1))
    onehot = onehot.astype(jnp.float32) * valid  # masked (bn, k)

    z_contrib = jax.lax.dot_general(
        onehot, y, (((0,), (0,)), ((), ())), precision=MATMUL_PRECISION,
        preferred_element_type=jnp.float32,
    )  # (k, m)
    g_contrib = jnp.sum(onehot, axis=0, keepdims=True).T  # (k, 1)

    @pl.when(i == 0)
    def _init():
        z_ref[...] = z_contrib
        g_ref[...] = g_contrib

    @pl.when(i > 0)
    def _acc():
        z_ref[...] += z_contrib
        g_ref[...] += g_contrib

    lab_ref[...] = labels


def apnc_assign_padded(
    Y: Array,
    C: Array,
    discrepancy: str,
    n_actual: int,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> tuple[Array, Array, Array]:
    """Y (n_pad, m), C (k_pad, m) -> Z (k_pad, m) f32, g (k_pad, 1) f32,
    labels (n_pad, 1) i32. Caller pads and unpads (ops.py)."""
    n, m = Y.shape
    k, _ = C.shape
    assert n % bn == 0, (n, bn)
    grid = (n // bn,)

    return pl.pallas_call(
        functools.partial(
            _assign_kernel, discrepancy=discrepancy, n_actual=n_actual, bn=bn
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec((k, m), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, m), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(Y, C)
