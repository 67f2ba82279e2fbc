"""APNC embedding family (paper Section 4).

An APNC embedding is ``y = f(phi) = R @ K_{L, i}`` where:

  * P4.1  f is linear            -> centroid-of-embeddings == embedding-of-centroid
  * P4.2  f is kernelized        -> only kernel evaluations vs landmarks L needed
  * P4.3  R is block-diagonal    -> each (R^(b), L^(b)) fits one worker's memory
  * P4.4  e(y, y_bar) ~ beta * ||phi - phi_bar||_2 for a known discrepancy e(.,.)

``APNCCoefficients`` carries the blocks as stacked arrays (q, m_b, l_b) /
(q, l_b, d), so the q=1 common case and the q>1 ensemble case share one code path.
The concrete instances (Nystrom, stable-distributions) only differ in how R is fit
and in which discrepancy e they declare ("l2" vs "l1").
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import Kernel
from repro.policy import MATMUL_PRECISION

Array = jax.Array
Discrepancy = Literal["l2", "l1"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class APNCCoefficients:
    """The (R, L) pair of Property 4.2/4.3, in block form.

    landmarks: (q, l_b, d)   -- the q disjoint landmark subsets L^(b)
    R:         (q, m_b, l_b) -- the q diagonal blocks of the coefficients matrix
    """

    landmarks: Array
    R: Array
    kernel: Kernel = dataclasses.field(metadata=dict(static=True))
    discrepancy: Discrepancy = dataclasses.field(metadata=dict(static=True))

    @property
    def q(self) -> int:
        return self.landmarks.shape[0]

    @property
    def m(self) -> int:  # total embedding dimensionality
        return self.R.shape[0] * self.R.shape[1]

    @property
    def l(self) -> int:  # total number of landmarks
        return self.landmarks.shape[0] * self.landmarks.shape[1]

    @property
    def d(self) -> int:  # input dimensionality
        return self.landmarks.shape[-1]


def embed_block(X: Array, landmarks_b: Array, R_b: Array, kernel: Kernel) -> Array:
    """One block of Algorithm 1: y_[b] = R^(b) K_{L^(b), i} for a batch of rows.

    X: (n, d), landmarks_b: (l_b, d), R_b: (m_b, l_b)  ->  (n, m_b).
    This is the map-only hot loop; the Pallas kernel `apnc_embed` implements the
    same contraction fused (see repro/kernels). Here: the pure-jnp fallback.
    """
    K = kernel.gram(X, landmarks_b)  # (n, l_b)
    return jnp.dot(K, R_b.T, precision=MATMUL_PRECISION)  # (n, m_b)


def embed(X: Array, coeffs: APNCCoefficients) -> Array:
    """Full APNC embedding Y = f(X): (n, d) -> (n, q * m_b).

    Blocks are independent (block-diagonal R) — the concatenation is Algorithm 1's
    shuffle-free join. q is static so a python loop unrolls into q fused matmuls.
    """
    parts = [
        embed_block(X, coeffs.landmarks[b], coeffs.R[b], coeffs.kernel)
        for b in range(coeffs.q)
    ]
    return jnp.concatenate(parts, axis=-1)


def csr_operands(landmarks: Array) -> tuple[Array, Array]:
    """What the sparse embedding reads of the landmarks (q, l_b, d): their
    rows transposed into one (d, q * l_b) table, gathered by column id, and
    their squared norms (1, q * l_b)."""
    L = landmarks.reshape(-1, landmarks.shape[-1])
    return L.T, jnp.sum(L * L, axis=-1)[None, :]


def embed_csr(values: Array, cols: Array, indptr: Array, landmarks_t: Array,
              landmarks_sq: Array, R: Array, kernel: Kernel) -> Array:
    """APNC embedding of one CSR block (`stream.blockstore.CSRBlock` leaves)
    without densifying it: (b, q * m_b).

    The gram's cross term X L^T is summed per row from the nonzeros against
    the gathered rows of L^T (`csr_operands`), ||x||^2 from the values; the
    kernel then comes from its parts (`Kernel.from_cross`) and is contracted
    with each R^(b) at HIGHEST, as `embed` does. Padding slots carry value 0
    and add nothing to either sum.
    """
    b = indptr.shape[0] - 1
    # Row of each slot: the row starts at or before it. Padding slots lie
    # after the last row's nonzeros and land in the last row, with value 0.
    row = jnp.cumsum(
        jnp.zeros(values.shape[0], jnp.int32).at[indptr[1:-1]].add(1, mode="drop"))
    cross = jax.ops.segment_sum(values[:, None] * landmarks_t[cols], row,
                                num_segments=b, indices_are_sorted=True)
    xx = jax.ops.segment_sum(values * values, row, num_segments=b,
                             indices_are_sorted=True)[:, None]
    K = kernel.from_cross(cross, xx, landmarks_sq)
    l_b = R.shape[-1]
    parts = [
        jnp.dot(K[:, i * l_b:(i + 1) * l_b], R[i].T, precision=MATMUL_PRECISION)
        for i in range(R.shape[0])
    ]
    return jnp.concatenate(parts, axis=-1)


def pairwise_discrepancy(Y: Array, C: Array, discrepancy: Discrepancy) -> Array:
    """e(y_i, c_j) for all pairs: Y (n, m), C (k, m) -> (n, k).

    l2 uses the inner-product expansion (one MXU matmul dominates); l1 is the
    stable-distributions estimator of Eq. (13) and is evaluated per-centroid to
    keep the footprint at O(n * m) instead of O(n * m * k).
    """
    if discrepancy == "l2":
        yy = jnp.sum(Y * Y, axis=-1, keepdims=True)  # (n, 1)
        cc = jnp.sum(C * C, axis=-1)[None, :]  # (1, k)
        cross = jnp.dot(Y, C.T, precision=MATMUL_PRECISION)  # (n, k)
        d2 = jnp.maximum(yy - 2.0 * cross + cc, 0.0)
        return jnp.sqrt(d2)
    if discrepancy == "l1":
        def one(c):
            return jnp.sum(jnp.abs(Y - c[None, :]), axis=-1)  # (n,)

        return jax.vmap(one, out_axes=1)(C)  # (n, k)
    raise ValueError(f"unknown discrepancy {discrepancy!r}")


def assign(Y: Array, C: Array, discrepancy: Discrepancy) -> Array:
    """Approximate assignment step, Eq. (4): argmin_c e(y_i, c)."""
    return jnp.argmin(pairwise_discrepancy(Y, C, discrepancy), axis=-1)


def sufficient_stats(Y: Array, labels: Array, k: int) -> tuple[Array, Array]:
    """The paper's (Z, g): per-cluster embedding sums and counts (Algorithm 2).

    These are the ONLY quantities that cross the network in the distributed
    clustering phase. Z: (k, m), g: (k,).
    """
    onehot = jax.nn.one_hot(labels, k, dtype=Y.dtype)  # (n, k)
    Z = jnp.dot(onehot.T, Y, precision=MATMUL_PRECISION)  # (k, m)
    g = jnp.sum(onehot, axis=0)  # (k,)
    return Z, g
