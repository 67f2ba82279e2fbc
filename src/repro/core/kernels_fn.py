"""Kernel functions kappa(.,.) used by the paper (Section 9).

All kernels operate on batches: ``gram(X, Z) -> K`` with ``K[i, j] = kappa(x_i, z_j)``
for ``X: (n, d)``, ``Z: (l, d)``. Everything is pure jnp so the same code runs inside
shard_map blocks and inside the Pallas reference oracles.

The paper uses:
  * RBF (PIE, ImageNet, all large-scale runs) with self-tuned sigma,
  * neural kernel tanh(a x'z + b)  (USPS, a=0.0045 b=0.11),
  * polynomial (x'z + 1)^deg      (MNIST, deg=5),
and we add linear as the trivial member.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.policy import MATMUL_PRECISION

Array = jax.Array


def _sq_dists(X: Array, Z: Array) -> Array:
    """Pairwise squared Euclidean distances, (n, l).

    Uses the expansion ||x - z||^2 = ||x||^2 - 2 x'z + ||z||^2 so the dominant cost
    is one (n, d) x (d, l) matmul — the same structure the Pallas kernel tiles.
    """
    xx = jnp.sum(X * X, axis=-1, keepdims=True)  # (n, 1)
    zz = jnp.sum(Z * Z, axis=-1, keepdims=True).T  # (1, l)
    cross = jnp.dot(X, Z.T, precision=MATMUL_PRECISION)  # (n, l)
    return jnp.maximum(xx - 2.0 * cross + zz, 0.0)


@dataclasses.dataclass(frozen=True)
class Kernel:
    """A kernel function with its parameters. Hashable => usable as a static arg."""

    name: str  # "rbf" | "poly" | "tanh" | "linear"
    gamma: float = 1.0  # rbf: exp(-gamma ||x-z||^2)
    degree: int = 5  # poly
    coef0: float = 1.0  # poly / tanh offset
    scale: float = 1.0  # tanh slope a

    def gram(self, X: Array, Z: Array) -> Array:
        """Dense kernel matrix K[i, j] = kappa(X[i], Z[j]); shape (n, l)."""
        if self.name == "rbf":
            return jnp.exp(-self.gamma * _sq_dists(X, Z))
        return self._from_dot(jnp.dot(X, Z.T, precision=MATMUL_PRECISION))

    def from_cross(self, cross: Array, xx: Array, zz: Array) -> Array:
        """The gram from its parts, for rows that are never densified:
        cross = X Z^T (n, l), xx = ||x||^2 (n, 1), zz = ||z||^2 (1, l)."""
        if self.name == "rbf":
            return jnp.exp(-self.gamma * jnp.maximum(xx - 2.0 * cross + zz, 0.0))
        return self._from_dot(cross)

    def _from_dot(self, dot: Array) -> Array:
        if self.name == "poly":
            return (dot + self.coef0) ** self.degree
        if self.name == "tanh":
            return jnp.tanh(self.scale * dot + self.coef0)
        if self.name == "linear":
            return dot
        raise ValueError(f"unknown kernel {self.name!r}")

    def diag(self, X: Array) -> Array:
        """kappa(x, x) for each row — needed by exact kernel k-means (Eq. 2)."""
        if self.name == "rbf":
            return jnp.ones(X.shape[0], X.dtype)
        sq = jnp.sum(X * X, axis=-1)
        if self.name == "poly":
            return (sq + self.coef0) ** self.degree
        if self.name == "tanh":
            return jnp.tanh(self.scale * sq + self.coef0)
        if self.name == "linear":
            return sq
        raise ValueError(f"unknown kernel {self.name!r}")


def self_tuned_rbf(X: Array, sample: int = 512, seed: int = 0) -> Kernel:
    """Self-tuning sigma estimate used by [7] and Section 9: sigma = mean pairwise
    distance over a small sample; gamma = 1 / (2 sigma^2)."""
    n = X.shape[0]
    idx = jax.random.choice(jax.random.PRNGKey(seed), n, (min(sample, n),), replace=False)
    S = X[idx]
    d2 = _sq_dists(S, S)
    # mean over off-diagonal distances
    m = d2.shape[0]
    sigma2 = jnp.sum(d2) / (m * (m - 1))
    sigma2 = jnp.maximum(sigma2, 1e-12)
    return Kernel("rbf", gamma=float(1.0 / (2.0 * sigma2)))


# Paper Section 9 kernel settings, by dataset family.
USPS_KERNEL = Kernel("tanh", scale=0.0045, coef0=0.11)
MNIST_KERNEL = Kernel("poly", degree=5, coef0=1.0)


def make_kernel(name: str, **kw) -> Kernel:
    return Kernel(name=name, **kw)
