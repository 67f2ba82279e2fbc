"""Random Fourier features as a first-class family member ("rff").

Rahimi-Recht features for the RBF kernel exp(-gamma ||x - z||^2):

    z(x) = sqrt(1/m) [cos(x W), sin(x W)],   W ~ N(0, 2 gamma I)  (d, m)

E[<z(x), z(z')>] = kappa(x, z'), so plain k-means on z(X) approximates kernel
k-means — Chitta et al. (1402.3849), previously dead-end baseline code in
core/baselines.py. On the protocol it gains every execution regime for free:
the stream/shard_map/minibatch backends, the fused-dispatch serving path, and
checkpointing. The member is landmark-free (the fit is a data-independent
draw; only d is read from the data) and declares e = l2, q = 1.

The draw matches core.baselines.rff_features bit-for-bit given the same key,
so the baseline is now a shim over this member.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import Kernel
from repro.embed.base import Embedding, EmbeddingProps, register_embedding
from repro.policy import MATMUL_PRECISION

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RFFParams:
    """The fitted RFF map: the frequency matrix W (gamma absorbed into the
    draw) plus the approximated kernel for provenance."""

    W: Array  # (d, m_half); output dim is 2 * m_half ([cos, sin])
    kernel: Kernel = dataclasses.field(metadata=dict(static=True))

    @property
    def m(self) -> int:  # total embedding dimensionality
        return 2 * self.W.shape[1]

    @property
    def d(self) -> int:  # input dimensionality
        return self.W.shape[0]

    @property
    def discrepancy(self) -> str:
        return "l2"

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.W.shape[1])


def rff_transform(params: RFFParams, X: Array) -> Array:
    """Reference map: (n, d) -> (n, 2 m_half) f32 in [cos, sin] layout."""
    proj = jnp.dot(X, params.W.astype(X.dtype), precision=MATMUL_PRECISION)
    scale = jnp.asarray(params.scale, proj.dtype)
    return scale * jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=-1)


@register_embedding
class RFFEmbedding(Embedding):
    name = "rff"
    params_cls = RFFParams
    landmark_free = True
    kernel_families = ("rbf",)  # shift-invariant members implemented

    def fit(self, key, data, kernel, *, l, m, t=None, q=1) -> RFFParams:
        """Draw W for m cosine features (output dim 2m). `l` and `t` are
        landmark/subset knobs of the kernelized members and are ignored;
        q > 1 block ensembles are not defined for this member."""
        if kernel.name != "rbf":
            raise ValueError(
                "the rff embedding approximates shift-invariant kernels; got "
                f"kernel {kernel.name!r} (use method='nystrom'/'sd' for "
                "arbitrary kernels, or 'tensorsketch' for polynomial)"
            )
        if q != 1:
            raise ValueError("rff is not blockwise; q must be 1")
        if m < 1:
            raise ValueError(f"m must be >= 1, got {m}")
        # Same split/draw as the original baseline (second key reserved for a
        # phase-shift variant) so rff_features replays bit-for-bit.
        kw, _ = jax.random.split(key)
        d = data.shape[-1]
        W = jax.random.normal(kw, (d, m), jnp.float32) * jnp.sqrt(2.0 * kernel.gamma)
        return RFFParams(W=W, kernel=kernel)

    def transform(self, params: RFFParams, X: Array) -> Array:
        return rff_transform(params, X)

    def pallas_transform(self, params: RFFParams, X: Array) -> Array:
        from repro.kernels import ops  # lazy: kernels are optional at import time

        return ops.rff_embed(X, params)

    def props(self, params: RFFParams) -> EmbeddingProps:
        return EmbeddingProps(
            linear=False, discrepancy="l2", blockwise=False,
            landmark_free=self.landmark_free,
        )
