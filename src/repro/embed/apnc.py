"""The two APNC members of the paper, on the Embedding protocol.

  * "nystrom" — Section 6 / Algorithm 3: R = Lambda_m^{-1/2} V_m^T from the
    rank-m eigendecomposition of K_LL; e = l2.
  * "sd"      — Section 7 / Algorithm 4: p-stable (Gaussian) directions in the
    whitened kernel space of the centered landmark gram; e = l1 (Eq. 13).

Both share `APNCCoefficients` (core.apnc) as their typed params — y = R K_{L,i}
— so they share one transform (core.apnc.embed as the jnp reference, the fused
Pallas kernel of kernels/apnc_embed.py as the fast path) and one checkpoint
layout; they differ only in how R is fit and in the declared discrepancy.

This module is the real home of the coefficient fits; `core.nystrom.fit` and
`core.stable.fit` are shims over it for the original call sites.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.apnc import APNCCoefficients, embed
from repro.core.kernels_fn import Kernel
from repro.embed.base import Embedding, EmbeddingProps, register_embedding
from repro.policy import MATMUL_PRECISION

Array = jax.Array

_EIG_EPS = 1e-8
_EIG_RCOND = 1e-6  # relative to the top eigenvalue, pinv-style


def _inv_sqrt_clamped(lam: Array) -> Array:
    """1/sqrt(lam) with tiny/negative eigenvalues zeroed. The cutoff is
    RELATIVE to the top eigenvalue (plus an absolute floor): rank-deficient
    grams (e.g. the linear kernel, rank <= d) produce roundoff eigenvalues
    around l * eps * ||K|| — far above any absolute floor — whose inverse
    square roots would amplify pure noise by orders of magnitude and break
    exact-arithmetic properties like P4.1 linearity numerically
    (tests/test_embed.py). Genuinely informative small eigendirections sit
    well above this cutoff on the paper's kernels."""
    eps = jnp.maximum(_EIG_EPS, _EIG_RCOND * jnp.maximum(lam[-1], 0.0))
    return jnp.where(lam > eps, jax.lax.rsqrt(jnp.maximum(lam, eps)), 0.0)


def sample_landmarks(key: Array, X: Array, l: int) -> Array:
    """Algorithm 3 map phase: uniform sample of l rows (deterministic under key —
    the Bernoulli(l/n) of the paper is replaced by sampling without replacement so
    restarts reproduce exactly; the distribution is the same conditional on size)."""
    n = X.shape[0]
    idx = jax.random.choice(key, n, (l,), replace=False)
    return X[idx]


# ------------------------------------------------------------------- nystrom


def _nystrom_block(landmarks: Array, kernel: Kernel, m: int) -> Array:
    """Algorithm 3 reduce phase for one block: R^(b) = Lambda_m^{-1/2} V_m^T."""
    K_LL = kernel.gram(landmarks, landmarks)
    # eigh returns ascending order; take the top-m.
    lam, V = jnp.linalg.eigh(K_LL)  # (l,), (l, l)
    # Clamp tiny/negative eigenvalues (K_LL is PSD up to roundoff): their inverse
    # square root is zeroed, which drops the corresponding (noise) direction.
    inv_sqrt = _inv_sqrt_clamped(lam)[-m:]  # top-m (eigh is ascending)
    V_m = V[:, -m:]  # (l, m)
    return inv_sqrt[:, None] * V_m.T  # (m, l)


def fit_nystrom(
    key: Array, X: Array, kernel: Kernel, l: int, m: int, q: int = 1
) -> APNCCoefficients:
    """Fit APNC-Nys coefficients. l landmarks total, embedding dim q * m.

    q = 1 is the paper's Algorithm 3; q > 1 is the ensemble-Nystrom extension
    (each of q disjoint landmark subsets of size l // q gets its own R block).
    """
    if l % q:
        raise ValueError(f"l={l} must be divisible by q={q}")
    l_b = l // q
    if m > l_b:
        raise ValueError(f"m={m} must be <= landmarks-per-block {l_b}")
    landmarks = sample_landmarks(key, X, l).reshape(q, l_b, X.shape[-1])
    R = jnp.stack([_nystrom_block(landmarks[b], kernel, m) for b in range(q)])
    return APNCCoefficients(landmarks=landmarks, R=R, kernel=kernel, discrepancy="l2")


# ------------------------------------------------------------------------ sd


def _sd_block(key: Array, landmarks: Array, kernel: Kernel, m: int, t: int) -> Array:
    """Algorithm 4 reduce phase for one block (whiten the centered gram, sum
    random t-subsets of whitening rows, re-center)."""
    l = landmarks.shape[0]
    K_LL = kernel.gram(landmarks, landmarks)
    H = jnp.eye(l) - jnp.full((l, l), 1.0 / l)
    HK = jnp.dot(H, K_LL, precision=MATMUL_PRECISION)
    G = jnp.dot(HK, H, precision=MATMUL_PRECISION)  # centered gram
    G = 0.5 * (G + G.T)  # fight asymmetry from roundoff before eigh
    lam, V = jnp.linalg.eigh(G)
    E = _inv_sqrt_clamped(lam)[:, None] * V.T  # (l, l) inverse square root factor

    # m random t-subsets of rows of E (Alg 4 lines 11-14). A boolean selection
    # matrix S (m, l) with exactly t ones per row lets the sum be one matmul.
    def one_row(k):
        sel = jax.random.choice(k, l, (t,), replace=False)
        return jnp.zeros((l,)).at[sel].set(1.0)

    S = jax.vmap(one_row)(jax.random.split(key, m))  # (m, l)
    # rows R_r = (sum_{v in T_r} E_v) H   [Alg 4 line 15]
    R = jnp.dot(jnp.dot(S, E, precision=MATMUL_PRECISION), H, precision=MATMUL_PRECISION)
    # 1/sqrt(t) from Eq. (14) keeps projections O(1)-scaled; it is absorbed into
    # the constant beta of Property 4.4 but applying it keeps numerics tame.
    return R / jnp.sqrt(jnp.asarray(t, R.dtype))


def fit_sd(
    key: Array, X: Array, kernel: Kernel, l: int, m: int,
    t: int | None = None, q: int = 1,
) -> APNCCoefficients:
    """Fit APNC-SD coefficients. Default t = 40% of l per the paper's experiments."""
    if l % q:
        raise ValueError(f"l={l} must be divisible by q={q}")
    l_b = l // q
    t = max(1, int(round(0.4 * l_b))) if t is None else t
    if not 1 <= t <= l_b:
        raise ValueError(f"t={t} must be in [1, {l_b}]")
    k_sample, k_rows = jax.random.split(key)
    landmarks = sample_landmarks(k_sample, X, l).reshape(q, l_b, X.shape[-1])
    keys = jax.random.split(k_rows, q)
    R = jnp.stack([_sd_block(keys[b], landmarks[b], kernel, m, t) for b in range(q)])
    return APNCCoefficients(landmarks=landmarks, R=R, kernel=kernel, discrepancy="l1")


# ------------------------------------------------------------ family members


class _APNCBase(Embedding):
    """Shared transform/props/pallas path of the two (R, L) members."""

    params_cls = APNCCoefficients

    def transform(self, params: APNCCoefficients, X: Array) -> Array:
        return embed(X, params)

    def pallas_transform(self, params: APNCCoefficients, X: Array) -> Array:
        from repro.kernels import ops  # lazy: kernels are optional at import time

        return ops.apnc_embed(X, params)

    def props(self, params: APNCCoefficients) -> EmbeddingProps:
        return EmbeddingProps(
            # y = R K_{L, i} is linear in the KERNEL representation always
            # (P4.1 proper); it is linear in the INPUT exactly when kappa is.
            linear=params.kernel.name == "linear",
            discrepancy=params.discrepancy,
            blockwise=True,
            landmark_free=self.landmark_free,
        )


@register_embedding
class NystromEmbedding(_APNCBase):
    name = "nystrom"

    def fit(self, key, data, kernel, *, l, m, t=None, q=1):
        return fit_nystrom(key, data, kernel, l=l, m=m, q=q)


@register_embedding
class SDEmbedding(_APNCBase):
    name = "sd"

    def fit(self, key, data, kernel, *, l, m, t=None, q=1):
        return fit_sd(key, data, kernel, l=l, m=m, t=t, q=q)
