"""Plain reference of the imagenet-nystrom configuration's embedding.

APNC-Nystrom (arXiv:1311.2334, Section 6, Algorithm 3): l landmarks L drawn
from the rows, K_LL = kappa(L, L), R = Lambda_m^{-1/2} V_m^T from the top m
eigenpairs of K_LL, y(x) = R kappa(L, x), with kappa the rbf kernel
exp(-gamma ||x - z||^2).

Which rows became landmarks, and the signs of the eigenvectors, are the
fit's own draws, so the reference verifies them rather than redrawing:

  landmark_miss   landmarks that are not rows of X (must be 0);
  whiten_gap      max |R K_LL R^T - I| with K_LL in float64: R whitens K_LL;
  spectrum_gap    |trace(R K_LL^2 R^T) - sum of the top m eigenvalues of
                  K_LL| / that sum: R spans the top m eigenvectors and no
                  others (the trace is the sum of the eigenvalues it kept).

It then embeds X with those landmarks and that R in plain float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


_INDEX: dict = {}  # id(X) -> (X, probe vector, row order, sorted projections)


def _row_index(X: np.ndarray):
    """Rows of X sorted by a random projection, built once per X."""
    hit = _INDEX.get(id(X))
    if hit is None or hit[0] is not X:
        v = np.random.default_rng(0).standard_normal(X.shape[1]).astype(np.float32)
        fx = X @ v
        order = np.argsort(fx)
        hit = (X, v, order, fx[order])
        _INDEX.clear()
        _INDEX[id(X)] = hit
    return hit[1:]


def rows_missing(X: np.ndarray, rows: np.ndarray) -> int:
    """How many of `rows` are not rows of X (exact float equality)."""
    v, order, sx = _row_index(X)
    missing = 0
    for row, f in zip(rows, rows @ v):
        tol = 1e-3 * (abs(float(f)) + 1.0)
        lo = np.searchsorted(sx, f - tol, "left")
        hi = np.searchsorted(sx, f + tol, "right")
        if not any(np.array_equal(X[i], row) for i in order[lo:hi]):
            missing += 1
    return missing


def reference_params(config: dict, fit_key, answer_params: dict, X: np.ndarray):
    """(parameters the reference embeds with, {check name: value})."""
    gamma = config["gamma_times_d"] / config["d"]
    m = config["m"]
    L = np.asarray(answer_params["landmarks"], np.float32)[0]  # q = 1
    R = np.asarray(answer_params["R"], np.float64)[0]
    L64 = L.astype(np.float64)
    sq = ((L64 * L64).sum(1)[:, None] - 2.0 * L64 @ L64.T
          + (L64 * L64).sum(1)[None, :])
    K = np.exp(-gamma * np.maximum(sq, 0.0))
    lam = np.linalg.eigvalsh(K)
    top = float(lam[-m:].sum())
    checks = {
        "landmark_miss": float(rows_missing(X, L)),
        "whiten_gap": float(np.abs(R @ K @ R.T - np.eye(R.shape[0])).max()),
        "spectrum_gap": abs(float(np.trace(R @ K @ K @ R.T)) - top) / top,
    }
    params = {"L": jnp.asarray(L), "R": jnp.asarray(R.astype(np.float32)),
              "gamma": jnp.float32(gamma)}
    return params, checks


def embed(p, X):
    L = p["L"]
    sq = (jnp.sum(X * X, axis=1, keepdims=True)
          - 2.0 * jnp.dot(X, L.T, precision=HIGHEST)
          + jnp.sum(L * L, axis=1)[None, :])
    K = jnp.exp(-p["gamma"] * jnp.maximum(sq, 0.0))
    return jnp.dot(K, p["R"].T, precision=HIGHEST)


def control_params(config: dict, fit_key, answer_params: dict) -> dict:
    """The reference's Nystrom fit from the fit's landmarks one precision
    below the configuration's float32 (the gram in bfloat16), in the fit's
    place: the upper readings of `whiten_gap` and `spectrum_gap`."""
    gamma = config["gamma_times_d"] / config["d"]
    m = config["m"]
    L = jnp.asarray(np.asarray(answer_params["landmarks"])[0]).astype(jnp.bfloat16)
    sq = (jnp.sum(L * L, axis=1)[:, None] - 2.0 * jnp.dot(L, L.T)
          + jnp.sum(L * L, axis=1)[None, :])
    K = jnp.exp(-gamma * jnp.maximum(sq, 0.0)).astype(jnp.float32)
    lam, V = jnp.linalg.eigh(K)
    R = jax.lax.rsqrt(lam[-m:])[:, None] * V[:, -m:].T
    return {"landmarks": answer_params["landmarks"], "R": np.asarray(R)[None]}
