"""Plain reference of the rcv1-nystrom configuration's embedding.

APNC-Nystrom (arXiv:1311.2334, Section 6, Algorithm 3) on sparse rows:
l landmarks L drawn from the rows, K_LL = kappa(L, L), R = Lambda_m^{-1/2}
V_m^T from the top m eigenpairs of K_LL, y(x) = R kappa(L, x), with kappa
the rbf kernel exp(-gamma ||x - z||^2). The reference is dense: each chunk of
rows is densified on the device from its CSR nonzeros
(harness/rows_csr.py `ref_chunks`, 1,024 rows, 193 MB) and embedded with the
dense formula in plain float32 at HIGHEST.

Which rows became landmarks, and the signs of the eigenvectors, are the
fit's own draws, so the reference verifies them rather than redrawing:

  landmark_miss   landmarks that are not rows of X (must be 0): each dense
                  landmark row's (column ids, values) of its nonzeros must
                  be those of a CSR row, exactly;
  whiten_gap      max |R K_LL R^T - I| with K_LL in float64: R whitens K_LL;
  spectrum_gap    |trace(R K_LL^2 R^T) - sum of the top m eigenvalues of
                  K_LL| / that sum: R spans the top m eigenvectors and no
                  others.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


_INDEX: dict = {}  # id(X) -> (X, set of the rows' (ids, values) bytes)


def _row_key(ids: np.ndarray, vals: np.ndarray) -> tuple:
    keep = vals != 0
    return (np.asarray(ids[keep], np.int32).tobytes(),
            np.asarray(vals[keep], np.float32).tobytes())


def _row_set(X) -> set:
    """Every CSR row of X as (column ids, values) bytes, built once per X."""
    hit = _INDEX.get(id(X))
    if hit is None or hit[0] is not X:
        ptr = X.indptr
        keys = {_row_key(X.indices[ptr[i]:ptr[i + 1]], X.data[ptr[i]:ptr[i + 1]])
                for i in range(X.shape[0])}
        hit = (X, keys)
        _INDEX.clear()
        _INDEX[id(X)] = hit
    return hit[1]


def rows_missing(X, rows: np.ndarray) -> int:
    """How many dense `rows` are not rows of the CSR X (exact equality of
    their nonzeros' column ids and values)."""
    keys = _row_set(X)
    missing = 0
    for row in rows:
        ids = np.flatnonzero(row)
        if _row_key(ids, row[ids]) not in keys:
            missing += 1
    return missing


def _gram64(L: np.ndarray, gamma: float) -> np.ndarray:
    L64 = L.astype(np.float64)
    sq = ((L64 * L64).sum(1)[:, None] - 2.0 * L64 @ L64.T
          + (L64 * L64).sum(1)[None, :])
    return np.exp(-gamma * np.maximum(sq, 0.0))


def reference_params(config: dict, fit_key, answer_params: dict, X):
    """(parameters the reference embeds with, {check name: value})."""
    gamma = config["gamma_times_d"] / config["d"]
    m = config["m"]
    L = np.asarray(answer_params["landmarks"], np.float32)[0]  # q = 1
    R = np.asarray(answer_params["R"], np.float64)[0]
    K = _gram64(L, gamma)
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
