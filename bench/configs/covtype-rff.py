"""Plain reference of the covtype-rff configuration's embedding.

Random Fourier features (Rahimi and Recht, 2007) for the rbf kernel
exp(-gamma ||x - z||^2): z(x) = sqrt(1/h) [cos(x W), sin(x W)] with
W ~ N(0, 2 gamma I) of shape (d, h), h = `m` of the configuration, so the
embedding has 2h columns.

W is a draw from the fit's key, by the convention the estimator documents
for its phase 1 (`KernelKMeans`: key -> split into 3, the second is the
embedding fit's key; the member splits that into 2 and draws from the
first). The reference makes its own draw that way and embeds with it: it
never reads the W that the fit returned, it only compares it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def reference_params(config: dict, fit_key, answer_params: dict, X: np.ndarray | None):
    """(parameters the reference embeds with, {check name: value})."""
    d, h = config["d"], config["m"]
    gamma = config["gamma_times_d"] / d
    _, k_fit, _ = jax.random.split(fit_key, 3)
    kw, _ = jax.random.split(k_fit)
    W = jax.random.normal(kw, (d, h), jnp.float32) * jnp.sqrt(2.0 * gamma)
    W_np = np.asarray(W, np.float64)
    got = np.asarray(answer_params["W"], np.float64)
    gap = (float(np.abs(got - W_np).max() / np.abs(W_np).max())
           if got.shape == W_np.shape else float("inf"))
    return {"W": W}, {"param_gap": gap}


def embed(p, X):
    proj = jnp.dot(X, p["W"], precision=HIGHEST)
    scale = 1.0 / np.sqrt(p["W"].shape[1])
    return scale * jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=1)


def control_params(config: dict, fit_key, answer_params: dict) -> dict:
    """The reference's own W one precision below the configuration's float32
    (rounded to bfloat16), in the fit's place: the upper reading of
    `param_gap`."""
    params, _ = reference_params(config, fit_key, answer_params, None)
    W = params["W"].astype(jnp.bfloat16).astype(jnp.float32)
    return {"W": np.asarray(W)}
