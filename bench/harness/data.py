"""The cell's rows, made once per run from `--seed`.

The mixture is the one `repro.data.synthetic.gaussian_blobs` draws (per
cluster a centre N(0, separation^2) and per-feature scales 1 + anisotropy *
U[0, 1); each row a centre plus scaled Gaussian noise), copied here so that
the yardstick cannot move. Rows are drawn on the device in fixed-size chunks
by one jitted program and copied to host RAM once: the fit then streams them
from RAM, as a deployment streams from RAM or the page cache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_ROWS = 65536
STREAM_FIT = 1  # fold_in tags: one independent row stream per purpose
STREAM_HELD_OUT = 2


def base_key(seed: int) -> jax.Array:
    """Any whole seed, including ones past 32 bits, to one PRNG key."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def mixture_params(key, k: int, d: int, separation: float, anisotropy: float):
    kc, ka = jax.random.split(jax.random.fold_in(key, 0))
    centers = jax.random.normal(kc, (k, d), jnp.float32) * separation
    scales = 1.0 + anisotropy * jax.random.uniform(ka, (k, d), jnp.float32)
    return centers, scales


@partial(jax.jit, static_argnames=("rows",))
def _chunk(key, centers, scales, stream, index, *, rows: int):
    kl, kn = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, stream), index))
    labels = jax.random.randint(kl, (rows,), 0, centers.shape[0])
    noise = jax.random.normal(kn, (rows, centers.shape[1]), jnp.float32)
    return centers[labels] + noise * scales[labels]


def make_rows(seed: int, config: dict, n: int, stream: int = STREAM_FIT) -> np.ndarray:
    """(n, d) float32 host rows of the configuration's mixture."""
    mix = config["mixture"]
    key = base_key(seed)
    centers, scales = mixture_params(
        key, config["k"], config["d"], mix["separation"], mix["anisotropy"]
    )
    X = np.empty((n, config["d"]), np.float32)
    for c, lo in enumerate(range(0, n, CHUNK_ROWS)):
        hi = min(n, lo + CHUNK_ROWS)
        part = _chunk(key, centers, scales, stream, c, rows=CHUNK_ROWS)
        X[lo:hi] = np.asarray(part)[: hi - lo]
    return X
