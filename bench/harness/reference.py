"""The plain reference that decides `correct`: float32 `jax.numpy` at
precision HIGHEST, no kernels, no blocking beyond fixed-size row chunks, and
nothing imported from the program under test.

What a fit returns is checked against the data, as a solve is checked by its
residual. Each configuration's own reference (`configs/<name>.py`) rebuilds
or verifies the embedding the fit used and embeds X itself; this module
holds the shared part: distances, labels, inertia and centroids.

Numbers compared for one fit (each is a gap; smaller is better):

  assign_gap     widest amount by which a returned label's distance lies
                 above the nearest reference distance, over the mean
                 nearest distance. 0 when every label is a reference argmin.
  inertia_gap    |reported inertia - reference cost of the returned labels
                 under the returned centroids| / that cost.
  centroid_gap   max |centroids - mean of the reference embedding over the
                 returned labels|, over the largest such mean entry. At a
                 fixed point (no label changed) the centroids are the
                 returned ones. A fit stopped at the `iters` cap returns
                 centroids one update behind its labels; for it they are the
                 program's next update from its returned state
                 (fit_traffic.next_centroids).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
REF_CHUNK = 32768


def distances(Y, C, discrepancy: str):
    """(rows, k) discrepancies e(y, c): l2 distance or l1 distance."""
    if discrepancy == "l2":
        sq = (jnp.sum(Y * Y, axis=1, keepdims=True)
              - 2.0 * jnp.dot(Y, C.T, precision=HIGHEST)
              + jnp.sum(C * C, axis=1)[None, :])
        return jnp.sqrt(jnp.maximum(sq, 0.0))
    if discrepancy == "l1":
        return jax.lax.map(lambda c: jnp.sum(jnp.abs(Y - c[None, :]), axis=1), C).T
    raise ValueError(f"unknown discrepancy {discrepancy!r}")


@partial(jax.jit, static_argnames=("embed", "discrepancy"))
def _score_chunk(embed, discrepancy, ref_params, X, C, L, valid):
    Y = embed(ref_params, X)
    D = distances(Y, C, discrepancy)
    dmin = jnp.min(D, axis=1)
    dL = jnp.take_along_axis(D, L[:, None], axis=1)[:, 0]
    onehot = (L[:, None] == jnp.arange(C.shape[0])[None, :]).astype(jnp.float32)
    onehot = onehot * valid[:, None]
    Z = jnp.dot(onehot.T, Y, precision=HIGHEST)
    return dmin * valid, dL * valid, Z, jnp.sum(onehot, axis=0)


@dataclasses.dataclass
class FitAnswer:
    """What one fit returned, copied to the host when it finished."""

    index: int  # position of the fit in the window
    key_seed: int  # the fit's key is data.base_key(seed) folded with this
    params: dict  # numpy leaves of the fitted embedding parameters
    centroids: np.ndarray  # (k, m)
    labels: np.ndarray  # (n,) int32
    inertia: float
    n_iter: int
    iters_cap: int
    model: Any = None  # the fitted ClusterModel, on the device

    @property
    def converged(self) -> bool:
        return self.n_iter < self.iters_cap


def score_rows(chunks: Iterable[np.ndarray], chunk_rows: int, embed: Callable,
               ref_params: Any, centroids: np.ndarray, labels: np.ndarray,
               discrepancy: str) -> dict:
    """One pass over the rows, given in order as dense float32 chunks of at
    most `chunk_rows` rows (a row format's `ref_chunks`), each padded to
    `chunk_rows`: nearest reference distances, the returned labels'
    distances, and per-cluster sums of the reference embedding over the
    returned labels (float64 on the host)."""
    k = centroids.shape[0]
    C = jnp.asarray(centroids, jnp.float32)
    sum_dmin = sum_dL = 0.0
    worst = 0.0
    Z = g = None
    lo = 0
    for part in chunks:
        rows = part.shape[0]
        lab = labels[lo:lo + rows].astype(np.int32)
        valid = np.ones(chunk_rows, np.float32)
        if rows < chunk_rows:
            part = np.pad(part, ((0, chunk_rows - rows), (0, 0)))
            lab = np.pad(lab, (0, chunk_rows - rows))
            valid[rows:] = 0.0
        dmin, dL, Zc, gc = _score_chunk(
            embed, discrepancy, ref_params, jnp.asarray(part), C,
            jnp.asarray(lab), jnp.asarray(valid),
        )
        dmin = np.asarray(dmin, np.float64)
        dL = np.asarray(dL, np.float64)
        sum_dmin += float(dmin.sum())
        sum_dL += float(dL.sum())
        worst = max(worst, float(np.max(dL - dmin)))
        Zc = np.asarray(Zc, np.float64)
        gc = np.asarray(gc, np.float64)
        Z = Zc if Z is None else Z + Zc
        g = gc if g is None else g + gc
        lo += rows
    assert Z is not None and g is not None and Z.shape[0] == k
    if lo != labels.shape[0]:
        raise ValueError(f"the chunks hold {lo} rows, the labels {labels.shape[0]}")
    return {"sum_dmin": sum_dmin, "sum_dL": sum_dL, "worst": worst, "Z": Z,
            "g": g, "n": lo}


def fit_numbers(answer: FitAnswer, scored: dict,
                next_centroids: np.ndarray | None = None) -> dict:
    """The compared numbers of one fit from its scored pass. A fit stopped
    at the cap passes the program's next update as `next_centroids`."""
    mean_dmin = scored["sum_dmin"] / scored["n"]
    out = {
        "assign_gap": scored["worst"] / mean_dmin,
        "inertia_gap": abs(answer.inertia - scored["sum_dL"]) / scored["sum_dL"],
    }
    C = answer.centroids if next_centroids is None else next_centroids
    g = scored["g"]
    full = g > 0
    means = scored["Z"][full] / g[full][:, None]
    diff = np.abs(np.asarray(C, np.float64)[full] - means)
    out["centroid_gap"] = float(diff.max() / np.abs(means).max())
    return out


def nearest_table(X: np.ndarray, embed: Callable, ref_params: Any,
                  centroids: np.ndarray, discrepancy: str) -> np.ndarray:
    """(rows, k) float32 reference distances of every row of X: the serving
    check looks up each answered request's row here."""
    C = jnp.asarray(centroids, jnp.float32)
    fn = jax.jit(lambda p, x: distances(embed(p, x), C, discrepancy))
    out = np.empty((X.shape[0], C.shape[0]), np.float32)
    for lo in range(0, X.shape[0], REF_CHUNK):
        rows = min(REF_CHUNK, X.shape[0] - lo)
        part = X[lo:lo + rows]
        if rows < REF_CHUNK:
            part = np.pad(part, ((0, REF_CHUNK - rows), (0, 0)))
        out[lo:lo + rows] = np.asarray(fn(ref_params, jnp.asarray(part)))[:rows]
    return out
