"""Row format "csr": sparse rows held as CSR on the host, never densified for
the program. What a row format provides is set out in rows_mixture.py.

The rows are a seeded sparse topic mixture in the shape of a bag-of-words
TF-IDF corpus (the configuration's `csr` group gives the numbers):

  * a background term distribution Zipf(`zipf`) over the d term ids (the
    ranks put on the ids by a seeded permutation);
  * k topics, each boosting `topic_terms` ids drawn uniformly;
  * each row draws its topic uniformly, and its target count of distinct
    terms log-normally with mean `nnz_mean` and log-sd `nnz_sigma`, clipped
    to [`nnz_min`, `nnz_max`]; it then draws terms, each from its topic with
    probability `topic_share` and from the background otherwise, and keeps
    the first distinct ones up to its target;
  * a term's count among the row's draws is its tf; values are tf times the
    smooth idf log((1 + n) / (1 + df)) + 1 over the rows made, and each row
    is scaled to unit l2 norm. Column ids are sorted and distinct in a row.

`program_input` gives the program a CSR `BlockStore` (`BlockStore.from_csr`)
and nothing else; `ref_chunks` densifies chunks of REF_CHUNK_ROWS rows on the
device, for the plain reference only.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import data

REF_CHUNK_ROWS = 1024  # 193 MB dense at d = 47,236
DRAW_FACTOR = 1.5  # draws per row: 1.5 x its target distinct terms, + 8
DRAW_EXTRA = 8


@dataclasses.dataclass
class CSRRows:
    """Host CSR rows: `indptr` (n + 1), `indices` (int32), `data` (float32).
    `capacity` is the nonzero slots of the program's full store, recorded by
    `program_input`, so that a store of the first rows (`head`) is given the
    same compiled block shape."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    shape: tuple
    capacity: int | None = None

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def _rng(seed: int, stream: int) -> np.random.Generator:
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17, stream])


def make(seed: int, config: dict, n: int, stream: int = data.STREAM_FIT) -> CSRRows:
    g = config["csr"]
    d, k = config["d"], config["k"]
    # the corpus' terms and topics depend on the seed alone, the rows also
    # on the stream
    vocab = _rng(seed, 0)
    ids_by_rank = vocab.permutation(d).astype(np.int32)
    zipf = 1.0 / np.arange(1, d + 1, dtype=np.float64) ** g["zipf"]
    zipf_cdf = np.cumsum(zipf / zipf.sum())
    topics = np.argsort(vocab.random((k, d)), axis=1)[:, :g["topic_terms"]]
    topics = topics.astype(np.int32)

    rng = _rng(seed, stream)
    topic = rng.integers(0, k, n)
    mu = np.log(g["nnz_mean"]) - 0.5 * g["nnz_sigma"] ** 2
    target = np.clip(np.rint(rng.lognormal(mu, g["nnz_sigma"], n)),
                     g["nnz_min"], g["nnz_max"]).astype(np.int64)
    draws = np.ceil(DRAW_FACTOR * target).astype(np.int64) + DRAW_EXTRA
    row = np.repeat(np.arange(n, dtype=np.int64), draws)
    from_topic = rng.random(row.shape[0]) < g["topic_share"]
    ids = np.empty(row.shape[0], np.int32)
    nt = int(from_topic.sum())
    ids[from_topic] = topics[topic[row[from_topic]],
                             rng.integers(0, g["topic_terms"], nt)]
    rank = np.minimum(np.searchsorted(zipf_cdf, rng.random(row.shape[0] - nt)), d - 1)
    ids[~from_topic] = ids_by_rank[rank]

    # distinct (row, id) pairs: first draw of each and its count (the tf)
    key = row * d + ids
    order = np.argsort(key, kind="stable")
    sk = key[order]
    start = np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]])
    tf = np.diff(np.r_[start, sk.shape[0]]).astype(np.float32)
    first = order[start]  # draw index of each distinct pair, sorted by key
    urow = sk[start] // d
    # keep a row's first `target` distinct terms in draw order
    by_draw = np.argsort(first)
    row_start = np.searchsorted(urow[by_draw], np.arange(n))
    rank_in_row = np.empty(first.shape[0], np.int64)
    rank_in_row[by_draw] = np.arange(first.shape[0]) - row_start[urow[by_draw]]
    keep = rank_in_row < target[urow]
    urow, uid, tf = urow[keep], (sk[start] % d)[keep].astype(np.int32), tf[keep]

    df = np.bincount(uid, minlength=d).astype(np.float64)
    idf = (np.log((1.0 + n) / (1.0 + df)) + 1.0).astype(np.float32)
    vals = tf * idf[uid]
    norm = np.sqrt(np.bincount(urow, weights=vals.astype(np.float64) ** 2, minlength=n))
    vals = (vals / norm[urow]).astype(np.float32)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(urow, minlength=n), out=indptr[1:])
    return CSRRows(indptr, uid, vals, (n, d))


def head(rows: CSRRows, n: int) -> CSRRows:
    end = int(rows.indptr[n])
    return CSRRows(rows.indptr[:n + 1], rows.indices[:end], rows.data[:end],
                   (n, rows.shape[1]), rows.capacity)


def program_input(rows: CSRRows, block_rows: int, hold: str, devices: list):
    if hold != "host":
        raise ValueError(f"csr rows are held on the host only, not {hold!r}")
    from repro.stream.blockstore import BlockStore

    store = BlockStore.from_csr(rows, block_rows, capacity=rows.capacity)
    if rows.capacity is None:
        rows.capacity = store.nnz_capacity
    return store


def _slots(nnz: int) -> int:
    return max(128, 1 << max(nnz - 1, 0).bit_length())


@partial(jax.jit, static_argnames=("d",))
def _densify(vals, cols, rows, *, d: int):
    """(REF_CHUNK_ROWS, d) float32 from one chunk's nonzeros; slots past the
    chunk's nonzeros carry row REF_CHUNK_ROWS and are dropped."""
    X = jnp.zeros((REF_CHUNK_ROWS, d), jnp.float32)
    return X.at[rows, cols].set(vals, mode="drop")


def ref_chunks(rows: CSRRows):
    """Dense float32 chunks of at most REF_CHUNK_ROWS rows, in order, each
    densified on the device from its nonzeros (`.at[rows, cols].set`)."""
    n, d = rows.shape
    for lo in range(0, n, REF_CHUNK_ROWS):
        hi = min(n, lo + REF_CHUNK_ROWS)
        a, b = int(rows.indptr[lo]), int(rows.indptr[hi])
        slots = _slots(b - a)
        vals = np.zeros(slots, np.float32)
        cols = np.zeros(slots, np.int32)
        rid = np.full(slots, REF_CHUNK_ROWS, np.int32)
        vals[:b - a] = rows.data[a:b]
        cols[:b - a] = rows.indices[a:b]
        rid[:b - a] = np.repeat(np.arange(hi - lo, dtype=np.int32),
                                np.diff(rows.indptr[lo:hi + 1]))
        X = _densify(jnp.asarray(vals), jnp.asarray(cols), jnp.asarray(rid), d=d)
        yield X if hi - lo == REF_CHUNK_ROWS else X[:hi - lo]
