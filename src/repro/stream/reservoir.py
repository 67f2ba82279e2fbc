"""Reservoir sampling over a block stream (Vitter's Algorithm R, block form).

Landmark selection (Algorithm 3/4's map phase) and k-means++ seeding both need
a uniform row sample, but the stream's n may be unknown up front and the data
never co-resides. A reservoir gives an exactly-uniform `size`-row sample in one
pass with O(size * d) memory, independent of n.

Over a CSR store the reservoir holds sparse rows and returns them as one
host `CSRBlock`, with the same random draws as over the store's dense twin:
both keep the same row positions.
"""
from __future__ import annotations

import numpy as np

from repro.stream.blockstore import BlockStore, CSRBlock, CSRBlockStore


def reservoir_sample(store: BlockStore, size: int, *,
                     seed: int = 0) -> np.ndarray | CSRBlock:
    """One pass over `store`; returns (min(size, n), d) rows, uniformly without
    replacement over all rows seen. Deterministic given seed. A CSR store
    gives a host `CSRBlock` of those rows."""
    rng = np.random.default_rng(seed)
    csr = isinstance(store, CSRBlockStore)
    keep = min(size, store.n)
    # CSR: each slot holds one row's (column ids, values)
    reservoir = [None] * keep if csr else np.zeros((keep, store.d), dtype=store.dtype)
    seen = 0
    for b in range(store.num_blocks):
        blk = store.get(b)
        rows = blk.shape[0]
        take = min(max(size - seen, 0), rows)
        # replace phase: row t (0-based global) enters with prob size/(t+1)
        t = np.arange(seen + take, seen + rows)
        accept = rng.random(rows - take) < size / (t + 1)
        idx = np.nonzero(accept)[0]
        slots = rng.integers(0, size, size=idx.size) if idx.size else idx
        if csr:
            # fill phase, then replacements in row order: a later row
            # overwrites an earlier one landing in the same slot
            for s, r in zip(np.concatenate([np.arange(seen, seen + take), slots]),
                            np.concatenate([np.arange(take), take + idx])):
                reservoir[s] = blk.row(int(r))
        else:
            if take:  # fill phase: first `size` rows go straight in
                reservoir[seen:seen + take] = blk[:take]
            if idx.size:
                # later rows must overwrite earlier ones landing in the same slot
                reservoir[slots] = blk[take + idx]
        seen += rows
    if not csr:
        return reservoir
    lens = np.array([len(c) for c, _ in reservoir], np.int64)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    cols = np.concatenate([c for c, _ in reservoir]) if keep else np.zeros(0, np.int32)
    vals = np.concatenate([v for _, v in reservoir]) if keep else np.zeros(0, np.float32)
    return CSRBlock.from_rows(indptr, cols, vals, d=store.d)
