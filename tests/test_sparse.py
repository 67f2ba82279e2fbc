"""Sparse rows (CSR) through the normal fit path, on the CPU at a small size.

n = 3,000 rows, d = 5,000 columns, ~20 nonzeros a row, unit l2 norm, as a
TF-IDF corpus is held: the CSR store and its blocks, the reservoir over them,
the sparse embed against the dense one, and a whole stream fit on CSR against
the same fit on the densified rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sp = pytest.importorskip("scipy.sparse")

from repro import obs  # noqa: E402
from repro.api import KernelKMeans  # noqa: E402
from repro.core.apnc import embed  # noqa: E402
from repro.core.kernels_fn import Kernel  # noqa: E402
from repro.embed.apnc import fit_nystrom  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serving import ModelRegistry, ServingTier  # noqa: E402
from repro.stream.blockstore import (  # noqa: E402
    LANE, SLOT_TILE, BlockStore, CSRBlock, CSRBlockStore, bucket,
)
from repro.stream.engine import block_nbytes  # noqa: E402
from repro.stream.reservoir import reservoir_sample  # noqa: E402

N, D, NNZ = 3000, 5000, 20
KW = dict(kernel="rbf", kernel_params={"gamma": 0.5}, l=64, m=32, iters=10,
          block_rows=512, landmark_sample=1024, seed_sample=256)


def _corpus(n=N, d=D, nnz=NNZ, seed=0, empty=()):
    """Rows with ~nnz distinct sorted ids each, positive values, unit norm;
    the rows in `empty` hold no nonzeros."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.poisson(nnz, n), 1, d)
    lens[list(empty)] = 0
    cols = [np.sort(rng.choice(d, k, replace=False)) for k in lens]
    vals = [rng.random(k).astype(np.float32) + 0.1 for k in lens]
    vals = [v / np.linalg.norm(v) if v.size else v for v in vals]
    indptr = np.concatenate([[0], np.cumsum(lens)])
    X = sp.csr_matrix((np.concatenate(vals), np.concatenate(cols), indptr),
                      shape=(n, d), dtype=np.float32)
    return X


@pytest.fixture(scope="module")
def corpus():
    return _corpus(empty=(0, 7, 511, 512, 2999))


# ----------------------------------------------------------- the CSR store


def test_csr_store_round_trips_with_empty_rows_and_a_short_last_block(corpus):
    store = BlockStore.from_csr(corpus, 512)
    assert isinstance(store, CSRBlockStore)
    assert store.num_blocks == 6 and store.rows_of(5) == N - 5 * 512  # short
    dense = corpus.toarray()
    assert np.array_equal(store.materialize(), dense)
    for i in range(store.num_blocks):
        blk = store.get(i)
        lo = store.row_offset(i)
        assert blk.shape == (store.rows_of(i), D)
        assert blk.slots == store.nnz_capacity
        assert blk.nnz == int(corpus.indptr[lo + blk.shape[0]] - corpus.indptr[lo])
        assert np.array_equal(blk.toarray(), dense[lo:lo + blk.shape[0]])
    assert store.get(0).indptr[1] == 0  # row 0 is empty


def test_capacity_is_the_largest_block_rounded_to_a_lane(corpus):
    store = BlockStore.from_csr(corpus, 512)
    per_block = np.diff(corpus.indptr[np.minimum(np.arange(0, 7) * 512, N)])
    largest = int(per_block.max())
    assert store.nnz_capacity == -(-largest // SLOT_TILE) * SLOT_TILE
    assert store.nnz_capacity % LANE == 0 and store.nnz_capacity >= largest
    # a caller may ask for more, never for less than the largest block
    assert BlockStore.from_csr(corpus, 512, capacity=store.nnz_capacity + LANE) \
        .get(0).slots == store.nnz_capacity + LANE
    with pytest.raises(ValueError, match="largest block"):
        BlockStore.from_csr(corpus, 512, capacity=largest - 1)


def test_padding_slots_hold_zero_at_a_valid_column(corpus):
    store = BlockStore.from_csr(corpus, 512)
    for i in range(store.num_blocks):
        blk = store.get(i)
        assert blk.indptr[-1] == blk.nnz
        assert np.all(blk.values[blk.nnz:] == 0)
        assert np.all((blk.cols >= 0) & (blk.cols < D))
    assert block_nbytes(store.get(0)) == store.get(0).nbytes == (
        4 * 2 * store.nnz_capacity + 4 * 513)


def test_shard_keeps_the_format_and_the_capacity(corpus):
    store = BlockStore.from_csr(corpus, 512)
    part = store.shard(1, 2)
    assert isinstance(part, CSRBlockStore)
    assert part.nnz_capacity == store.nnz_capacity
    assert [part.block_id(i) for i in range(part.num_blocks)] == [1, 3, 5]
    assert np.array_equal(part.get(2).toarray(), store.get(5).toarray())


def test_block_takes_a_leading_row_slice_on_host_and_device(corpus):
    blk = BlockStore.from_csr(corpus, 512).get(1)
    want = blk.toarray()[:200]
    host = blk[:200]
    assert host.shape == (200, D) and host.slots == bucket(host.nnz)
    assert np.array_equal(host.toarray(), want)
    dev = jax.device_put(blk)[:200]
    assert dev.slots == host.slots and dev.nnz == host.nnz
    assert np.array_equal(dev.toarray(), want)
    with pytest.raises(TypeError):
        blk[3:10]


def test_reservoir_over_csr_picks_the_dense_twins_rows(corpus):
    csr = BlockStore.from_csr(corpus, 512)
    dense = BlockStore.from_array(corpus.toarray(), 512)
    for size, seed in ((1024, 3), (2 * N, 4), (100, 2**31 + 5)):
        got = reservoir_sample(csr, size, seed=seed)
        assert isinstance(got, CSRBlock) and got.slots == bucket(got.nnz)
        assert np.array_equal(got.toarray(), reservoir_sample(dense, size, seed=seed))


# ------------------------------------------------------------- the embed


@pytest.mark.parametrize("kernel", [Kernel("rbf", gamma=0.5), Kernel("linear"),
                                    Kernel("poly", degree=3, coef0=1.0)])
def test_from_cross_is_the_gram(kernel):
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((40, 30)), jnp.float32) / 5
    Z = jnp.asarray(rng.standard_normal((12, 30)), jnp.float32) / 5
    cross = jnp.dot(X, Z.T, precision=jax.lax.Precision.HIGHEST)
    got = kernel.from_cross(cross, jnp.sum(X * X, 1, keepdims=True),
                            jnp.sum(Z * Z, 1)[None, :])
    np.testing.assert_allclose(got, kernel.gram(X, Z), rtol=1e-6, atol=1e-6)


def test_sparse_embed_equals_the_dense_embed(corpus):
    """Both in float32: the cross term is summed over a row's ~20 nonzeros in
    another order than the dense dot over 5,000 columns, a few ulps of each
    kernel value (~1e-7); R = Lambda^-1/2 V^T multiplies it by at most
    1/sqrt of the smallest kept eigenvalue (~30 here), so 2e-5 of Y's
    largest entry bounds it with room."""
    store = BlockStore.from_csr(corpus, 512)
    dense = corpus.toarray()
    params = fit_nystrom(jax.random.PRNGKey(0), jnp.asarray(dense[:1024]),
                         Kernel("rbf", gamma=0.5), l=64, m=32)
    for i in (0, store.num_blocks - 1):  # empty rows; the short last block
        blk = store.get(i)
        assert blk.slots > blk.nnz  # padding is present
        lo = store.row_offset(i)
        want = np.asarray(embed(jnp.asarray(dense[lo:lo + blk.shape[0]]), params))
        for x in (blk, jax.device_put(blk)):
            got = np.asarray(ops.sparse_embed(x, params))
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=2e-5 * np.abs(want).max())
    # the block map routes a CSR block to the same embed
    got = np.asarray(ops.embed_block_map(BlockStore.from_csr(corpus[:300], 300).get(0),
                                         params))
    np.testing.assert_allclose(got, np.asarray(embed(jnp.asarray(dense[:300]), params)),
                               rtol=0, atol=2e-5 * np.abs(got).max())


def test_padding_slots_do_not_change_the_embed(corpus):
    params = fit_nystrom(jax.random.PRNGKey(0), jnp.asarray(corpus[:1024].toarray()),
                         Kernel("rbf", gamma=0.5), l=64, m=32)
    small = BlockStore.from_csr(corpus, 512).get(2)
    big = BlockStore.from_csr(corpus, 512, capacity=4 * small.slots).get(2)
    assert np.array_equal(np.asarray(ops.sparse_embed(small, params)),
                          np.asarray(ops.sparse_embed(big, params)))


# ------------------------------------------------------------- the fit


@pytest.fixture(scope="module")
def twin_fits(corpus):
    key = jax.random.PRNGKey(3)
    csr = KernelKMeans(8, backend="stream", **KW).fit(corpus, key=key)
    dense = KernelKMeans(8, backend="stream", **KW).fit(
        BlockStore.from_array(corpus.toarray(), 512), key=key)
    return csr, dense


def test_csr_fit_has_the_dense_twins_landmarks(twin_fits):
    csr, dense = twin_fits
    assert csr.backend_ == "stream"
    assert np.array_equal(np.asarray(csr.model_.params.landmarks),
                          np.asarray(dense.model_.params.landmarks))


def test_csr_fit_agrees_with_the_dense_twin(twin_fits, corpus):
    """Labels agree except where two centroids are within float32 rounding
    of a row (a tie): each disagreeing row's two distances, in the dense
    twin's embedding, differ by under 1e-4 of the mean distance. Inertia
    agrees within 1e-5 relative (the embeddings differ by summation order)."""
    csr, dense = twin_fits
    assert abs(csr.inertia_ - dense.inertia_) <= 1e-5 * dense.inertia_
    off = np.flatnonzero(csr.labels_ != dense.labels_)
    assert off.size <= 0.01 * N
    if off.size:
        Y = np.asarray(embed(jnp.asarray(corpus[off].toarray()), dense.model_.params))
        C = np.asarray(dense.model_.centroids)
        dist = np.linalg.norm(Y[:, None, :] - C[None], axis=-1)
        rows = np.arange(off.size)
        gap = np.abs(dist[rows, csr.labels_[off]] - dist[rows, dense.labels_[off]])
        assert gap.max() <= 1e-4 * dist.min(1).mean()


def test_self_tuned_rbf_on_a_csr_sample_is_the_dense_twins(corpus):
    """The self-tuned gamma reads the same 512 rows of the sample; only the
    order of its float32 sum over their pairs differs."""
    est = KernelKMeans(8, kernel="rbf", backend="stream", random_state=4)
    sample = reservoir_sample(BlockStore.from_csr(corpus, 512), 1024, seed=5)
    got = est._resolve_kernel(sample)
    want = est._resolve_kernel(jnp.asarray(sample.toarray()))
    assert got.name == want.name == "rbf"
    assert got.gamma == pytest.approx(want.gamma, rel=1e-5)


def test_auto_on_csr_fits_on_the_stream_backend(corpus):
    est = KernelKMeans(8, backend="auto", **dict(KW, iters=2))
    est.fit(BlockStore.from_csr(corpus, 512), key=jax.random.PRNGKey(1))
    assert est.backend_ == "stream" and est.labels_.shape == (N,)
    assert set(np.unique(est.labels_)) <= set(range(8))


def test_csr_counters_count_what_was_stepped(corpus):
    store = BlockStore.from_csr(corpus, 512)
    est = KernelKMeans(8, backend="stream", **dict(KW, iters=3))
    with obs.scoped("sparse.") as m:
        est.fit(store, key=jax.random.PRNGKey(2))
    passes = est.n_iter_ + 1  # every Lloyd iteration and the final assignment
    assert m["sparse.blocks"] == passes * store.num_blocks
    assert m["sparse.nnz"] == passes * corpus.nnz
    assert m["sparse.slots"] == passes * store.num_blocks * store.nnz_capacity


def test_csr_fit_records_the_sparse_step_span(corpus):
    obs.enable_tracing()
    obs.clear_trace()
    try:
        KernelKMeans(8, backend="stream", **dict(KW, iters=1)).fit(
            corpus, key=jax.random.PRNGKey(2))
        names = [s.name for s in obs.TRACER.spans()]
    finally:
        obs.disable_tracing()
    assert names.count("lloyd.sparse_step") == 2 * 6  # one iteration + final
    assert "lloyd.fused_step" not in names


@pytest.mark.parametrize("backend", ["local", "shard_map", "stream_shard",
                                     "minibatch"])
def test_other_backends_raise_on_csr(corpus, backend):
    with pytest.raises(NotImplementedError, match="CSR"):
        KernelKMeans(8, backend=backend, **KW).fit(corpus)


@pytest.mark.parametrize("method", ["rff", "sd", "tensorsketch"])
def test_other_members_raise_on_csr(corpus, method):
    with pytest.raises(NotImplementedError, match="CSR"):
        KernelKMeans(8, method=method, backend="stream", **KW).fit(corpus)


def test_sweep_partial_fit_and_serving_raise_on_csr(corpus, twin_fits):
    csr, _ = twin_fits
    with pytest.raises(NotImplementedError, match="CSR"):
        KernelKMeans(8, backend="stream", **KW).sweep(corpus, [4, 8])
    with pytest.raises(NotImplementedError, match="CSR"):
        KernelKMeans(8, **KW).partial_fit(corpus[:512])
    store = BlockStore.from_csr(corpus, 512)
    for op in (csr.predict, csr.transform, csr.score):
        for x in (corpus, store):
            with pytest.raises(NotImplementedError, match="CSR"):
                op(x)
    reg = ModelRegistry(max_batch=4)
    reg.register("m", csr.model_)
    with ServingTier(reg) as tier:
        with pytest.raises(NotImplementedError, match="CSR"):
            tier.submit(0, corpus[0], "m")
