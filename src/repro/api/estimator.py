"""`KernelKMeans`: the unified estimator over every execution regime.

The paper's whole point is ONE embedding *family* definition (Section 4) that
makes every execution strategy share the same math — for every member of the
family, not just APNC (see repro.embed: nystrom/sd/rff/tensorsketch ship
registered, `register_embedding` adds more). This facade makes the API match:
one estimator with the full lifecycle

    fit(X_or_BlockStore) / partial_fit / predict / transform / score / save / load

dispatching to interchangeable backends ("local", "shard_map", "stream",
"minibatch"; "auto" picks by input type, data size and mesh availability) and
producing one canonical `ClusterModel` artifact regardless of backend.

Phase 1 (coefficient fit + seeding) runs HERE, identically for every backend:
a reservoir sample over the blocked view of the data selects landmarks, fits
(R, L), and seeds k-means++ restarts — so backends differ only in how they run
the Lloyd iterations, and `local` and `stream` reach the identical fixed point
from the identical init (asserted in tests/test_api.py).
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.backends import FitContext
from repro.api.model import ClusterModel, FitMeta
from repro.api.registry import get_backend, get_embedding, resolve_kernel
from repro.core.kernels_fn import Kernel, self_tuned_rbf
from repro.core.lloyd import block_cost, centroid_update, kmeanspp_init
from repro.kernels import ops
from repro.policy import ComputePolicy
from repro.stream.blockstore import BlockStore, CSRBlock, is_csr
from repro.stream.reservoir import reservoir_sample

Array = jax.Array

# backend="auto": in-memory arrays at or beyond this many rows are clustered
# out-of-core (wrapped in a BlockStore) instead of fully embedded on device.
AUTO_STREAM_ROWS = 2_000_000


def _reject_csr(X, op: str) -> None:
    if is_csr(X):
        raise NotImplementedError(
            f"{op} of CSR rows is not supported: CSR input is fitted by "
            "KernelKMeans(method='nystrom', backend='stream').fit only")


def phase1_keys(key: Array) -> tuple[Array, Array, Array]:
    """The facade's canonical phase-1 PRNG split: (k_sample, k_fit, k_seed).

    Independent streams for WHICH rows the reservoir keeps, the embedding
    fit's draws, and the k-means++ seeding — one key must not feed two draws
    (reservoir selection would correlate with the fit). Anything that mirrors
    the facade's seeding (benchmarks/stream_bench.py's hand-rolled driver)
    must take its keys from HERE, so a future seeding change cannot silently
    desynchronize label-identity baselines.

    Args:
        key: The fit's root PRNG key.

    Returns:
        The (k_sample, k_fit, k_seed) subkey triple.
    """
    k_sample, k_fit, k_seed = jax.random.split(key, 3)
    return k_sample, k_fit, k_seed


class KernelKMeans:
    """Kernel k-means via explicit embeddings (the paper's embed-and-conquer),
    scikit-learn-shaped, with pluggable execution backends and a pluggable
    embedding family (repro.embed).

    Parameters mirror `APNCConfig` (paper Section 9) plus the execution axes:

    k:               number of clusters.
    kernel:          registered kernel name ("rbf"|"poly"|"tanh"|"linear") or a
                     `Kernel` instance. With kernel="rbf" and no gamma in
                     kernel_params, sigma is self-tuned on the landmark sample.
    kernel_params:   keyword params for a string kernel (gamma, degree, ...).
    method:          registered embedding family member (see repro.embed):
                     "nystrom" (APNC-Nys, l2), "sd" (APNC-SD, l1), "rff"
                     (random Fourier features, rbf kernels), "tensorsketch"
                     (polynomial kernels), or anything register_embedding'd.
    backend:         "local" | "shard_map" | "stream" | "stream_shard" |
                     "minibatch" | "auto". auto -> "stream_shard" for a
                     BlockStore input plus a mesh with >1 data-axis device,
                     "stream" for any other BlockStore input, "shard_map" when
                     a mesh was given, "stream" for arrays with >=
                     AUTO_STREAM_ROWS rows, else "local". CSR input (a
                     scipy.sparse matrix or a CSR BlockStore) is fitted by
                     "stream" alone, with method "nystrom"; auto picks it.
    l, m, t, q:      landmark count, embedding dim per block, SD subset size,
                     ensemble blocks — as in the paper. Landmark-free members
                     (rff, tensorsketch) read only m.
    iters, n_init:   Lloyd cap and k-means++ restarts (best inertia wins).
    decay, epochs:   minibatch backend: sufficient-stat decay and stream passes.
    block_rows:      blocking used when wrapping an in-memory array.
    landmark_sample: reservoir size for landmark/coefficient fitting.
    seed_sample:     rows of the landmark sample used for k-means++ seeding.
    policy:          `ComputePolicy` (pallas routing, precision, prefetch).
    mesh:            jax Mesh for the shard_map / stream_shard backends.
    scheduler:       stream_shard pass scheduling: "lockstep" (fixed
                     block->device placement, on-mesh reduce) or "pool" (the
                     fault-tolerant repro.pool control plane: leased
                     reassignable block tasks, straggler stealing, identical
                     labels — see DESIGN.md section 14).
    random_state:    seed used when fit() is not given an explicit key.

    After fit: `model_` (the ClusterModel artifact), `labels_`, `inertia_`,
    `n_iter_`, `kernel_` (the resolved Kernel), `backend_` (the backend that
    actually ran), and `fit_report_` (a `repro.obs.FitReport`: phase
    wall-times, the per-iteration inertia trajectory, pass counts, bytes
    streamed — also attached to `model_.report`).

    Example:
        >>> import numpy as np
        >>> from repro.api import KernelKMeans
        >>> X = np.random.default_rng(0).normal(size=(512, 8)).astype("float32")
        >>> est = KernelKMeans(4, l=32, m=16, backend="local").fit(X)
        >>> sorted(set(est.predict(X[:10]))) <= [0, 1, 2, 3]
        True
    """

    def __init__(
        self,
        k: int,
        *,
        kernel: str | Kernel = "rbf",
        kernel_params: dict | None = None,
        method: str = "nystrom",
        backend: str = "auto",
        l: int = 300,
        m: int = 200,
        t: int | None = None,
        q: int = 1,
        iters: int = 20,
        n_init: int = 1,
        decay: float = 0.9,
        epochs: int = 1,
        block_rows: int = 4096,
        landmark_sample: int = 4096,
        seed_sample: int = 1024,
        policy: ComputePolicy | None = None,
        mesh: Any | None = None,
        scheduler: str = "lockstep",
        random_state: int = 0,
    ):
        self.k = int(k)
        self.kernel = kernel
        self.kernel_params = dict(kernel_params or {})
        self.method = method
        self.backend = backend
        self.l, self.m, self.t, self.q = l, m, t, q
        self.iters, self.n_init = iters, n_init
        self.decay, self.epochs = decay, epochs
        self.block_rows = block_rows
        self.landmark_sample = landmark_sample
        self.seed_sample = seed_sample
        self.policy = policy if policy is not None else ComputePolicy()
        self.mesh = mesh
        self.scheduler = scheduler
        self.random_state = random_state

        self.model_: ClusterModel | None = None
        self.labels_: np.ndarray | None = None
        self.inertia_: float | None = None
        self.n_iter_: int | None = None
        self.kernel_: Kernel | None = None
        self.backend_: str | None = None
        self.fit_report_: obs.FitReport | None = None
        self._pf_state: tuple[Array, Array, int] | None = None  # (Z, g, rows)
        self._phases: dict[str, float] = {}  # phase1/backend wall times

    # ------------------------------------------------------------- dispatch

    def _choose_backend(self, X) -> str:
        if self.backend != "auto":
            return self.backend
        if is_csr(X):
            return "stream"  # the one backend with a sparse block route
        if isinstance(X, BlockStore):
            # Blocked input + a mesh with >1 data-axis device -> shard the
            # stream across the mesh (one producer + one block shard per
            # device); otherwise the single-device exact stream.
            if self.mesh is not None:
                from repro.stream.sharded import shard_devices

                if len(shard_devices(self.mesh)) > 1:
                    return "stream_shard"
            return "stream"
        if self.mesh is not None:
            return "shard_map"
        if int(np.asarray(X.shape[0] if hasattr(X, "shape") else len(X))) >= AUTO_STREAM_ROWS:
            return "stream"
        return "local"

    def _resolve_kernel(self, sample: np.ndarray) -> Kernel:
        # Self-tune ONLY when no params were given at all — any explicit
        # kernel_params (including typos) must reach the registry factory,
        # which validates them.
        if not isinstance(self.kernel, Kernel) and self.kernel == "rbf" \
                and not self.kernel_params:
            # paper Section 9 self-tuning, estimated on the landmark sample
            if isinstance(sample, CSRBlock):
                # the <= 512 rows self_tuned_rbf draws, densified; sigma is a
                # mean over all their pairs, so their order does not matter
                idx = jax.random.choice(
                    jax.random.PRNGKey(self.random_state), sample.shape[0],
                    (min(512, sample.shape[0]),), replace=False)
                rows = jnp.asarray(sample.toarray(np.asarray(idx)))
                return self_tuned_rbf(rows, sample=rows.shape[0],
                                      seed=self.random_state)
            return self_tuned_rbf(jnp.asarray(sample), seed=self.random_state)
        return resolve_kernel(self.kernel, self.kernel_params)

    # ------------------------------------------------------------ lifecycle

    def _fit_params_and_pool(self, sample: Array, k_fit: Array):
        """The shared front half of phase 1: resolve the kernel, fit the
        embedding member's params on the sample, embed the seeding pool. Used
        identically by fit() (reservoir sample) and partial_fit() (first
        block)."""
        self.kernel_ = self._resolve_kernel(sample)
        params = get_embedding(self.method).fit(
            k_fit, sample, self.kernel_, l=self.l, m=self.m, t=self.t, q=self.q
        )
        pool = ops.embed_block_map(
            sample[: self.seed_sample], params, policy=self.policy
        )
        return params, pool

    def _phase1(self, X, key: Array, backend_name: str):
        """The backend-independent front of every fit/sweep: blocked view,
        landmark sample, embedding fit, seeding pool. Returns
        (store, array, params, pool, k_seed) — k-means++ draws come off
        `k_seed` per restart, identically for fit() and sweep(). CSR input
        is wrapped as a CSR store and sampled as CSR rows: nothing of it is
        densified but the landmarks."""
        if is_csr(X) and not isinstance(X, BlockStore):
            X = BlockStore.from_csr(X, self.block_rows)
        if isinstance(X, BlockStore):
            self._reject_sharded(X, "fit")
            store, array = X, None
        else:
            # Only the resident backends want the whole matrix on device; the
            # streaming ones must stay O(block) in device memory. jnp.asarray
            # is a no-op for an already-device-resident f32 array, and for
            # host numpy f32 input the host view is zero-copy. The host copy
            # for device-array input is deliberate: sampling through the SAME
            # BlockStore blocking on every backend is what makes phase 1 (and
            # therefore local-vs-stream labels) bitwise identical.
            array = (jnp.asarray(X, jnp.float32)
                     if backend_name in ("local", "shard_map") else None)
            X_np = (np.asarray(X, np.float32) if isinstance(X, np.ndarray)
                    else np.asarray(array if array is not None else X,
                                    dtype=np.float32))
            store = BlockStore.from_array(X_np, self.block_rows)
        k_sample, k_fit, k_seed = phase1_keys(key)
        self._phases = {}
        with self._phase("reservoir"):
            sample = reservoir_sample(store, self.landmark_sample,
                                      seed=int(k_sample[-1]))
            if not isinstance(sample, CSRBlock):
                sample = jnp.asarray(sample)
        with self._phase("embed_fit"):
            params, pool = self._fit_params_and_pool(sample, k_fit)
            jax.block_until_ready(pool)
        return store, array, params, pool, k_seed

    def _phase(self, name: str):
        """Span + wall-time accounting for one pipeline phase; the accumulated
        seconds become the FitReport's `phases` dict."""
        phases = self._phases
        span = obs.span(f"phase.{name}", cat="phase")

        class _Timer:
            def __enter__(self_t):
                span.__enter__()
                self_t.t0 = time.perf_counter()
                return self_t

            def __exit__(self_t, *exc):
                phases[name] = (phases.get(name, 0.0)
                                + time.perf_counter() - self_t.t0)
                return span.__exit__(*exc)

        return _Timer()

    def _prepare(self, X, key: Array, backend_name: str,
                 checkpoint_dir=None) -> FitContext:
        """Phase 1, shared by every backend: blocked view, landmark sample,
        embedding fit, k-means++ seeding."""
        store, array, params, pool, k_seed = self._phase1(X, key, backend_name)
        with self._phase("seed"):
            inits = [
                kmeanspp_init(
                    jax.random.fold_in(k_seed, r), pool, self.k,
                    params.discrepancy
                )
                for r in range(max(1, self.n_init))
            ]
            jax.block_until_ready(inits)
        return FitContext(
            store=store, array=array, params=params, k=self.k, inits=inits,
            iters=self.iters, policy=self.policy, decay=self.decay,
            epochs=self.epochs, mesh=self.mesh, scheduler=self.scheduler,
            checkpoint_dir=checkpoint_dir,
        )

    def fit(self, X, y=None, *, key: Array | None = None,
            checkpoint_dir: str | Path | None = None) -> "KernelKMeans":
        """Fit on an in-memory array or a BlockStore; backend per `backend=`.

        checkpoint_dir= turns on mid-fit Lloyd checkpoints for the streaming
        backends: iteration-granular (epoch-granular for minibatch) state is
        saved crash-atomically under `checkpoint_dir/restart_<r>/`, and a
        killed fit re-invoked with the same key and checkpoint_dir resumes
        mid-Lloyd (phase 1 re-runs — it's cheap and key-deterministic — but no
        completed Lloyd iteration is repeated; pair with `sweep`'s staged
        embedding or a Y-block store to also skip re-embedding).

        CSR rows (a scipy.sparse matrix or a CSR ``BlockStore``) are fitted
        without densifying them, by ``method="nystrom"`` on the ``stream``
        backend (``auto`` picks it); any other method or backend raises
        ``NotImplementedError``.

        Args:
            X: (n, d) array-like, a ``BlockStore`` for out-of-core input, or
                CSR rows.
            y: Ignored (sklearn signature compatibility).
            key: PRNG key; ``None`` seeds from ``random_state``.
            checkpoint_dir: Root directory for mid-fit Lloyd checkpoints
                (streaming backends; ``None`` = no checkpointing).

        Returns:
            self, fitted (``model_`` / ``labels_`` / ``inertia_`` set).
        """
        key = key if key is not None else jax.random.PRNGKey(self.random_state)
        name = self._choose_backend(X)
        backend = get_backend(name)  # fail fast, before the embedding fit
        get_embedding(self.method)  # likewise: reject typos before streaming data
        if is_csr(X) and (name != "stream" or self.method != "nystrom"):
            raise NotImplementedError(
                f"CSR rows are fitted by method='nystrom' on backend='stream' "
                f"only, not method={self.method!r} on backend={name!r}")
        metrics_before = obs.snapshot("engine.")
        ctx = self._prepare(X, key, name, checkpoint_dir)
        with self._phase("lloyd"):
            out = backend(ctx)
        self._finish(ctx.params, out, name)
        self._attach_report(name, out=out, metrics_before=metrics_before)
        self._pf_state = None
        return self

    def fit_predict(self, X, *, key: Array | None = None) -> np.ndarray:
        """``fit(X, key=key).labels_`` in one call (sklearn convention).

        Args:
            X: (n, d) array-like or ``BlockStore``.
            key: PRNG key; ``None`` seeds from ``random_state``.

        Returns:
            (n,) int32 training labels of the best restart.
        """
        return self.fit(X, key=key).labels_

    def sweep(
        self,
        X,
        k_grid,
        *,
        restarts: int | None = None,
        key: Array | None = None,
        checkpoint_dir: str | Path | None = None,
    ):
        """Embed-once model selection: materialize the embedding exactly once,
        then run `restarts` k-means++ restarts for every k in `k_grid`
        directly over the cached embedded blocks — one engine pass feeds every
        candidate per Lloyd iteration, so the R*|k_grid| candidate lattice
        costs ~one embedding pass plus cheap linear k-means instead of
        R*|k_grid| full fits (benchmarks/sweep_bench.py).

        Supported backends: "local", "stream", "stream_shard" (per `backend=`
        / the auto dispatch). Returns a `repro.sweep.SweepResult` — every
        candidate's ClusterModel, the inertia table, and a deterministic
        best-model selection the estimator adopts (labels_/inertia_/model_
        afterwards describe the winner, ready to predict/save/serve).

        `restarts=None` uses `n_init`. `sweep(k_grid=[k], restarts=1)` is
        exactly `fit(k)`: identical labels from the same key (the keystone
        invariant, asserted for every registered embedding member on both
        stream backends in tests/test_sweep.py).

        `checkpoint_dir=` persists the embed-once stage (params + pool + Y
        blocks, in the policy's `cache_dtype` wire form) before clustering and
        the SweepResult after: an interrupted sweep re-invoked with the same
        key and checkpoint_dir resumes PAST the embedding pass (no second
        embed — tests assert via the engine's pass counter).

        Args:
            X: (n, d) array-like or ``BlockStore``.
            k_grid: Candidate cluster counts, one sweep column per k.
            restarts: k-means++ restarts per k; ``None`` uses ``n_init``.
            key: PRNG key; ``None`` seeds from ``random_state``.
            checkpoint_dir: Stage/result persistence root (``None`` = off).

        Returns:
            A ``repro.sweep.SweepResult``; the estimator adopts its best
            candidate.
        """
        from repro.sweep import sweep_estimator

        _reject_csr(X, "sweep")
        return sweep_estimator(
            self, X, k_grid, restarts=restarts, key=key,
            checkpoint_dir=checkpoint_dir,
        )

    def partial_fit(self, X, *, key: Array | None = None) -> "KernelKMeans":
        """Online face of the minibatch backend: one decayed (Z, g) update per
        call. On a cold estimator the first call fits the embedding and seeds
        centroids from that block; on a fitted or loaded estimator it
        continues from the existing ClusterModel (fresh decayed stats, the
        restored centroids as the assignment anchor). Either way, later calls
        just embed + assign + update — O(block) forever.

        Args:
            X: One (b, d) block of the stream.
            key: Cold-start PRNG key; ``None`` seeds from ``random_state``.

        Returns:
            self, updated in place.
        """
        _reject_csr(X, "partial_fit")
        Xb = jnp.asarray(np.asarray(X, np.float32))
        if self.model_ is None:
            # landmark-free members (rff, tensorsketch) only read the input
            # dim from the first block, but k-means++ seeding still needs at
            # least k distinct rows; kernelized members need their l landmarks
            need, what = (
                (self.k, f"k={self.k} rows to seed centroids")
                if get_embedding(self.method).landmark_free
                else (self.l, f"l={self.l} rows to fit the embedding")
            )
            if Xb.shape[0] < need:
                raise ValueError(
                    f"partial_fit cold start needs the first block to hold at "
                    f"least {what}, got {Xb.shape[0]}; buffer a larger first "
                    "block"
                )
            key = key if key is not None else jax.random.PRNGKey(self.random_state)
            k_fit, k_seed = jax.random.split(key)
            params, pool = self._fit_params_and_pool(
                Xb[: self.landmark_sample], k_fit
            )
            centroids = kmeanspp_init(k_seed, pool, self.k, params.discrepancy)
            self._pf_state = (
                jnp.zeros((self.k, params.m), jnp.float32),
                jnp.zeros((self.k,), jnp.float32),
                0,
            )
        else:
            params, centroids = self.model_.params, self.model_.centroids
            if self._pf_state is None:  # warm start from fit()/load()
                self._pf_state = (
                    jnp.zeros((self.k, params.m), jnp.float32),
                    jnp.zeros((self.k,), jnp.float32),
                    self.model_.meta.rows_seen,
                )
        Z, g, rows = self._pf_state
        y = ops.embed_block_map(Xb, params, policy=self.policy)
        from repro.core.lloyd import assign_stats

        Z_b, g_b, labels = assign_stats(
            y, centroids, self.k, params.discrepancy, policy=self.policy
        )
        Z = self.decay * Z + Z_b
        g = self.decay * g + g_b
        centroids = centroid_update(Z, g, centroids)
        inertia = float(block_cost(y, centroids, params.discrepancy))
        rows += int(Xb.shape[0])
        self._pf_state = (Z, g, rows)
        out_meta = self._fit_meta(backend="minibatch", rows_seen=rows, n_init=1)
        self.model_ = ClusterModel(
            params=params, centroids=centroids,
            inertia=jnp.asarray(inertia, jnp.float32), meta=out_meta,
        )
        self.labels_ = np.asarray(labels, np.int32)
        self.inertia_ = inertia
        self.n_iter_ = 0
        self.backend_ = "minibatch"
        return self

    def _fit_meta(self, **kw) -> FitMeta:
        return FitMeta(
            k=self.k, method=self.method,
            kernel_name=getattr(self.kernel_, "name", ""),
            l=self.l, m=self.m, t=self.t, q=self.q, iters_cap=self.iters,
            decay=self.decay, epochs=self.epochs,
            landmark_sample=self.landmark_sample, seed_sample=self.seed_sample,
            block_rows=self.block_rows, random_state=self.random_state,
            **kw,
        )

    def _attach_report(self, backend_name: str, *, out=None,
                       metrics_before: dict | None = None,
                       trajectory: list | None = None,
                       shifts: list | None = None,
                       iters: int | None = None,
                       rows_seen: int | None = None,
                       extra: dict | None = None) -> obs.FitReport:
        """Assemble the FitReport for the run that just finished and surface
        it (`fit_report_`, and `model_.report` as a plain non-pytree
        attribute — measurement, not model state)."""
        d = obs.delta(metrics_before or {}, obs.snapshot("engine."))
        report = obs.FitReport(
            backend=backend_name,
            phases=dict(self._phases),
            inertia_trajectory=(list(out.trajectory) if out is not None
                                else list(trajectory or [])),
            centroid_shifts=(list(out.shifts) if out is not None
                             else list(shifts or [])),
            iters=int(out.iters) if out is not None else int(iters or 0),
            rows_seen=(int(out.rows_seen) if out is not None
                       else int(rows_seen or 0)),
            extra=dict(extra or {}),
            **obs.report_from_metrics_delta(d),
        )
        self.fit_report_ = report
        if self.model_ is not None:
            self.model_.report = report
        return report

    def _finish(self, params, out, backend_name: str) -> None:
        meta = self._fit_meta(
            backend=backend_name, iters=int(out.iters),
            rows_seen=int(out.rows_seen), n_init=max(1, self.n_init),
        )
        self.model_ = ClusterModel(
            params=params, centroids=jnp.asarray(out.centroids),
            inertia=jnp.asarray(out.inertia, jnp.float32), meta=meta,
        )
        self.labels_ = np.asarray(out.labels, np.int32)
        self.inertia_ = float(out.inertia)
        self.n_iter_ = int(out.iters)
        self.backend_ = backend_name

    # ------------------------------------------------------------ inference

    def _require_model(self) -> ClusterModel:
        if self.model_ is None:
            raise RuntimeError("estimator is not fitted; call fit() or load()")
        return self.model_

    @staticmethod
    def _reject_sharded(store: BlockStore, op: str) -> None:
        """A shard() of a store covers only a subset of global rows; a dense
        (n,)-shaped answer would silently hold -1 for every unvisited row."""
        covered = sum(store.rows_of(i) for i in range(store.num_blocks))
        if covered != store.n:
            raise ValueError(
                f"{op} got a sharded BlockStore covering {covered} of "
                f"{store.n} rows; run {op} per shard (each worker fills its "
                "own global offsets) or pass the unsharded store"
            )

    def predict(self, X) -> np.ndarray:
        """Nearest-centroid assignment of unseen points (array or BlockStore).

        Blocked inputs stream through the double-buffered engine at the
        policy's prefetch depth.

        Args:
            X: (n, d) array-like or an unsharded ``BlockStore``.

        Returns:
            (n,) int32 cluster labels.
        """
        model = self._require_model()
        _reject_csr(X, "predict")
        if isinstance(X, BlockStore):
            from repro.stream.engine import map_reduce

            self._reject_sharded(X, "predict")
            labels = np.full(X.n, -1, dtype=np.int32)

            def _emit(i, out):
                lo = X.row_offset(i)
                labels[lo:lo + out.shape[0]] = out

            map_reduce(
                X,
                lambda blk: ops.predict_block(  # labels only: no (Z, g)
                    blk, model.params, model.centroids, policy=self.policy
                ),
                lambda acc, _: acc, None,
                prefetch=self.policy.prefetch, emit=_emit,
            )
            return labels
        return np.asarray(model.predict(X, policy=self.policy), np.int32)

    def transform(self, X):
        """The fitted embedding Y = f(X).

        Arrays map to an (n, m) array; a BlockStore maps to a host-staged
        BlockStore of embedded blocks (still O(block) on device).

        Args:
            X: (n, d) array-like or ``BlockStore``.

        Returns:
            The embedded rows, in the input's container shape.
        """
        model = self._require_model()
        _reject_csr(X, "transform")
        if isinstance(X, BlockStore):
            from repro.stream.lloyd import stream_embed

            return stream_embed(X, model.params, policy=self.policy)
        from repro import embed

        return embed.transform(model.params, jnp.asarray(X, jnp.float32), self.policy)

    def score(self, X) -> float:
        """Negative clustering inertia of X under the fitted centroids.

        Higher is better (sklearn convention).

        Args:
            X: (n, d) array-like or an unsharded ``BlockStore``.

        Returns:
            ``-sum_i e(y_i, c_label(i))`` as a float.
        """
        model = self._require_model()
        _reject_csr(X, "score")
        disc = model.discrepancy
        if isinstance(X, BlockStore):
            from repro.stream.engine import map_reduce

            self._reject_sharded(X, "score")
            total = map_reduce(
                X,
                lambda blk: block_cost(
                    ops.embed_block_map(blk, model.params, policy=self.policy),
                    model.centroids, disc,
                ),
                lambda acc, c: acc + c, jnp.asarray(0.0),
                prefetch=self.policy.prefetch,
            )
            return -float(total)
        from repro import embed

        Y = embed.transform(model.params, jnp.asarray(X, jnp.float32), self.policy)
        return -float(block_cost(Y, model.centroids, disc))

    # ---------------------------------------------------------- persistence

    def save(self, ckpt_dir: str | Path, *, step: int = 0) -> Path:
        """Persist the ClusterModel artifact (crash-atomic, elastic restore).

        Args:
            ckpt_dir: Checkpoint root directory.
            step: Step label for the checkpoint layer's keep_last rotation.

        Returns:
            The written step directory.
        """
        from repro.distributed.checkpoint import save_cluster_model

        return save_cluster_model(ckpt_dir, self._require_model(), step=step)

    @classmethod
    def load(cls, ckpt_dir: str | Path, *, step: int | None = None,
             policy: ComputePolicy | None = None) -> "KernelKMeans":
        """Rebuild a serving-ready estimator from a persisted ClusterModel.

        Works regardless of which backend fit the artifact.

        Args:
            ckpt_dir: Checkpoint root directory (as passed to ``save``).
            step: Specific step to load; ``None`` = latest valid.
            policy: ``ComputePolicy`` for subsequent inference (``None`` =
                defaults).

        Returns:
            A fitted estimator (``model_`` set, ready to predict/serve).
        """
        from repro.distributed.checkpoint import load_cluster_model

        model = load_cluster_model(ckpt_dir, step=step)
        meta = model.meta
        # The kernel comes back fully resolved when the member's params carry
        # it (all built-ins do); landmark-free members may legitimately not.
        kernel = getattr(model.params, "kernel", None)
        est = cls(
            model.k,
            kernel=kernel if kernel is not None else (meta.kernel_name or "rbf"),
            method=meta.method,
            backend=meta.backend if meta.backend != "unknown" else "auto",
            # restore the recorded fit hyperparameters so a keyless refit on
            # the same data reproduces the original fit (legacy artifacts
            # recorded none of these — fall back to shapes / constructor
            # defaults, which are APNC-shaped)
            l=meta.l or getattr(model.params, "l", 0) or 300,
            m=meta.m or (model.params.R.shape[1]
                         if hasattr(model.params, "R") else model.params.m),
            t=meta.t, q=meta.q, iters=meta.iters_cap or 20,
            n_init=max(1, meta.n_init), decay=meta.decay, epochs=meta.epochs,
            landmark_sample=meta.landmark_sample or 4096,
            seed_sample=meta.seed_sample or 1024,
            block_rows=meta.block_rows or 4096,
            random_state=meta.random_state, policy=policy,
        )
        est.kernel_ = kernel
        est.model_ = model
        est.inertia_ = float(model.inertia)
        est.n_iter_ = model.meta.iters
        est.backend_ = model.meta.backend
        return est
