"""Multi-candidate Lloyd drivers over a cached embedding.

The sweep's cost model: the embedding pass is the dominant per-pass cost
(BENCH_embed.json), so running R restarts x a k-grid as independent `fit`
calls pays it R*|k_grid|*(iters+1) times. These drivers pay it ZERO times —
they iterate directly over already-embedded Y blocks (the staged cache of
`ensure_embedding_cache`) and feed EVERY candidate from each engine pass:

  * `sweep_lloyd`          — one stream of Y blocks per iteration; per block,
    per k-grid entry, the (Z, g, labels) statistics of all R restarts are
    computed in one dispatch (vmapped across restarts — or `lax.map` under a
    Pallas-routed policy, so each restart assigns through the identical fused
    kernel the single-candidate path uses);
  * `sweep_lloyd_sharded`  — the same lattice on a device mesh: device d
    streams the round-robin Y shard `y_store.shard(d, D)`, per-device stats
    are reduced ONCE per iteration per k (the same shuffle structure as
    `ooc_lloyd_sharded`), and centroids update once;
  * `sweep_lloyd_local`    — resident-Y candidates via `core.lloyd.lloyd`
    (identical calls to the local backend, just minus the re-embedding).

Fixed-point parity is the design constraint, not an accident: each candidate's
update sequence is bitwise the single-candidate driver's (same per-block
summation order from the same zeros, same centroid_update, same final
assignment pass under the final centroids), so `sweep(k_grid=[k], restarts=1)`
reproduces `fit(k)` label-for-label — asserted for every registered embedding
member on both stream backends in tests/test_sweep.py. Candidates converge
individually: a candidate whose labels stop changing is a Lloyd fixed point,
so the extra iterations other candidates still need are numerical no-ops for
it; the engine stops tracking it (and drops a k-group's dispatch entirely once
all its restarts converged).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lloyd import centroid_update
from repro.kernels import ops
from repro.policy import ComputePolicy
from repro.stream.blockstore import BlockStore
from repro.stream.engine import map_reduce
from repro.stream.sharded import (
    _device_copies,
    _replicate,
    cross_device_sum,
    sharded_map_reduce,
)

Array = jax.Array


class SweepLloydOut(NamedTuple):
    """Raw result of one multi-candidate run (the orchestrator wraps it)."""

    labels: list  # [k_index][restart] -> (n,) int32 host labels
    centroids: list  # [k_index] -> (R, k_i, m) final centroids
    inertia: np.ndarray  # (len(k_grid), R) float
    iters: np.ndarray  # (len(k_grid), R) iterations run per candidate
    passes: int  # Lloyd engine passes over the cached Y (excl. final assign)


def _per_candidate(policy: ComputePolicy, one):
    """Lift a single-candidate map over the restart axis. vmap batches the
    R restarts into one program; under a Pallas-routed policy we `lax.map`
    instead — each restart then runs the IDENTICAL fused assignment kernel
    the single-candidate drivers dispatch, keeping sweep==fit label parity
    independent of the kernels' (absent) batching rules."""
    if policy.resolve_pallas():
        return lambda C: jax.lax.map(one, C)
    return jax.vmap(one)


@partial(jax.jit, static_argnames=("k", "discrepancy", "policy"))
def _multi_stats(y, C, k, discrepancy, policy):
    """One Y block, all R restarts of one k: C (R, k, m) ->
    Z (R, k, m), g (R, k), labels (R, rows). Each restart runs the IDENTICAL
    Y-mode `ops.lloyd_step_plan` step the single-candidate drivers dispatch
    (the discarded cost is dead-code-eliminated under jit)."""
    plan = ops.lloyd_step_plan(discrepancy=discrepancy, policy=policy)

    def one(c):
        Z, g, labels, _ = plan.step(y, c)
        return Z, g, labels

    return _per_candidate(policy, one)(C)


@partial(jax.jit, static_argnames=("discrepancy", "policy"))
def _multi_assign_cost(y, C, discrepancy, policy):
    """Final-pass map: labels (R, rows) + per-restart block cost (R,) — the
    plan's final-pass form, lifted over restarts."""
    plan = ops.lloyd_step_plan(discrepancy=discrepancy, policy=policy)

    def one(c):
        return plan.assign(y, c)

    return _per_candidate(policy, one)(C)


_update_batch = jax.jit(jax.vmap(centroid_update))


def _zeros_like_stats(inits: Sequence[Array], active: Sequence[int]):
    """The per-k (Z, g) identity elements, matching ooc_lloyd's explicit
    zeros so the per-block summation starts identically."""
    return [
        (
            jnp.zeros(inits[i].shape, jnp.float32),
            jnp.zeros(inits[i].shape[:2], jnp.float32),
        )
        for i in active
    ]


def _pick_labels(lab_index):
    """emit_pick for the per-k list of map outputs: each k's labels, at
    position 2 in the (Z, g, labels) stats tuple, 0 in the final-pass
    (labels, cost) pair. Only these are copied to the host."""
    return lambda outs: [o[lab_index] for o in outs]


def _label_writer(labels, converged, changed, k_indices):
    """Emit callback factory: write each candidate's block labels (host
    arrays, one (R, rows) stack per k, as `_pick_labels` selects them) at
    `lo` and flag changes against the previously stored pass (ooc_lloyd's
    criterion, per candidate)."""

    def write(lo, labs):
        for j, i in enumerate(k_indices):
            lab = labs[j]
            for r in range(lab.shape[0]):
                if converged is not None and converged[i, r]:
                    continue
                sl = labels[i][r][lo:lo + lab.shape[1]]
                if changed is not None and not changed[i, r] \
                        and not np.array_equal(lab[r], sl):
                    changed[i, r] = True
                labels[i][r][lo:lo + lab.shape[1]] = lab[r]

    return write


def _advance(cents, inits, active, stats, converged, changed, iters_run):
    """Post-pass bookkeeping shared by both stream drivers: one centroid
    update per active k, per-candidate iteration counts, convergence flags.
    Returns the still-active k indices."""
    for j, i in enumerate(active):
        Z, g = stats[j]
        cents[i] = _update_batch(Z, g, cents[i])
        for r in range(inits[i].shape[0]):
            if not converged[i, r]:
                iters_run[i, r] += 1
                if not changed[i, r]:
                    converged[i, r] = True
    return [i for i in active if not converged[i].all()]


def sweep_lloyd(
    y_store: BlockStore,
    inits: Sequence[Array],
    discrepancy,
    *,
    iters: int,
    policy: ComputePolicy,
    prefetch: int | None = None,
) -> SweepLloydOut:
    """Exact multi-candidate Lloyd over cached Y blocks, single device.

    inits[i] is the (R, k_i, m) stack of restart seeds for k-grid entry i.
    Per iteration ONE pass streams every Y block; per block, one dispatch per
    still-active k computes all R restarts' statistics. Per-candidate update
    rule, summation order and final assignment match `ooc_lloyd` exactly.
    """
    prefetch = policy.prefetch if prefetch is None else prefetch
    K = len(inits)
    n = y_store.n
    cents = [jnp.asarray(c) for c in inits]
    R_of = [int(c.shape[0]) for c in cents]
    R = max(R_of)
    labels = [
        [np.full(n, -1, dtype=np.int32) for _ in range(R_of[i])]
        for i in range(K)
    ]
    converged = np.zeros((K, R), dtype=bool)
    iters_run = np.zeros((K, R), dtype=np.int64)
    active = list(range(K))

    passes = 0
    while passes < iters and active:
        changed = np.zeros((K, R), dtype=bool)
        cell = {i: cents[i] for i in active}  # rebound per pass, no retrace
        write = _label_writer(labels, converged, changed, active)

        def map_fn(y, _cell=cell, _act=active):
            return [
                _multi_stats(
                    y, _cell[i], int(_cell[i].shape[1]), discrepancy, policy
                )
                for i in _act
            ]

        def combine(acc, outs):
            return [
                (a[0] + o[0], a[1] + o[1]) for a, o in zip(acc, outs)
            ]

        stats = map_reduce(
            y_store, map_fn, combine, _zeros_like_stats(cents, active),
            prefetch=prefetch,
            emit=lambda i, labs: write(y_store.row_offset(i), labs),
            emit_pick=_pick_labels(2), label="sweep_lloyd",
        )
        active = _advance(
            cents, cents, active, stats, converged, changed, iters_run
        )
        passes += 1

    # Final pass under the final centroids: authoritative labels + inertia
    # for EVERY candidate (mirrors lloyd._final_assign).
    write_final = _label_writer(labels, None, None, list(range(K)))

    def final_fn(y):
        return [
            _multi_assign_cost(y, cents[i], discrepancy, policy)
            for i in range(K)
        ]

    costs = map_reduce(
        y_store, final_fn,
        lambda acc, outs: [a + o[1] for a, o in zip(acc, outs)],
        [jnp.zeros((R_of[i],), jnp.float32) for i in range(K)],
        prefetch=prefetch,
        emit=lambda i, labs: write_final(y_store.row_offset(i), labs),
        emit_pick=_pick_labels(0), label="sweep_lloyd",
    )
    inertia = np.stack([np.asarray(c, dtype=np.float64) for c in costs])
    return SweepLloydOut(labels, cents, inertia, iters_run, passes)


def sweep_lloyd_sharded(
    y_store: BlockStore,
    inits: Sequence[Array],
    discrepancy,
    *,
    iters: int,
    policy: ComputePolicy,
    devices: Sequence,
    prefetch: int | None = None,
) -> SweepLloydOut:
    """The candidate lattice on a device mesh: device d streams Y shard
    `y_store.shard(d, D)`; per iteration the per-device (Z, g) stats of every
    active candidate are reduced in ONE cross-device sum (the same shuffle
    structure as `ooc_lloyd_sharded`, now carrying the whole lattice's
    k*(m+1)*R floats per k) and centroids update once. Fixed point identical
    to `sweep_lloyd` — and, per candidate, to `ooc_lloyd(devices=...)`."""
    prefetch = policy.prefetch if prefetch is None else prefetch
    devices = list(devices)
    D = len(devices)
    K = len(inits)
    n = y_store.n
    shards = [y_store.shard(d, D) for d in range(D)]
    cents = [_replicate(jnp.asarray(c), devices) for c in inits]
    R_of = [int(c.shape[0]) for c in cents]
    R = max(R_of)
    labels = [
        [np.full(n, -1, dtype=np.int32) for _ in range(R_of[i])]
        for i in range(K)
    ]
    converged = np.zeros((K, R), dtype=bool)
    iters_run = np.zeros((K, R), dtype=np.int64)
    active = list(range(K))

    def device_cells(act):
        """Per-device, per-active-k centroid views (zero-copy off the
        replicated arrays), rebuilt each pass."""
        views = {i: _device_copies(cents[i], devices) for i in act}
        return [{i: views[i][d] for i in act} for d in range(D)]

    passes = 0
    while passes < iters and active:
        changed = np.zeros((K, R), dtype=bool)
        cells = device_cells(active)
        writers = [
            _label_writer(labels, converged, changed, active)
            for _ in range(D)
        ]

        def make_map(d, _act=active, _cells=cells):
            def fn(y):
                return [
                    _multi_stats(
                        y, _cells[d][i], int(_cells[d][i].shape[1]),
                        discrepancy, policy,
                    )
                    for i in _act
                ]

            return fn

        def combine(acc, outs):
            return [(a[0] + o[0], a[1] + o[1]) for a, o in zip(acc, outs)]

        zeros_d = [
            jax.device_put(_zeros_like_stats(cents, active), dev)
            for dev in devices
        ]
        accs = sharded_map_reduce(
            shards, [make_map(d) for d in range(D)], combine, zeros_d,
            devices=devices, prefetch=prefetch,
            emits=[
                (lambda i, labs, s=shards[d], w=writers[d]:
                 w(s.row_offset(i), labs))
                for d in range(D)
            ],
            emit_pick=_pick_labels(2),
        )
        reduced = cross_device_sum(accs, devices)
        active = _advance(
            cents, cents, active, reduced, converged, changed, iters_run
        )
        passes += 1

    # Final pass: labels + per-candidate inertia, one partial cost vector per
    # device summed on the host (the last tiny shuffle).
    cells = device_cells(list(range(K)))
    final_writers = [
        _label_writer(labels, None, None, list(range(K)))
        for _ in range(D)
    ]

    def make_final(d, _cells=cells):
        def fn(y):
            return [
                _multi_assign_cost(y, _cells[d][i], discrepancy, policy)
                for i in range(K)
            ]

        return fn

    zeros_d = [
        jax.device_put(
            [jnp.zeros((R_of[i],), jnp.float32) for i in range(K)], dev
        )
        for dev in devices
    ]
    costs = sharded_map_reduce(
        shards, [make_final(d) for d in range(D)],
        lambda acc, outs: [a + o[1] for a, o in zip(acc, outs)],
        zeros_d, devices=devices, prefetch=prefetch,
        emits=[
            (lambda i, labs, s=shards[d], w=final_writers[d]:
             w(s.row_offset(i), labs))
            for d in range(D)
        ],
        emit_pick=_pick_labels(0),
    )
    inertia = np.stack([
        np.sum([np.asarray(costs[d][i], dtype=np.float64) for d in range(D)],
               axis=0)
        for i in range(K)
    ])
    cents_host = [jnp.asarray(np.asarray(c)) for c in cents]
    return SweepLloydOut(labels, cents_host, inertia, iters_run, passes)


def sweep_lloyd_local(
    Y: Array,
    inits: Sequence[Array],
    discrepancy,
    *,
    iters: int,
    policy: ComputePolicy,
) -> SweepLloydOut:
    """Resident-Y candidates: the identical `core.lloyd.lloyd` calls the
    local backend makes, minus its per-fit re-embedding."""
    from repro.core.lloyd import lloyd

    K = len(inits)
    R = max(int(c.shape[0]) for c in inits)
    labels: list = []
    cents: list = []
    inertia = np.zeros((K, R), dtype=np.float64)
    iters_run = np.zeros((K, R), dtype=np.int64)
    for i, C in enumerate(inits):
        k_labels, k_cents = [], []
        for r in range(int(C.shape[0])):
            res = lloyd(
                Y, int(C.shape[1]), discrepancy=discrepancy, iters=iters,
                init=C[r], policy=policy,
            )
            k_labels.append(np.asarray(res.labels, dtype=np.int32))
            k_cents.append(res.centroids)
            inertia[i, r] = float(res.inertia)
            iters_run[i, r] = int(res.iters)
        labels.append(k_labels)
        cents.append(jnp.stack(k_cents))
    return SweepLloydOut(labels, cents, inertia, iters_run, int(iters_run.max()))
