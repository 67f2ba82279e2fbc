"""Streaming Lloyd drivers on top of the block engine.

Two regimes, both memory-O(block) on device and both sharing the exact
reduce step of `core.lloyd` (`centroid_update`):

  * `ooc_lloyd`  — exact out-of-core Lloyd: per iteration, stream every block,
    accumulate the global (Z, g), update centroids once. Same fixed point as
    the in-memory `core.lloyd.lloyd` given the same init: the only difference
    is the summation grouping of Z.
  * `minibatch_lloyd` — single-pass streaming Lloyd with decayed sufficient
    statistics Z <- gamma Z + Z_b (Chitta et al., approximate kernel k-means):
    clustering cost decouples from n, for larger-than-disk / continuous-ingest
    streams where "iterate until convergence" is not an option.

Blocks may hold raw inputs X (pass `coeffs=`, the fitted EmbeddingParams of
ANY registered member — repro.embed: each block is embedded on the fly, fused
with assignment — the honest out-of-core path where not even the embedding Y
is ever materialized) or precomputed embeddings Y (pass
`discrepancy=`; see `stream_embed` for staging Y blocks to host RAM once when
host memory allows — it saves re-embedding every iteration).

Execution (Pallas routing, prefetch depth) resolves through one ComputePolicy;
the old `use_pallas=` keyword is a deprecated alias. These drivers back the
"stream" and "minibatch" backends of `repro.api.KernelKMeans`.
"""
from __future__ import annotations

from operator import itemgetter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.apnc import Discrepancy
from repro.embed.base import EmbeddingParams
from repro.core.lloyd import centroid_update, kmeanspp_init
from repro.kernels import ops
from repro.policy import ComputePolicy, resolve_policy
from repro.stream.blockstore import BlockStore, WritableBlockStore
from repro.stream.engine import cache_embedding, map_reduce
from repro.stream.reservoir import reservoir_sample

Array = jax.Array


class StreamLloydResult(NamedTuple):
    labels: np.ndarray  # (n,) int32, host-resident
    centroids: Array  # (k, m)
    inertia: float  # sum of e(y_i, c_{pi(i)})
    iters: int  # iterations actually run
    rows_seen: int  # total rows streamed (epochs * n for exact)
    # Observability trailers (defaulted so legacy positional construction and
    # unpacking keep working): per-iteration inertia (exact drivers: the cost
    # of iteration t's assignment; minibatch: per-epoch accumulated block
    # costs) and per-update centroid shifts ||c_{t+1} - c_t||_F.
    trajectory: tuple = ()
    shifts: tuple = ()


def _block_map(coeffs, discrepancy, centroids_cell, pol: ComputePolicy):
    """(Z, g, labels, cost) map for one block, built from the ONE
    `ops.lloyd_step_plan` every backend shares: X-mode when coeffs given
    (embed fused into the step — one Pallas dispatch for fusable members under
    a Pallas policy), Y-mode otherwise. Labels stay at index 2 (the label emits
    pick out[2]); the trailing cost is the block's inertia under the SAME
    centroids. `centroids_cell` is a 1-element list so minibatch can swap
    centroids between blocks without retracing."""
    plan = ops.lloyd_step_plan(params=coeffs, discrepancy=discrepancy, policy=pol)
    return plan.block_map(centroids_cell)


def stream_embed(
    store: BlockStore,
    coeffs: EmbeddingParams,
    *,
    policy: ComputePolicy | None = None,
    use_pallas: bool | None = None,
    prefetch: int | None = None,
) -> WritableBlockStore:
    """Algorithm 1 over a block stream: X blocks in, Y blocks staged to host
    RAM (O(n*m) host, still O(block) device). Use when host memory fits Y and
    several Lloyd iterations will reuse it. The policy's `cache_dtype` picks
    the staging codec (f32 / bf16 / int8, DESIGN.md §17); compressed blocks
    are dequantized on device by the Lloyd plan when later passes read them."""
    pol = resolve_policy(policy, use_pallas, owner="stream.stream_embed: ")
    prefetch = pol.prefetch if prefetch is None else prefetch
    # cache_embedding writes by GLOBAL block id, so a shard's local block i
    # lands at global block i * num_shards + shard_index
    return cache_embedding(
        store,
        lambda x: ops.embed_block_map(x, coeffs, policy=pol),
        d_out=coeffs.m,
        codec=pol.cache_dtype,
        prefetch=prefetch,
    )


def _resolve_init(store, coeffs, discrepancy, k, init, key, seed_sample, pol):
    if init is not None:
        return jnp.asarray(init)
    if key is None:
        raise ValueError("provide key= for k-means++ init or init= centroids")
    # Independent draws for WHICH rows seed (reservoir) and HOW they seed
    # (k-means++): reusing `key` for both correlates row selection with the
    # seeding choices made among those rows.
    k_res, k_pp = jax.random.split(key)
    sample = jnp.asarray(reservoir_sample(store, seed_sample, seed=int(k_res[-1])))
    if coeffs is not None:  # raw X rows -> embed the reservoir before seeding
        sample = ops.embed_block_map(sample, coeffs, policy=pol)
    return kmeanspp_init(k_pp, sample, k, discrepancy)


def _resolve_devices(devices, mesh):
    """The sharded path trigger: explicit devices win; a mesh contributes its
    data-axis devices; None/None keeps the single-device drivers."""
    if devices is not None and mesh is not None:
        raise ValueError("pass at most one of devices= and mesh=")
    if devices is not None:
        return list(devices)
    if mesh is not None:
        from repro.stream.sharded import shard_devices

        return shard_devices(mesh)
    return None


def ooc_lloyd(
    store: BlockStore,
    k: int,
    *,
    coeffs: EmbeddingParams | None = None,
    discrepancy: Discrepancy | None = None,
    iters: int = 20,
    key: Array | None = None,
    init: Array | None = None,
    seed_sample: int = 1024,
    policy: ComputePolicy | None = None,
    use_pallas: bool | None = None,
    prefetch: int | None = None,
    devices=None,
    mesh=None,
    scheduler: str = "lockstep",
    checkpoint_dir=None,
    lease_timeout: float = 60.0,
) -> StreamLloydResult:
    """Exact out-of-core Lloyd: identical update rule to `core.lloyd.lloyd`,
    memory O(block). Stops early when no label changes (same criterion as the
    in-memory loop). Labels live in a host int32 array (4n bytes).

    devices=/mesh= routes the iteration through `repro.stream.sharded`: each
    device streams a round-robin block shard through its own producer and the
    per-device (Z, g) are reduced once per iteration — same fixed point,
    memory O(block) per device.

    scheduler= selects the sharded pass executor: "lockstep" (fixed
    placement, on-mesh reduce) or "pool" (repro.pool leased tasks: survives
    dead/slow workers, deterministic block-ordered merge). Single-device runs
    are inherently lockstep; asking for "pool" without devices is an error.

    checkpoint_dir= enables mid-fit crash recovery: iteration-granular state
    saves, resumed on a refit with the same data/k/init (same key)."""
    if (coeffs is None) == (discrepancy is None):
        raise ValueError("pass exactly one of coeffs= (raw X blocks) or discrepancy= (Y blocks)")
    pol = resolve_policy(policy, use_pallas, owner="stream.ooc_lloyd: ")
    prefetch = pol.prefetch if prefetch is None else prefetch
    disc = coeffs.discrepancy if coeffs is not None else discrepancy
    centroids_cell = [
        _resolve_init(store, coeffs, disc, k, init, key, seed_sample, pol)
    ]
    devs = _resolve_devices(devices, mesh)
    if devs is not None:
        from repro.stream.sharded import ooc_lloyd_sharded

        return ooc_lloyd_sharded(
            store, k, coeffs=coeffs, discrepancy=discrepancy, iters=iters,
            init=centroids_cell[0], policy=pol, prefetch=prefetch, devices=devs,
            scheduler=scheduler, checkpoint_dir=checkpoint_dir,
            lease_timeout=lease_timeout,
        )
    if scheduler != "lockstep":
        raise ValueError(
            f"scheduler={scheduler!r} needs devices=/mesh=: the single-device "
            "driver has no worker pool")
    m = int(centroids_cell[0].shape[1])
    map_fn = _block_map(coeffs, disc, centroids_cell, pol)

    labels_host = np.full(store.n, -1, dtype=np.int32)
    changed_cell = [True]

    def emit(i, new):
        lo = store.row_offset(i)
        sl = labels_host[lo:lo + new.shape[0]]
        if not changed_cell[0] and not np.array_equal(new, sl):
            changed_cell[0] = True
        labels_host[lo:lo + new.shape[0]] = new

    zero = (jnp.zeros((k, m), jnp.float32), jnp.zeros((k,), jnp.float32),
            jnp.zeros((), jnp.float32))
    trajectory: list[float] = []
    shifts: list[float] = []
    it = 0
    fp = None
    if checkpoint_dir is not None:
        from repro.distributed.checkpoint import lloyd_fingerprint
        from repro.launch.elastic import resume_lloyd_state

        fp = lloyd_fingerprint(kind="ooc", n=store.n, d=store.d, k=k, m=m,
                               init=centroids_cell[0],
                               cache_dtype=getattr(store, "codec", "f32"))
        state = resume_lloyd_state(checkpoint_dir, fingerprint=fp,
                                   devices_used=1)
        if state is not None:
            it = state["step"]
            labels_host[:] = state["labels"]
            changed_cell[0] = state["changed"]
            trajectory = list(state["trajectory"])
            shifts = list(state["shifts"])
            centroids_cell[0] = jnp.asarray(state["centroids"])
    while it < iters and changed_cell[0]:
        changed_cell[0] = False
        with obs.span("lloyd.iter", cat="lloyd", iter=it) as sp:
            Z, g, cost = map_reduce(
                store, map_fn,
                lambda acc, out: (acc[0] + out[0], acc[1] + out[1], acc[2] + out[3]),
                zero, prefetch=prefetch, emit=emit, emit_pick=itemgetter(2),
            )
            new_c = centroid_update(Z, g, centroids_cell[0])
            shift = float(jnp.linalg.norm(new_c - centroids_cell[0]))
            trajectory.append(float(cost))
            shifts.append(shift)
            sp.set(inertia=trajectory[-1], shift=shift)
            centroids_cell[0] = new_c
        it += 1
        if checkpoint_dir is not None:
            from repro.distributed.checkpoint import save_lloyd_state

            save_lloyd_state(
                checkpoint_dir, step=it, centroids=centroids_cell[0],
                labels=labels_host, trajectory=trajectory, shifts=shifts,
                changed=changed_cell[0], fingerprint=fp, devices_used=1,
            )

    # Final pass under the final centroids: labels + inertia (matches the
    # post-loop assignment of core.lloyd at any fixed point). Its inertia is
    # the trajectory's last point — exactly the model's reported inertia.
    inertia = _final_assign(
        store, coeffs, disc, centroids_cell, labels_host, prefetch, pol
    )
    trajectory.append(inertia)
    return StreamLloydResult(
        labels_host, centroids_cell[0], inertia, it, (it + 1) * store.n,
        tuple(trajectory), tuple(shifts),
    )


def _final_assign(store, coeffs, disc, centroids_cell, labels_host, prefetch, pol):
    """Final labels + inertia under the final centroids, ONE plan `assign`
    dispatch per block. The embed-once-reuse-Y trick this pass used to
    hand-roll now lives inside the plan, shared with stream/sharded's final
    pass (labels at index 0, cost at 1 — the final-pass convention)."""
    plan = ops.lloyd_step_plan(params=coeffs, discrepancy=disc, policy=pol)

    def emit(i, lab):
        lo = store.row_offset(i)
        labels_host[lo:lo + lab.shape[0]] = lab

    inertia = map_reduce(
        store, plan.assign_map(centroids_cell), lambda acc, out: acc + out[1],
        jnp.asarray(0.0), prefetch=prefetch, emit=emit, emit_pick=itemgetter(0),
    )
    return float(inertia)


def minibatch_lloyd(
    store: BlockStore,
    k: int,
    *,
    coeffs: EmbeddingParams | None = None,
    discrepancy: Discrepancy | None = None,
    decay: float = 0.9,
    epochs: int = 1,
    key: Array | None = None,
    init: Array | None = None,
    seed_sample: int = 1024,
    policy: ComputePolicy | None = None,
    use_pallas: bool | None = None,
    prefetch: int | None = None,
    devices=None,
    mesh=None,
    checkpoint_dir=None,
) -> StreamLloydResult:
    """Single-pass (per epoch) streaming Lloyd with decayed sufficient stats:

        Z <- decay * Z + Z_b,   g <- decay * g + g_b,   c = Z / g

    Centroids move after *every* block, so one pass over the stream already
    clusters; decay < 1 forgets stale assignments (and, on continuous-ingest
    streams, drifting distributions). decay=1, epochs=iters recovers something
    close to exact Lloyd but with block-staleness in the assignments.

    devices=/mesh= shards the stream: one block per device per round, one
    decayed update per round (see `repro.stream.sharded`)."""
    if (coeffs is None) == (discrepancy is None):
        raise ValueError("pass exactly one of coeffs= (raw X blocks) or discrepancy= (Y blocks)")
    pol = resolve_policy(policy, use_pallas, owner="stream.minibatch_lloyd: ")
    prefetch = pol.prefetch if prefetch is None else prefetch
    disc = coeffs.discrepancy if coeffs is not None else discrepancy
    centroids_cell = [
        _resolve_init(store, coeffs, disc, k, init, key, seed_sample, pol)
    ]
    devs = _resolve_devices(devices, mesh)
    if devs is not None:
        from repro.stream.sharded import minibatch_lloyd_sharded

        return minibatch_lloyd_sharded(
            store, k, coeffs=coeffs, discrepancy=discrepancy, decay=decay,
            epochs=epochs, init=centroids_cell[0], policy=pol,
            prefetch=prefetch, devices=devs, checkpoint_dir=checkpoint_dir,
        )
    m = int(centroids_cell[0].shape[1])
    map_fn = _block_map(coeffs, disc, centroids_cell, pol)

    labels_host = np.full(store.n, -1, dtype=np.int32)

    @jax.jit
    def fold(Z, g, cost, out, c):
        Zn = decay * Z + out[0]
        gn = decay * g + out[1]
        return Zn, gn, cost + out[3], centroid_update(Zn, gn, c)

    state = [jnp.zeros((k, m), jnp.float32), jnp.zeros((k,), jnp.float32),
             jnp.zeros((), jnp.float32)]

    def emit(i, lab):
        lo = store.row_offset(i)
        labels_host[lo:lo + lab.shape[0]] = lab

    def combine(acc, out):
        state[0], state[1], state[2], centroids_cell[0] = fold(
            state[0], state[1], state[2], out, centroids_cell[0]
        )
        return acc

    # Per-EPOCH trajectory: the accumulated block costs of that epoch's
    # assignments (each under the centroids current when its block streamed —
    # the decayed trajectory has no single per-iteration centroid snapshot).
    trajectory: list[float] = []
    seen_cost = 0.0
    start_ep = 0
    fp = None
    if checkpoint_dir is not None:
        from repro.distributed.checkpoint import lloyd_fingerprint
        from repro.launch.elastic import resume_lloyd_state

        fp = lloyd_fingerprint(kind="minibatch", n=store.n, d=store.d, k=k,
                               m=m, init=centroids_cell[0], decay=decay,
                               cache_dtype=getattr(store, "codec", "f32"))
        saved = resume_lloyd_state(checkpoint_dir, fingerprint=fp,
                                   devices_used=1)
        if saved is not None:
            start_ep = saved["step"]
            labels_host[:] = saved["labels"]
            trajectory = list(saved["trajectory"])
            centroids_cell[0] = jnp.asarray(saved["centroids"])
            state[0] = jnp.asarray(saved["stats"]["Z"])
            state[1] = jnp.asarray(saved["stats"]["g"])
            state[2] = jnp.asarray(saved["stats"]["seen_cost"])
            seen_cost = float(state[2])
    for ep in range(start_ep, epochs):
        with obs.span("lloyd.epoch", cat="lloyd", epoch=ep) as sp:
            map_reduce(store, map_fn, combine, None, prefetch=prefetch,
                       emit=emit, emit_pick=itemgetter(2))
            total = float(state[2])
            trajectory.append(total - seen_cost)
            seen_cost = total
            sp.set(inertia=trajectory[-1])
        if checkpoint_dir is not None:
            from repro.distributed.checkpoint import save_lloyd_state

            save_lloyd_state(
                checkpoint_dir, step=ep + 1, centroids=centroids_cell[0],
                labels=labels_host, trajectory=trajectory, shifts=[],
                changed=True, fingerprint=fp, devices_used=1,
                stats={"Z": state[0], "g": state[1], "seen_cost": state[2]},
            )

    inertia = _final_assign(
        store, coeffs, disc, centroids_cell, labels_host, prefetch, pol
    )
    trajectory.append(inertia)
    return StreamLloydResult(  # +1 pass: _final_assign streams everything again
        labels_host, centroids_cell[0], inertia, epochs, (epochs + 1) * store.n,
        tuple(trajectory), (),
    )


def stream_fit_predict(
    key: Array,
    store: BlockStore,
    kernel,
    k: int,
    cfg=None,
    *,
    mode: str = "exact",
    landmark_sample: int = 4096,
    decay: float = 0.9,
    epochs: int = 1,
    prefetch: int | None = None,
):
    """End-to-end embed-and-conquer over a block stream:

    1. reservoir-sample rows for landmark selection (one pass),
    2. fit the embedding on the sample — tiny and resident, as in the paper (P4.3),
    3. cluster the stream: exact out-of-core Lloyd or single-pass mini-batch,
       embedding fused into the per-block map (Y never materializes).

    Returns (StreamLloydResult, EmbeddingParams).
    """
    from repro.core.kkmeans import APNCConfig, fit_coefficients

    cfg = cfg or APNCConfig()
    pol = cfg.compute
    # Three independent streams: WHICH rows the reservoir keeps, the
    # coefficient fit's draws, and the clustering seed — reusing one key for
    # the reservoir and the fit correlates landmark selection with the
    # embedding's own randomness.
    k_sample, k_fit, k_cluster = jax.random.split(key, 3)
    sample = jnp.asarray(reservoir_sample(store, landmark_sample, seed=int(k_sample[-1])))
    coeffs = fit_coefficients(k_fit, sample, kernel, cfg)
    common = dict(coeffs=coeffs, key=k_cluster, policy=pol, prefetch=prefetch)
    if mode == "exact":
        res = ooc_lloyd(store, k, iters=cfg.iters, **common)
    elif mode == "minibatch":
        res = minibatch_lloyd(store, k, decay=decay, epochs=epochs, **common)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return res, coeffs
