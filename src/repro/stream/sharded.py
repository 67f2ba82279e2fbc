"""Multi-device out-of-core MapReduce: the paper's job layout on a real mesh.

`core.distributed` runs Algorithms 1+2 as shard_map programs with Y fully
resident across the mesh; `repro.stream` streams blocks but through a single
device. This module closes the gap — the communication-avoiding layout of
Bellavita et al. applied to the stream engine:

  device d of D            <-> mapper d
  store.shard(d, D)        <-> the round-robin HDFS block subset mapper d pulls
  BlockPrefetcher(device=) <-> mapper-local ingest (its own producer + queue)
  per-device (Z, g) fold   <-> in-mapper combiner
  cross_device_sum         <-> the shuffle: ONE reduction of k*(m+1) floats
                               per device per Lloyd iteration
  centroid_update once     <-> the single reducer

Memory is O(block) *per device*: no device ever holds more than one block of
X (or Y), one block of its embedding, and the (k, m)/(k,) statistics — past
both single-device HBM and, with a memmap/generator store, host RAM.

Exact sharded Lloyd reaches the same fixed point as the single-device
`ooc_lloyd` given the same init (identical labels; centroids differ only by
float summation grouping — asserted through the public API for every
registered embedding member in tests/test_stream_sharded.py). The sharded
mini-batch variant (Chitta et al., per-device) applies one decayed update per
*round* of D device-local blocks instead of per block, so its trajectory is
approximate by design, like the single-device mini-batch itself.
"""
from __future__ import annotations

import threading
from functools import lru_cache
from operator import itemgetter
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.lloyd import centroid_update
from repro.kernels import ops
from repro.policy import ComputePolicy
from repro.stream.blockstore import BlockStore
from repro.stream.engine import BlockPrefetcher, map_reduce

Array = jax.Array


def shard_devices(mesh=None) -> list:
    """The devices a sharded stream run maps block shards onto: one stream
    per DATA-axis coordinate of the mesh (the `model` axis carries no rows —
    same convention as `core.distributed.data_axes_of`), or every local
    device when no mesh is given."""
    if mesh is None:
        return list(jax.local_devices())
    arr = np.asarray(mesh.devices)
    for ax in reversed(range(arr.ndim)):
        if mesh.axis_names[ax] == "model":
            arr = np.take(arr, 0, axis=ax)
    return list(arr.flatten())


def sharded_map_reduce(
    shards: Sequence[BlockStore],
    map_fns: Sequence[Callable[[Any], Any]],
    combine_fn: Callable[[Any, Any], Any],
    inits: Sequence[Any],
    *,
    devices: Sequence,
    prefetch: int = 2,
    emits: Sequence[Callable[[int, Any], None] | None] | None = None,
    emit_pick: Callable[[Any], Any] | None = None,
) -> list:
    """One free-running `map_reduce` per device, concurrently: device d
    streams `shards[d]` through its own producer queue (blocks committed to
    `devices[d]`), folds its own accumulator with `combine_fn`, and calls its
    own `emits[d]` in local block order, on the host arrays `emit_pick`
    selects (the engine's deferred emit). Returns the per-device accumulators
    — the caller owns the cross-device reduction (`cross_device_sum`).

    `map_fns[d]` must keep its inputs on `devices[d]` (close over
    device_put coefficients/centroids); jit dispatch follows the committed
    block, so D devices compute concurrently while D producers ingest.
    """
    D = len(devices)
    accs: list = [None] * D
    errs: list = [None] * D

    def run(d: int) -> None:
        if d > 0 or threading.current_thread() is not threading.main_thread():
            # per-device executor threads trace on a stable shard lane (the
            # degenerate D==1 call runs inline on the driver's own lane)
            obs.set_lane(f"shard:{devices[d]}")
        try:
            accs[d] = map_reduce(
                shards[d], map_fns[d], combine_fn, inits[d],
                prefetch=prefetch, emit=emits[d] if emits is not None else None,
                emit_pick=emit_pick, device=devices[d],
            )
        except BaseException as e:  # noqa: BLE001 - re-raised on the caller
            errs[d] = e

    if D == 1:  # no thread hop for the degenerate mesh
        run(0)
    else:
        threads = [threading.Thread(target=run, args=(d,), daemon=True) for d in range(D)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for e in errs:
        if e is not None:
            raise e
    return accs


def stream_embed_sharded(
    store: BlockStore,
    coeffs,
    *,
    devices: Sequence,
    policy: ComputePolicy | None = None,
    prefetch: int = 2,
):
    """The sharded embed-ONCE pass: device d embeds its round-robin block
    shard `store.shard(d, D)` and all D streams write into ONE shared
    host-staged Y store (disjoint global block ids, so concurrent writers
    never touch the same rows). Returns the staged `WritableBlockStore`,
    unwritten-block-guarded like the single-device `stream_embed`."""
    from repro.policy import as_policy
    from repro.stream.engine import cache_embedding
    from repro.stream.blockstore import BlockStore as _BS

    pol = as_policy(policy)
    devices = list(devices)
    D = len(devices)
    out = _BS.empty(n=store.n, d=coeffs.m, block_rows=store.block_rows,
                    codec=pol.cache_dtype)
    shards = [store.shard(d, D) for d in range(D)]
    coeffs_d = [jax.device_put(coeffs, dev) for dev in devices]

    def run(d: int):
        cache_embedding(
            shards[d],
            lambda x, p=coeffs_d[d]: ops.embed_block_map(x, p, policy=pol),
            d_out=coeffs.m, out=out, prefetch=prefetch, device=devices[d],
        )

    if D == 1:
        run(0)
    else:
        errs: list = [None] * D

        def guarded(d: int):
            try:
                run(d)
            except BaseException as e:  # noqa: BLE001 - re-raised on the caller
                errs[d] = e

        threads = [threading.Thread(target=guarded, args=(d,), daemon=True)
                   for d in range(D)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errs:
            if e is not None:
                raise e
    return out


# ------------------------------------------------------- cross-device reduce


@lru_cache(maxsize=16)
def _shard_mesh(devices: tuple) -> Mesh:
    """One 1-D mesh per device tuple — rebuilt-per-call Mesh/Sharding objects
    would cost host time every iteration/round of the drivers."""
    return Mesh(np.asarray(devices), ("shard",))


def _replicate(tree, devices):
    """Place a pytree identically on every shard device (the paper's
    broadcast of the small reducer state)."""
    if len(devices) == 1:
        return jax.device_put(tree, devices[0])
    mesh = _shard_mesh(tuple(devices))
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _device_copies(arr: Array, devices) -> list:
    """Per-device views of a replicated array, in `devices` order — the
    committed operand each device's map closure needs (zero-copy: the data
    already lives on every shard device)."""
    if len(devices) == 1:
        return [arr]
    by_dev = {s.device: s.data for s in arr.addressable_shards}
    return [by_dev[d] for d in devices]


def cross_device_sum(accs: Sequence, devices) -> Any:
    """The shuffle: per-device stat pytrees (each committed to its device)
    -> their elementwise sum, replicated on every device. Leaves are stacked
    into one (D, ...) array sharded over a 1-D device mesh, so a single
    `jnp.sum` over the device axis lowers to the cross-device reduction —
    the psum-equivalent, moving exactly the per-device stat bytes."""
    if len(devices) == 1:
        return accs[0]
    with obs.span("reduce.cross_device", cat="reduce", devices=len(devices)):
        sharding = NamedSharding(_shard_mesh(tuple(devices)), P("shard"))

        def stack_sum(*leaves):
            glob = jax.make_array_from_single_device_arrays(
                (len(devices),) + leaves[0].shape, sharding,
                [l[None] for l in leaves]
            )
            return jnp.sum(glob, axis=0)

        return jax.tree_util.tree_map(stack_sum, *accs)


# ------------------------------------------------------------ plan map fns
#
# Every per-block map below is built from the ONE `ops.lloyd_step_plan`
# (stats AND final-pass forms) — the same plan core.lloyd, stream.lloyd and
# the sweep engine run, so under a Pallas-enabled policy every backend
# assigns through the same kernel and boundary rows cannot flip between the
# stream / stream_shard / pool label-identity invariants.


def _device_plans(coeffs_d, disc, pol, devices):
    """One plan per device, closed over that device's committed params."""
    return [
        ops.lloyd_step_plan(params=coeffs_d[d], discrepancy=disc, policy=pol)
        for d in range(len(devices))
    ]


def _stat_map_fns(coeffs_d, cells, k, disc, pol, devices):
    """Per-device (Z, g, labels, cost) maps reading the device's centroid
    cell — swapped between iterations/rounds without retracing."""
    plans = _device_plans(coeffs_d, disc, pol, devices)
    return [plan.block_map(cell) for plan, cell in zip(plans, cells)]


def _assign_map_fns(coeffs_d, disc, c_locals, pol, devices):
    """Per-device final-pass (labels, cost) maps under fixed centroids."""
    plans = _device_plans(coeffs_d, disc, pol, devices)
    return [plan.assign_map([c]) for plan, c in zip(plans, c_locals)]


# ------------------------------------------------- pool scheduling policy
#
# The lockstep executor above is ONE scheduling policy: block→device
# placement fixed at fit start, one producer per device, a cross-device
# on-mesh reduction per iteration. The pool policy (repro.pool) replaces it
# with leased, reassignable block tasks — any worker can execute any block,
# dead workers' leases are requeued, stragglers' unread blocks stolen — and
# replaces the on-mesh reduction with a host-side float32 merge in global
# block-id order. That merge order is the determinism rule: the folded
# (Z, g, cost) is bitwise independent of which worker ran which block, in
# what order, with how many duplicate re-executions (duplicates are dropped
# at the pool, and every execution of a block is the same pure function of
# the same bits). A chaos run therefore reproduces the fault-free pool run
# exactly; pool vs lockstep differs only by float summation grouping, the
# same tolerance class as stream vs stream_shard.


def _pool_label_emit(store, labels_host, changed=None, index=2):
    def emit(i, out):
        lo = store.row_offset(i)
        new = np.asarray(out[index], dtype=np.int32)
        if changed is not None and not changed[0] \
                and not np.array_equal(new, labels_host[lo:lo + new.shape[0]]):
            changed[0] = True
        labels_host[lo:lo + new.shape[0]] = new

    return emit


def _pool_stat_pass(store, map_fns, labels_host, changed, devices,
                    lease_timeout, label):
    """One fault-tolerant (Z, g, cost) pass: pool-scheduled map, then the
    deterministic host merge in global block-id order."""
    from repro.pool import pool_map_reduce

    outs = pool_map_reduce(
        store, map_fns, devices=devices, lease_timeout=lease_timeout,
        emit=_pool_label_emit(store, labels_host, changed), label=label,
    )
    Z = np.zeros(outs[0][0].shape, np.float32)
    g = np.zeros(outs[0][1].shape, np.float32)
    cost = np.zeros((), np.float32)
    for out in outs:
        Z += out[0]
        g += out[1]
        cost += out[3]
    return Z, g, float(cost)


def _final_assign_pool(store, coeffs_d, disc, c_locals, labels_host, pol,
                       devices, lease_timeout):
    from repro.pool import pool_map_reduce

    fns = _assign_map_fns(coeffs_d, disc, c_locals, pol, devices)
    outs = pool_map_reduce(
        store, fns, devices=devices, lease_timeout=lease_timeout,
        emit=_pool_label_emit(store, labels_host, index=0),
        label="final_assign_pool",
    )
    cost = np.zeros((), np.float32)
    for out in outs:
        cost += out[1]
    return float(cost)


# ----------------------------------------------------------- Lloyd drivers


def _label_emits(shards, labels_host, changed=None):
    def make(shard):
        def emit(i, new):
            lo = shard.row_offset(i)
            if changed is not None and not changed[0] \
                    and not np.array_equal(new, labels_host[lo:lo + new.shape[0]]):
                changed[0] = True
            labels_host[lo:lo + new.shape[0]] = new

        return emit

    return [make(s) for s in shards]


def _final_assign_sharded(
    shards, coeffs_d, disc, c_locals, labels_host, pol, prefetch, devices
):
    """Final pass under the final centroids: labels + inertia, one partial
    cost per device summed on the host (the last tiny shuffle)."""
    fns = _assign_map_fns(coeffs_d, disc, c_locals, pol, devices)

    def emit_of(shard):
        def emit(i, lab):
            lo = shard.row_offset(i)
            labels_host[lo:lo + lab.shape[0]] = lab

        return emit

    zeros = [jax.device_put(jnp.asarray(0.0), dev) for dev in devices]
    costs = sharded_map_reduce(
        shards, fns, lambda acc, out: acc + out[1], zeros,
        devices=devices, prefetch=prefetch, emits=[emit_of(s) for s in shards],
        emit_pick=itemgetter(0),
    )
    return float(sum(float(c) for c in costs))


def ooc_lloyd_sharded(
    store: BlockStore,
    k: int,
    *,
    coeffs,
    discrepancy,
    iters: int,
    init: Array,
    policy: ComputePolicy,
    prefetch: int,
    devices: Sequence,
    scheduler: str = "lockstep",
    checkpoint_dir=None,
    lease_timeout: float = 60.0,
):
    """Exact out-of-core Lloyd across `devices`: same update rule (and fixed
    point) as the single-device `ooc_lloyd`, memory O(block) per device.
    Called through `ooc_lloyd(devices=...)`, which resolves init/policy.

    scheduler: "lockstep" keeps the fixed block→device placement with the
    on-mesh (Z, g) reduction; "pool" runs every pass through the
    fault-tolerant `repro.pool` control plane (leases, requeue, stealing,
    deterministic block-ordered merge), surviving dead and slow workers.

    checkpoint_dir: when given, the state after every iteration (iteration
    number, centroids, labels, trajectory) is saved crash-atomically; a
    refit over the same problem (same shapes + same init, i.e. same
    estimator key) resumes mid-fit instead of restarting from the init.

    policy.sstep > 1 enables the communication-avoiding s-step variant on the
    lockstep scheduler: each device updates its OWN centroids from its local
    (Z, g) for s-1 iterations, and only every s-th iteration (and the last
    one) pays the cross-device shuffle — the per-device assignments drift
    slightly between syncs, but the final pass always runs under globally
    synchronized centroids (DESIGN.md §16). The pool scheduler merges on the
    host every pass by construction and ignores the knob, as does D == 1
    (local IS global). Checkpoints are only written at sync boundaries.
    """
    from repro.stream.lloyd import StreamLloydResult

    if scheduler not in ("lockstep", "pool"):
        raise ValueError(f"unknown scheduler {scheduler!r}: "
                         "expected 'lockstep' or 'pool'")
    devices = list(devices)
    D = len(devices)
    disc = coeffs.discrepancy if coeffs is not None else discrepancy
    shards = [store.shard(d, D) for d in range(D)]
    coeffs_d = [jax.device_put(coeffs, dev) if coeffs is not None else None
                for dev in devices]
    m = int(init.shape[1])
    sstep = policy.sstep if scheduler == "lockstep" and D > 1 else 1
    c = _replicate(jnp.asarray(init), devices)
    c_locals = _device_copies(c, devices)
    cells: list[list] = [[None] for _ in range(D)]
    map_fns = _stat_map_fns(coeffs_d, cells, k, disc, policy, devices)

    labels_host = np.full(store.n, -1, dtype=np.int32)
    changed = [True]
    emits = _label_emits(shards, labels_host, changed)
    zero = (jnp.zeros((k, m), jnp.float32), jnp.zeros((k,), jnp.float32),
            jnp.zeros((), jnp.float32))
    zeros_d = [jax.device_put(zero, dev) for dev in devices]

    trajectory: list[float] = []
    shifts: list[float] = []
    it = 0
    fp = None
    if checkpoint_dir is not None:
        from repro.distributed.checkpoint import lloyd_fingerprint
        from repro.launch.elastic import resume_lloyd_state

        fp = lloyd_fingerprint(kind="ooc", n=store.n, d=store.d, k=k, m=m,
                               init=init,
                               cache_dtype=getattr(store, "codec", "f32"))
        state = resume_lloyd_state(checkpoint_dir, fingerprint=fp,
                                   devices_used=D)
        if state is not None:
            it = state["step"]
            labels_host[:] = state["labels"]
            changed[0] = state["changed"]
            trajectory = list(state["trajectory"])
            shifts = list(state["shifts"])
            c = _replicate(jnp.asarray(state["centroids"]), devices)
            c_locals = _device_copies(c, devices)

    synced = True
    while it < iters and changed[0]:
        changed[0] = False
        with obs.span("lloyd.iter", cat="lloyd", iter=it, devices=D,
                      scheduler=scheduler) as sp:
            for d in range(D):
                cells[d][0] = c_locals[d]
            if scheduler == "pool":
                Zh, gh, cost = _pool_stat_pass(
                    store, map_fns, labels_host, changed, devices,
                    lease_timeout, "lloyd_pool",
                )
                Z, g = jnp.asarray(Zh), jnp.asarray(gh)
                c_host = jnp.asarray(np.asarray(c))
                new_c = _replicate(centroid_update(Z, g, c_host), devices)
                shift = float(jnp.linalg.norm(
                    jnp.asarray(np.asarray(new_c)) - c_host))
                trajectory.append(float(cost))
                c = new_c
                c_locals = _device_copies(c, devices)
            else:
                accs = sharded_map_reduce(
                    shards, map_fns,
                    lambda acc, out: (acc[0] + out[0], acc[1] + out[1],
                                      acc[2] + out[3]),
                    list(zeros_d), devices=devices, prefetch=prefetch,
                    emits=emits, emit_pick=itemgetter(2),
                )
                # s-step sync rule: always at s-boundaries, and always on the
                # LAST iteration (cap reached or labels fixed) so the loop
                # never exits on drifted per-device centroids.
                synced = (sstep == 1 or (it + 1) % sstep == 0
                          or it + 1 >= iters or not changed[0])
                if synced:
                    Z, g, cost = cross_device_sum(accs, devices)
                    # Empty clusters fall back to the last SYNCED centroids
                    # (`c`): with sstep == 1 that is exactly the classic rule.
                    new_c = centroid_update(Z, g, c)
                    shift = float(jnp.linalg.norm(new_c - c))
                    trajectory.append(float(cost))
                    c = new_c
                    c_locals = _device_copies(c, devices)
                else:
                    # Deferred shuffle: each device folds ONLY its local
                    # stats into its own centroids — zero cross-device bytes
                    # this iteration. The global trajectory cost is still the
                    # host sum of the per-device scalar costs.
                    new_locals = [
                        centroid_update(accs[d][0], accs[d][1], c_locals[d])
                        for d in range(D)
                    ]
                    cost = sum(float(accs[d][2]) for d in range(D))
                    # Shift is reported from device 0's local update (there
                    # is no single global centroid set between syncs).
                    shift = float(jnp.linalg.norm(new_locals[0] - c_locals[0]))
                    trajectory.append(cost)
                    c_locals = new_locals
            shifts.append(shift)
            sp.set(inertia=trajectory[-1], shift=shift, synced=synced)
        it += 1
        if checkpoint_dir is not None and synced:
            from repro.distributed.checkpoint import save_lloyd_state

            save_lloyd_state(
                checkpoint_dir, step=it, centroids=np.asarray(c),
                labels=labels_host, trajectory=trajectory, shifts=shifts,
                changed=changed[0], fingerprint=fp, devices_used=D,
            )

    c_locals = _device_copies(c, devices)
    if scheduler == "pool":
        inertia = _final_assign_pool(
            store, coeffs_d, disc, c_locals, labels_host, policy, devices,
            lease_timeout,
        )
        # Join workers still draining a re-executed block (stragglers whose
        # pass already ended): the fit's engine-counter accounting — and the
        # FitReport delta built from it — must be final when we return.
        from repro.pool.executor import drain_stale

        drain_stale()
    else:
        inertia = _final_assign_sharded(
            shards, coeffs_d, disc, c_locals, labels_host, policy, prefetch,
            devices,
        )
    trajectory.append(inertia)
    centroids = jnp.asarray(np.asarray(c))  # off the mesh: plain default-device array
    return StreamLloydResult(
        labels_host, centroids, inertia, it, (it + 1) * store.n,
        tuple(trajectory), tuple(shifts),
    )


def minibatch_lloyd_sharded(
    store: BlockStore,
    k: int,
    *,
    coeffs,
    discrepancy,
    decay: float,
    epochs: int,
    init: Array,
    policy: ComputePolicy,
    prefetch: int,
    devices: Sequence,
    checkpoint_dir=None,
):
    """Per-device mini-batch Lloyd (Chitta et al., sharded): per round, every
    device assigns ONE of its local blocks under the current centroids; the
    round's per-device stats are reduced once and folded into the decayed
    global (Z, g); centroids move once per round of D blocks. Devices whose
    shard is exhausted contribute zero stats in the ragged final rounds.

    checkpoint_dir: epoch-granular crash recovery — the decayed (Z, g)
    sufficient statistics are part of the saved state, so a resumed fit
    continues the same decay trajectory."""
    from repro.stream.lloyd import StreamLloydResult

    devices = list(devices)
    D = len(devices)
    disc = coeffs.discrepancy if coeffs is not None else discrepancy
    shards = [store.shard(d, D) for d in range(D)]
    coeffs_d = [jax.device_put(coeffs, dev) if coeffs is not None else None
                for dev in devices]
    m = int(init.shape[1])
    c = _replicate(jnp.asarray(init), devices)
    cells: list[list] = [[None] for _ in range(D)]
    map_fns = _stat_map_fns(coeffs_d, cells, k, disc, policy, devices)

    zero = (jnp.zeros((k, m), jnp.float32), jnp.zeros((k,), jnp.float32),
            jnp.zeros((), jnp.float32))
    zeros_d = [jax.device_put(zero, dev) for dev in devices]
    Z, g = _replicate(zero[:2], devices)

    labels_host = np.full(store.n, -1, dtype=np.int32)

    trajectory: list[float] = []
    start_ep = 0
    fp = None
    if checkpoint_dir is not None:
        from repro.distributed.checkpoint import lloyd_fingerprint
        from repro.launch.elastic import resume_lloyd_state

        fp = lloyd_fingerprint(kind="minibatch", n=store.n, d=store.d, k=k,
                               m=m, init=init, decay=decay,
                               cache_dtype=getattr(store, "codec", "f32"))
        state = resume_lloyd_state(checkpoint_dir, fingerprint=fp,
                                   devices_used=D)
        if state is not None:
            start_ep = state["step"]
            labels_host[:] = state["labels"]
            trajectory = list(state["trajectory"])
            c = _replicate(jnp.asarray(state["centroids"]), devices)
            Z = _replicate(jnp.asarray(state["stats"]["Z"]), devices)
            g = _replicate(jnp.asarray(state["stats"]["g"]), devices)
    for ep in range(start_ep, epochs):
        epoch_cost = 0.0
        with obs.span("lloyd.epoch", cat="lloyd", epoch=ep, devices=D) as sp:
            pfs = [BlockPrefetcher(shards[d], prefetch=prefetch, device=devices[d])
                   for d in range(D)]
            try:
                while True:
                    for d, cd in enumerate(_device_copies(c, devices)):
                        cells[d][0] = cd
                    round_outs = []
                    stats = list(zeros_d)
                    for d in range(D):
                        item = next(pfs[d], None)
                        if item is None:
                            continue
                        i, blk = item
                        out = map_fns[d](blk)
                        stats[d] = (out[0], out[1], out[3])
                        round_outs.append((d, i, out))
                    if not round_outs:
                        break
                    Zb, gb, costb = cross_device_sum(stats, devices)
                    Z = decay * Z + Zb
                    g = decay * g + gb
                    c = centroid_update(Z, g, c)
                    epoch_cost += float(costb)
                    for d, i, out in round_outs:
                        lo = shards[d].row_offset(i)
                        lab = np.asarray(out[2], dtype=np.int32)
                        labels_host[lo:lo + lab.shape[0]] = lab
            finally:
                for pf in pfs:
                    pf.close()
            trajectory.append(epoch_cost)
            sp.set(inertia=epoch_cost)
        if checkpoint_dir is not None:
            from repro.distributed.checkpoint import save_lloyd_state

            save_lloyd_state(
                checkpoint_dir, step=ep + 1, centroids=np.asarray(c),
                labels=labels_host, trajectory=trajectory, shifts=[],
                changed=True, fingerprint=fp, devices_used=D,
                stats={"Z": np.asarray(Z), "g": np.asarray(g)},
            )

    c_locals = _device_copies(c, devices)
    inertia = _final_assign_sharded(
        shards, coeffs_d, disc, c_locals, labels_host, policy, prefetch, devices
    )
    trajectory.append(inertia)
    centroids = jnp.asarray(np.asarray(c))
    return StreamLloydResult(
        labels_host, centroids, inertia, epochs, (epochs + 1) * store.n,
        tuple(trajectory), (),
    )
