"""Distributed APNC on a TPU mesh — the MapReduce programs of the paper (Alg 1 + 2)
expressed as shard_map SPMD programs.

Mapping (DESIGN.md section 2):
  * HDFS data blocks          -> X / Y sharded over the ("pod","data") mesh axes
  * broadcast of (R, L)       -> replicated coefficient arrays (they are small; P4.3)
  * map-only embedding job    -> shard-local gram + matmul, ZERO collectives
  * in-mapper combiner (Z, g) -> shard-local sufficient stats
  * shuffle of (Z, g)         -> ONE psum of (k*m + k) floats per Lloyd iteration
  * single reducer Y_bar      -> computed redundantly on every shard post-psum

The embedding phase HLO is asserted collective-free and the clustering phase HLO is
asserted to contain only the (Z, g) psum in tests/test_distributed.py — these are the
paper's two communication claims, checked structurally.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.apnc import Discrepancy, pairwise_discrepancy
from repro.core.lloyd import assign_stats, centroid_update
from repro.policy import ComputePolicy, resolve_policy

Array = jax.Array


def data_axes_of(mesh: Mesh) -> tuple[str, ...]:
    """The axes APNC shards rows over: every mesh axis except 'model' (the APNC
    programs have no tensor-parallel dimension — 'model' stays idle/replicated,
    or is used by the caller to run independent restarts)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def shard_rows(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(data_axes_of(mesh)))


def distributed_embed(
    mesh: Mesh, X: Array, params, *,
    policy: ComputePolicy | None = None, use_pallas: bool | None = None,
) -> Array:
    """Algorithm 1 on the mesh, for ANY registered embedding member. X is
    row-sharded; the embedding params (tiny, P4.3) are replicated. Map-only:
    the lowered program contains no collectives (asserted in tests)."""
    axes = data_axes_of(mesh)
    pol = resolve_policy(policy, use_pallas, owner="distributed_embed: ")

    def block(x_shard, p):
        # route through the single policy dispatch point so pallas AND
        # precision behave exactly as on the local/stream paths
        from repro import embed

        return embed.transform(p, x_shard, pol)

    fn = jax.shard_map(
        block,
        mesh=mesh,
        # P() is a spec PREFIX for the params pytree: every leaf replicated.
        in_specs=(P(axes), P()),
        out_specs=P(axes),
    )
    return fn(X, params)


def distributed_lloyd(
    mesh: Mesh,
    Y: Array,
    init_centroids: Array,
    *,
    k: int,
    discrepancy: Discrepancy,
    iters: int = 20,
    policy: ComputePolicy | None = None,
    use_pallas: bool | None = None,
    return_costs: bool = False,
) -> tuple[Array, Array]:
    """Algorithm 2 on the mesh. Per iteration, each shard:
      map:     assign its rows to the nearest centroid under e  (Eq. 4)
      combine: accumulate Z (k, m) and g (k,) locally
      shuffle: psum((Z, g)) over the data axes       <- the ONLY communication
      reduce:  Y_bar = Z / g, computed redundantly everywhere

    Returns (labels row-sharded, final centroids replicated); with
    `return_costs=True`, also the (iters,) per-iteration global inertia
    (each iteration's assignment cost under its pre-update centroids) — a
    separate jit'd program, so the default path's compiled artifact is
    untouched.
    """
    pallas = resolve_policy(
        policy, use_pallas, owner="distributed_lloyd: "
    ).resolve_pallas()
    if return_costs:
        return _distributed_lloyd_costs(
            mesh, Y, init_centroids, k=k, discrepancy=discrepancy, iters=iters,
            pallas=pallas,
        )
    return _distributed_lloyd(
        mesh, Y, init_centroids, k=k, discrepancy=discrepancy, iters=iters,
        pallas=pallas,
    )


@partial(jax.jit, static_argnames=("mesh", "k", "discrepancy", "iters", "pallas"))
def _distributed_lloyd(
    mesh: Mesh,
    Y: Array,
    init_centroids: Array,
    *,
    k: int,
    discrepancy: Discrepancy,
    iters: int,
    pallas: bool,
) -> tuple[Array, Array]:
    axes = data_axes_of(mesh)

    def shard_fn(y_shard, c0):
        def body(_, c):
            Z, g, _ = assign_stats(
                y_shard, c, k, discrepancy, policy=ComputePolicy(pallas=pallas)
            )
            Z = jax.lax.psum(Z, axes)
            g = jax.lax.psum(g, axes)
            return centroid_update(Z, g, c)

        c = jax.lax.fori_loop(0, iters, body, c0)
        D = pairwise_discrepancy(y_shard, c, discrepancy)
        return jnp.argmin(D, axis=-1).astype(jnp.int32), c

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(axes), P()),
    )
    return fn(Y, init_centroids)


@partial(jax.jit, static_argnames=("mesh", "k", "discrepancy", "iters", "pallas"))
def _distributed_lloyd_costs(
    mesh: Mesh,
    Y: Array,
    init_centroids: Array,
    *,
    k: int,
    discrepancy: Discrepancy,
    iters: int,
    pallas: bool,
) -> tuple[Array, Array, Array]:
    """`_distributed_lloyd` plus the per-iteration global inertia. The costs
    carried through the loop stay shard-LOCAL (psum'ing inside the body would
    flip the carry's replication type mid-loop, which shard_map rejects); the
    whole (iters,) vector is reduced ONCE after the loop — also cheaper than
    iters scalar psums."""
    axes = data_axes_of(mesh)

    def shard_fn(y_shard, c0):
        def body(i, carry):
            c, costs = carry
            Z, g, _ = assign_stats(
                y_shard, c, k, discrepancy, policy=ComputePolicy(pallas=pallas)
            )
            local_cost = jnp.sum(
                jnp.min(pairwise_discrepancy(y_shard, c, discrepancy), axis=-1)
            )
            costs = costs.at[i].set(local_cost)
            Z = jax.lax.psum(Z, axes)
            g = jax.lax.psum(g, axes)
            return centroid_update(Z, g, c), costs

        # Seed the carry from the shard so its replication type matches the
        # device-varying local costs written into it (a bare constant would
        # enter the loop replicated and trip the carry check).
        costs0 = jnp.zeros((iters,), jnp.float32) + 0.0 * y_shard[0, 0]
        c, costs = jax.lax.fori_loop(0, iters, body, (c0, costs0))
        costs = jax.lax.psum(costs, axes)
        D = pairwise_discrepancy(y_shard, c, discrepancy)
        return jnp.argmin(D, axis=-1).astype(jnp.int32), c, costs

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(P(axes), P(), P()),
    )
    return fn(Y, init_centroids)


def sample_rows_global(key: Array, X: Array, count: int) -> Array:
    """Uniform global row sample (used for landmark selection and seeding). Under
    jit/SPMD the gather crosses shards automatically; count is tiny (<= ~2k)."""
    idx = jax.random.choice(key, X.shape[0], (count,), replace=False)
    return jnp.take(X, idx, axis=0)


def distributed_fit_predict(
    mesh: Mesh,
    key: Array,
    X: Array,
    kernel,
    k: int,
    cfg=None,
):
    """End-to-end distributed embed-and-conquer.

    1. sample landmarks globally (Alg 3/4 map phase),
    2. fit coefficients — replicated; the l x l eigensolve is tiny (P4.3),
    3. Algorithm 1 embedding (map-only),
    4. k-means++-lite seeding from a global sample,
    5. Algorithm 2 Lloyd with psum'd (Z, g).
    """
    from repro.core.kkmeans import APNCConfig, fit_coefficients
    from repro.core.lloyd import kmeanspp_init

    cfg = cfg or APNCConfig()
    k_land, k_seed = jax.random.split(key)

    # Landmark sample + coefficient fit: small, replicated everywhere.
    coeffs = fit_coefficients(k_land, X, kernel, cfg)

    Y = distributed_embed(mesh, X, coeffs, policy=cfg.compute)

    # Seed on a bounded global sample so seeding cost is O(sample * k), not O(n k).
    # Separate keys: reusing one for the row sample AND k-means++ correlates
    # which rows are candidates with which candidates get picked.
    k_sample, k_pp = jax.random.split(k_seed)
    sample = sample_rows_global(k_sample, Y, min(Y.shape[0], 16 * k))
    c0 = kmeanspp_init(k_pp, sample, k, coeffs.discrepancy)

    labels, centroids = distributed_lloyd(
        mesh, Y, c0, k=k, discrepancy=coeffs.discrepancy, iters=cfg.iters,
        policy=cfg.compute,
    )
    return labels, centroids, coeffs
