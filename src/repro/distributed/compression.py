"""Gradient compression for the data-parallel all-reduce: int8 quantization with
error feedback (EF-SGD style).

Under pjit the gradient reduction is implicit (autodiff inserts it), so the
compressed path is an explicit shard_map DDP mode: per-shard raw gradients are
quantized to int8 against a per-leaf max-abs scale, summed as int32 across the
dp axes (no overflow for <= 2^23 shards), dequantized with the psum'd scale, and
the quantization residual is carried to the next step (error feedback keeps the
bias bounded; convergence validated in tests/test_compression.py).

Wire saving: 1 byte/element instead of 4 on the DP all-reduce => 4x fewer
gradient bytes across pods, where the links are thinnest (the "pod" axis).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def init_error_state(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def _quantize(g: Array) -> tuple[Array, Array]:
    scale = jnp.max(jnp.abs(g)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(grads: Any, error: Any, axes: tuple[str, ...]) -> tuple[Any, Any]:
    """MUST run inside shard_map over `axes`. Returns (mean_grads, new_error)."""
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = _quantize(g32)
        total = jax.lax.psum(q.astype(jnp.int32), axes)  # int32 wire sum
        scale_sum = jax.lax.psum(scale, axes)
        # each shard contributed q_i * scale_i; using the mean scale for dequant
        # is exact when scales match and bounded-error otherwise — the residual
        # goes back into the error feedback.
        mean_scale = scale_sum / n
        deq = total.astype(jnp.float32) * mean_scale / n
        local_recon = q.astype(jnp.float32) * scale
        new_e = g32 - local_recon  # residual of OUR contribution
        return deq, new_e

    flat_g, td = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree.leaves(error)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    mean_g = jax.tree_util.tree_unflatten(td, [o[0] for o in out])
    new_e = jax.tree_util.tree_unflatten(td, [o[1] for o in out])
    return mean_g, new_e


def make_ddp_compressed_step(mesh: Mesh, loss_fn, opt_update, axes=("data",)):
    """DDP train step with int8-EF gradient exchange.

    params are REPLICATED (classic DDP), batch sharded over `axes`. loss_fn:
    (params, batch) -> scalar (per-shard mean). opt_update: (params, grads,
    opt_state) -> (params, opt_state).
    Returns step(params, opt_state, err_state, batch) -> (params, opt_state,
    err_state, loss).
    """

    def shard_body(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axes)
        grads, err = compressed_psum(grads, err, axes)
        params, opt_state = opt_update(params, grads, opt_state)
        return params, opt_state, err, loss

    batch_spec = P(axes)

    def step(params, opt_state, err, batch):
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), P(), P(), batch_spec),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(params, opt_state, err, batch)

    return step
