"""Pallas TPU kernel: causal flash attention (the LM-side compute hot-spot).

Same VMEM/MXU discipline as the APNC kernels: online-softmax accumulators live
in VMEM scratch across the innermost (kv-block) grid dimension; every tile is
128-lane aligned; fully-masked tiles are SKIPPED via @pl.when (the triangle-scan
idea of models/attention.py expressed at the Mosaic grid level — predicated-off
blocks cost no MXU cycles on TPU).

    grid = (B*H, S/bq, S/bk)        # kv innermost, sequential
    skip block unless kv_start <= q_end       (causal)
         and kv_end   >  q_start - window     (sliding window, if any)
    S_tile = q_blk @ k_blk^T        (MXU, f32)
    online max/sum update in VMEM scratch; output written at the last kv block.

Head-flattening (B*H leading dim) and GQA repeats happen in ops.py; the oracle
is ref.flash_attention_ref (direct masked softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BQ = 256
DEFAULT_BK = 256
_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *,
                  bq: int, bk: int, nk: int, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = qi * bq
    kv_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    # causal: the block is live iff its first kv position can be attended by the
    # last q position; sliding window bounds it from below.
    live = kv_start <= q_start + bq - 1
    if window:
        live &= kv_start + bk - 1 > q_start - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, Dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = rows >= cols
        if window:
            mask &= rows - cols < window
        s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))  # monotone
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: Array, k: Array, v: Array, *, window: int = 0, scale: float | None = None,
    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK, interpret: bool = False,
) -> Array:
    """q/k/v: (BH, S, Dh) with S % bq == S % bk == 0. Returns (BH, S, Dh).

    VMEM at defaults (bq=bk=256, Dh<=256, f32 scratch):
    q/k/v tiles 3*128KB + acc 256KB + m/l 2KB ~= 0.7MB << 16MB.
    """
    BH, S, Dh = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    grid = (BH, S // bq, S // bk)
    if scale is None:
        scale = Dh ** -0.5  # NOTE: callers with a PADDED Dh must pass the true scale

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=grid[2],
                          window=window, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
