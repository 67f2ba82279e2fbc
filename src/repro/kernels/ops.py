"""jit'd public wrappers for the Pallas kernels: padding, dispatch, unpadding.

On a TPU the kernels compile to Mosaic. On any other backend they run with
interpret=True (the kernel body executes as XLA on the CPU), which is how the
tests run them. `interpret=None` picks by backend.
"""
from __future__ import annotations

import warnings
from functools import partial, wraps

import jax
import jax.numpy as jnp

from repro.core.apnc import APNCCoefficients, csr_operands, embed_csr
from repro.core.kernels_fn import Kernel
from repro.embed.rff import RFFParams
from repro.kernels import apnc_assign as _assign
from repro.kernels import apnc_embed as _embed
from repro.kernels import lloyd_step as _lloyd_step
from repro.kernels import rff_embed as _rff
from repro.policy import ComputePolicy, resolve_policy
from repro.stream.blockstore import CSRBlock, EncodedBlock

Array = jax.Array

_LANE = 128  # TPU lane width: last-dim tiles should be multiples of this
_BIG = 1.0e6  # sentinel coordinate for padded centroids (never wins argmin)


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x: Array, mult: int, axis: int, value: float = 0.0) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("kernel", "bn", "bl", "bd", "interpret"))
def _embed_block_padded(X, landmarks, R, kernel: Kernel, bn, bl, bd, interpret):
    n = X.shape[0]
    Xp = _pad_to(_pad_to(X, bd, 1), bn, 0)
    Lp = _pad_to(_pad_to(landmarks, bd, 1), bl, 0)
    # Pad R columns (landmark dim) with ZEROS so padded landmarks contribute 0,
    # and rows (embedding dim) with zeros -> extra output dims sliced off.
    Rp = _pad_to(_pad_to(R, bl, 1), _LANE, 0)
    Y = _embed.apnc_embed_block(Xp, Lp, Rp, kernel, bn=bn, bl=bl, bd=bd, interpret=interpret)
    return Y[:n, : R.shape[0]]


def apnc_embed(
    X: Array,
    coeffs: APNCCoefficients,
    *,
    bn: int = _embed.DEFAULT_BN,
    bl: int = _embed.DEFAULT_BL,
    bd: int = _embed.DEFAULT_BD,
    interpret: bool | None = None,
) -> Array:
    """Fused APNC embedding (Algorithm 1 hot loop). X (n, d) -> Y (n, m_total) f32."""
    interpret = _auto_interpret(interpret)
    bl_eff = min(bl, max(_LANE, ((coeffs.landmarks.shape[1] + _LANE - 1) // _LANE) * _LANE))
    bd_eff = min(bd, max(_LANE, ((X.shape[1] + _LANE - 1) // _LANE) * _LANE))
    bn_eff = min(bn, max(8, ((X.shape[0] + 7) // 8) * 8))
    parts = [
        _embed_block_padded(
            X, coeffs.landmarks[b], coeffs.R[b], coeffs.kernel,
            bn_eff, bl_eff, bd_eff, interpret,
        )
        for b in range(coeffs.q)
    ]
    return jnp.concatenate(parts, axis=-1)


@partial(jax.jit, static_argnames=("discrepancy", "bn", "interpret"))
def _assign_padded(Y, C, discrepancy, bn, interpret):
    n, m = Y.shape
    k = C.shape[0]
    Yp = _pad_to(_pad_to(Y, _LANE, 1), bn, 0)
    # zero-pad the feature dim on BOTH Y and C: l2/l1 distances are unchanged.
    Cp = _pad_to(_pad_to(C, _LANE, 1), 8, 0)
    if Cp.shape[0] != k:  # sentinel rows: huge coords never win the argmin
        Cp = Cp.at[k:].set(_BIG)
    Z, g, labels = _assign.apnc_assign_padded(
        Yp, Cp, discrepancy, n_actual=n, bn=bn, interpret=interpret
    )
    return Z[:k, :m], g[:k, 0], labels[:n, 0]


def apnc_assign(
    Y: Array,
    C: Array,
    discrepancy: str,
    *,
    bn: int = _assign.DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Fused assignment + sufficient stats (Algorithm 2 map + combiner).

    Y (n, m), C (k, m) -> Z (k, m) f32, g (k,) f32, labels (n,) i32.
    """
    interpret = _auto_interpret(interpret)
    bn_eff = min(bn, max(8, ((Y.shape[0] + 7) // 8) * 8))
    return _assign_padded(Y, C, discrepancy, bn_eff, interpret)


@partial(jax.jit, static_argnames=("scale", "bn", "bm", "bd", "interpret"))
def _rff_block_padded(X, W, scale, bn, bm, bd, interpret):
    n = X.shape[0]
    m = W.shape[1]
    Xp = _pad_to(_pad_to(X, bd, 1), bn, 0)
    # Pad W feature rows with ZEROS (padded input dims contribute nothing to
    # the projection) and columns to the tile; extra outputs are sliced off.
    Wp = _pad_to(_pad_to(W, bd, 0), bm, 1)
    cos, sin = _rff.rff_embed_block(
        Xp, Wp, scale=scale, bn=bn, bm=bm, bd=bd, interpret=interpret
    )
    return jnp.concatenate([cos[:n, :m], sin[:n, :m]], axis=-1)


def rff_embed(
    X: Array,
    params,
    *,
    bn: int = _rff.DEFAULT_BN,
    bm: int = _rff.DEFAULT_BM,
    bd: int = _rff.DEFAULT_BD,
    interpret: bool | None = None,
) -> Array:
    """Fused RFF map (the "rff" member's hot loop): X (n, d) -> Y (n, 2m) f32
    in [cos, sin] layout, matmul and trig fused through VMEM."""
    interpret = _auto_interpret(interpret)
    W = params.W
    bm_eff = min(bm, max(_LANE, ((W.shape[1] + _LANE - 1) // _LANE) * _LANE))
    bd_eff = min(bd, max(_LANE, ((X.shape[1] + _LANE - 1) // _LANE) * _LANE))
    bn_eff = min(bn, max(8, ((X.shape[0] + 7) // 8) * 8))
    return _rff_block_padded(
        X, W, params.scale, bn_eff, bm_eff, bd_eff, interpret
    )


# ---------------------------------------------------------------------------
# Sparse rows: the APNC embedding of a CSR block
# ---------------------------------------------------------------------------


_csr_operands = jax.jit(csr_operands)


@partial(jax.jit, static_argnames=("kernel",))
def _sparse_embed_block(values, cols, indptr, landmarks_t, landmarks_sq, R, kernel):
    """One CSR block's APNC embedding (core.apnc.embed_csr): an XLA gather of
    L^T rows by column id and a sorted segment sum per row, then the rbf (or
    other) kernel and the contraction with R^T at HIGHEST. A dispatch of its
    own, so a device trace finds its ops under this name."""
    return embed_csr(values, cols, indptr, landmarks_t, landmarks_sq, R, kernel)


def sparse_embed(blk: CSRBlock, params, operands=None,
                 policy: ComputePolicy | None = None) -> Array:
    """APNC embedding (Nystrom or SD coefficients) of a `CSRBlock` (host or
    device). `operands` are `csr_operands(params.landmarks)`, when the
    caller keeps them. A policy asking for bf16 off the Pallas path computes
    in bf16 (f32 out), as `embed.transform` does for dense rows."""
    if not isinstance(params, APNCCoefficients):
        raise NotImplementedError(
            f"CSR rows embed only through APNC coefficients (nystrom, sd), "
            f"not {type(params).__name__}")
    lt, lsq = operands if operands is not None else _csr_operands(params.landmarks)
    vals, R = blk.values, params.R
    pol = resolve_policy(policy, owner="ops.sparse_embed: ")
    if pol.precision == "bf16" and not pol.resolve_pallas():
        vals, lt, lsq, R = (jnp.asarray(a, jnp.bfloat16) for a in (vals, lt, lsq, R))
        return _sparse_embed_block(vals, blk.cols, blk.indptr, lt, lsq, R,
                                   params.kernel).astype(jnp.float32)
    return _sparse_embed_block(vals, blk.cols, blk.indptr, lt, lsq, R,
                               params.kernel)


@partial(jax.jit, static_argnames=("policy",))
def _embed_block_map(x: Array, params, policy: ComputePolicy) -> Array:
    from repro import embed  # single routing point for EVERY registered member

    return embed.transform(params, x, policy)


def embed_block_map(
    x: Array, params, *,
    policy: ComputePolicy | None = None, use_pallas: bool | None = None,
) -> Array:
    """Block-shaped embedding entry for the stream engine: one jit'd dispatch
    per (block_rows, d) block for ANY registered embedding's params, routed
    per ComputePolicy (use_pallas= is a deprecated alias). The jit
    specializes per params pytree type, so the dispatch on the member's
    transform happens at trace time, not per block. A `CSRBlock` takes
    `sparse_embed`."""
    pol = resolve_policy(policy, use_pallas, owner="ops.embed_block_map: ")
    if isinstance(x, CSRBlock):
        return sparse_embed(x, params, policy=pol)
    return _embed_block_map(x, params, pol)


@partial(jax.jit, static_argnames=("policy",))
def _embed_assign_block(
    x: Array, params, centroids: Array, policy: ComputePolicy
) -> tuple[Array, Array, Array]:
    from repro.core.lloyd import assign_stats

    y = _embed_block_map(x, params, policy)
    return assign_stats(
        y, centroids, centroids.shape[0], params.discrepancy, policy=policy
    )


def embed_assign_block(
    x: Array, params, centroids: Array, *,
    policy: ComputePolicy | None = None, use_pallas: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Fused block map for streaming Lloyd and the assignment service: embed a
    raw (block_rows, d) block (any registered member) and reduce it to
    (Z, g, labels) against the current centroids — one device dispatch,
    nothing but the block resident."""
    pol = resolve_policy(policy, use_pallas, owner="ops.embed_assign_block: ")
    return _embed_assign_block(x, params, centroids, pol)


@partial(jax.jit, static_argnames=("policy",))
def _embed_assign_block_cost(
    x: Array, params, centroids: Array, policy: ComputePolicy
) -> tuple[Array, Array, Array, Array]:
    from repro.core.lloyd import assign_stats, block_cost

    y = _embed_block_map(x, params, policy)
    Z, g, labels = assign_stats(
        y, centroids, centroids.shape[0], params.discrepancy, policy=policy
    )
    return Z, g, labels, block_cost(y, centroids, params.discrepancy)


def embed_assign_block_cost(
    x: Array, params, centroids: Array, *,
    policy: ComputePolicy | None = None,
) -> tuple[Array, Array, Array, Array]:
    """`embed_assign_block` plus the block's inertia contribution under the
    SAME centroids, in the same dispatch: (Z, g, labels, cost). The assignment
    routes through the identical policy path as `embed_assign_block` — the
    cost is an extra reduction over the shared distance matrix (CSE'd on the
    jnp path), so labels cannot differ from the cost-free op. This is how the
    streaming drivers record the per-iteration inertia trajectory without an
    extra pass."""
    pol = resolve_policy(policy, owner="ops.embed_assign_block_cost: ")
    return _embed_assign_block_cost(x, params, centroids, pol)


@partial(jax.jit, static_argnames=("policy",))
def _embed_predict_block(
    x: Array, params, centroids: Array, policy: ComputePolicy
) -> Array:
    from repro.core.apnc import assign

    y = _embed_block_map(x, params, policy)
    return assign(y, centroids, params.discrepancy)


def predict_block(
    x: Array, params, centroids: Array, *,
    policy: ComputePolicy | None = None,
) -> Array:
    """Labels-ONLY fused block map for serving: embed + nearest-centroid in
    one jit'd dispatch, without building the (Z, g) sufficient statistics the
    training maps need — the cheapest per-request path."""
    pol = resolve_policy(policy, owner="ops.predict_block: ")
    return _embed_predict_block(x, params, centroids, pol)


# ---------------------------------------------------------------------------
# Fused Lloyd step: padded wrappers for kernels/lloyd_step.py
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kernel", "discrepancy", "bn", "interpret"))
def _fused_apnc_step_padded(x, landmarks, R, C, kernel, discrepancy, bn, interpret):
    n = x.shape[0]
    m = R.shape[0]
    k = C.shape[0]
    Xp = _pad_to(_pad_to(x, _LANE, 1), bn, 0)
    Lp = _pad_to(_pad_to(landmarks, _LANE, 1), _LANE, 0)
    # Zero R columns for padded landmarks (contribute nothing) and zero R rows
    # for padded embedding dims — so C's matching padded columns can be zero.
    Rp = _pad_to(_pad_to(R, _LANE, 1), _LANE, 0)
    Cp = _pad_to(_pad_to(C, _LANE, 1), 8, 0)
    if Cp.shape[0] != k:  # sentinel rows: huge coords never win the argmin
        Cp = Cp.at[k:].set(_BIG)
    Z, g, labels, cost = _lloyd_step.fused_apnc_step(
        Xp, Lp, Rp, Cp, kernel, discrepancy, n_actual=n, bn=bn, interpret=interpret
    )
    return Z[:k, :m], g[:k, 0], labels[:n, 0], cost[0, 0]


@partial(jax.jit, static_argnames=("scale", "discrepancy", "bn", "interpret"))
def _fused_rff_step_padded(x, W, C, scale, discrepancy, bn, interpret):
    n = x.shape[0]
    mh = W.shape[1]
    k = C.shape[0]
    Xp = _pad_to(_pad_to(x, _LANE, 1), bn, 0)
    Wp = _pad_to(_pad_to(W, _LANE, 0), _LANE, 1)
    mhp = Wp.shape[1]
    # C arrives in the real [cos, sin] layout (k, 2*mh); re-lay it out to the
    # kernel's padded [cos | 0 | sin | 0] so lanes line up with Y in-kernel.
    Cp = jnp.concatenate(
        [_pad_to(C[:, :mh], _LANE, 1), _pad_to(C[:, mh:], _LANE, 1)], axis=1
    )
    Cp = _pad_to(Cp, 8, 0)
    if Cp.shape[0] != k:
        Cp = Cp.at[k:].set(_BIG)
    Z, g, labels, cost = _lloyd_step.fused_rff_step(
        Xp, Wp, Cp, discrepancy, n_actual=n,
        scale=scale, m_half=mh, bn=bn, interpret=interpret,
    )
    Z = jnp.concatenate([Z[:k, :mh], Z[:k, mhp : mhp + mh]], axis=1)
    return Z, g[:k, 0], labels[:n, 0], cost[0, 0]


def fused_member(params) -> str | None:
    """Which fused lloyd_step kernel can serve these params, if any.

    "apnc" (q == 1 Nystrom/SD: landmarks + R fit whole in VMEM), "rff", or
    None — q > 1 APNC and non-fusable members (TensorSketch's FFT) fall back
    to the un-fused embed + assign chain.
    """
    if params is None:
        return None
    if isinstance(params, APNCCoefficients):
        return "apnc" if params.q == 1 else None
    if isinstance(params, RFFParams):
        return "rff"
    return None


def fused_lloyd_step(
    x: Array, params, centroids: Array, *,
    bn: int = _lloyd_step.DEFAULT_BN, interpret: bool | None = None,
) -> tuple[Array, Array, Array, Array]:
    """ONE Pallas dispatch for a whole Lloyd block step: embed the raw block,
    assign, and reduce to (Z, g, labels, cost) without Y touching HBM.
    Only valid when `fused_member(params)` is not None."""
    interpret = _auto_interpret(interpret)
    bn_eff = min(bn, max(8, ((x.shape[0] + 7) // 8) * 8))
    member = fused_member(params)
    if member == "apnc":
        return _fused_apnc_step_padded(
            x, params.landmarks[0], params.R[0], centroids,
            params.kernel, params.discrepancy, bn_eff, interpret,
        )
    if member == "rff":
        return _fused_rff_step_padded(
            x, params.W, centroids, params.scale,
            params.discrepancy, bn_eff, interpret,
        )
    raise ValueError(f"no fused lloyd step for params of type {type(params)!r}")


# ---------------------------------------------------------------------------
# LloydStepPlan: the one policy-resolved per-block Lloyd step
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("discrepancy", "policy"))
def _assign_stats_cost_y(y: Array, centroids: Array, discrepancy, policy):
    from repro.core.lloyd import assign_stats, block_cost

    Z, g, labels = assign_stats(
        y, centroids, centroids.shape[0], discrepancy, policy=policy
    )
    return Z, g, labels, block_cost(y, centroids, discrepancy)


@partial(jax.jit, static_argnames=("discrepancy", "policy"))
def _assign_cost_y(y: Array, centroids: Array, discrepancy, policy):
    Z, g, labels, cost = _assign_stats_cost_y(y, centroids, discrepancy, policy)
    return labels, cost


@partial(jax.jit, static_argnames=("discrepancy", "bn", "interpret"))
def _dequant_step_padded(Yq, scale, C, discrepancy, bn, interpret):
    n, m = Yq.shape
    k = C.shape[0]
    # Zero payload padding dequantizes to exactly 0, matching zero-padded C.
    Yp = _pad_to(_pad_to(Yq, _LANE, 1), bn, 0)
    Cp = _pad_to(_pad_to(C, _LANE, 1), 8, 0)
    if Cp.shape[0] != k:  # sentinel rows: huge coords never win the argmin
        Cp = Cp.at[k:].set(_BIG)
    # Normalize scale to the (1, m) per-column row the kernel broadcasts
    # (int8 ships one; bf16's scalar 1.0 broadcasts up); zero-pad the lane
    # axis like Yq — zero payload columns dequantize to 0 either way.
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim == 0:
        scale = jnp.full((1, m), scale, jnp.float32)
    Sp = _pad_to(jnp.reshape(scale, (1, m)), _LANE, 1)
    Z, g, labels, cost = _lloyd_step.fused_dequant_step(
        Yp, Sp, Cp, discrepancy,
        n_actual=n, bn=bn, interpret=interpret,
    )
    return Z[:k, :m], g[:k, 0], labels[:n, 0], cost[0, 0]


@partial(jax.jit, static_argnames=("discrepancy", "policy"))
def _dequant_assign_stats_cost(payload, scale, centroids, discrepancy, policy):
    """Y-mode step over a quantized staged block (EncodedBlock wire form).
    Pallas policy: the fused dequant kernel — Yq * scale happens in VMEM and
    the f32 block never touches HBM. jnp policy: dequantize then the shared
    reference chain (bit-identical routing to the f32 Y-mode path)."""
    if policy.resolve_pallas():
        bn_eff = min(
            _lloyd_step.DEFAULT_BN, max(8, ((payload.shape[0] + 7) // 8) * 8)
        )
        return _dequant_step_padded(
            payload, scale, centroids, discrepancy, bn_eff,
            _auto_interpret(None),
        )
    y = payload.astype(jnp.float32) * scale
    return _assign_stats_cost_y(y, centroids, discrepancy, policy)


@partial(jax.jit, static_argnames=("discrepancy", "policy"))
def _dequant_assign_cost(payload, scale, centroids, discrepancy, policy):
    Z, g, labels, cost = _dequant_assign_stats_cost(
        payload, scale, centroids, discrepancy, policy
    )
    return labels, cost


@partial(jax.jit, static_argnames=("policy",))
def _embed_assign_cost_x(x: Array, params, centroids: Array, policy):
    Z, g, labels, cost = _embed_assign_block_cost(x, params, centroids, policy)
    return labels, cost


class LloydStepPlan:
    """One policy-resolved, jitted Lloyd block step, shared by EVERY backend.

    `lloyd_step_plan(...)` resolves the (params, policy) pair ONCE into a plan;
    every consumer (core.lloyd, stream, stream_shard lockstep + pool, sweep)
    then builds its iteration from the same two calls instead of hand-wiring
    the embed -> assign -> stats chain per driver:

        step(block, centroids)   -> (Z, g, labels, cost)   # stats convention
        assign(block, centroids) -> (labels, cost)          # final-pass form

    `block` is a RAW (rows, d) block when the plan carries embedding params
    (X-mode), or an already-embedded (rows, m) block when built with
    `params=None, discrepancy=...` (Y-mode: the local backend and the sweep
    engine's staged cache). Routing, most specific first:

      * Pallas policy + fusable member (APNC q=1, RFF): the fused
        kernels/lloyd_step.py kernel — embed + assign + reduce in one
        dispatch, Y never leaves VMEM.
      * Pallas policy, non-fusable (q>1 APNC, TensorSketch) or Y-mode: the
        existing per-stage kernels (`apnc_embed`/`rff_embed` + `apnc_assign`).
      * otherwise: the jnp reference chain — bit-identical to the
        pre-plan drivers (it IS the same jitted functions).

    A `CSRBlock` (sparse rows, X-mode with APNC params) is told apart by a
    Python isinstance before any of these and takes its own route whatever
    the policy: `_sparse_embed_block` gives Y, which feeds the Y-mode assign
    (`_assign_padded` under a Pallas policy). Dense blocks never see it.

    Both methods are pure and traceable (safe inside lax.while_loop / vmap);
    `block_map(cell)` / `assign_map(cell)` wrap them for the stream engine —
    host-level closures over a 1-element centroids cell, instrumented with the
    `lloyd.fused_step` span and `engine.fused_dispatches` counter when fused,
    and, for a CSR block, the `lloyd.sparse_step` span and the
    `sparse.blocks`, `sparse.nnz` and `sparse.slots` counters.
    """

    def __init__(self, *, params, discrepancy: str, policy: ComputePolicy, member):
        self.params = params
        self.discrepancy = discrepancy
        self.policy = policy
        self.fused_member = member
        self._csr_ops = None  # csr_operands of the landmarks, made once

    def _sparse_y(self, block: CSRBlock) -> Array:
        """Y of a CSR block. The transposed landmark table is built once a
        plan (kept only when the params are concrete, not traced)."""
        if self._csr_ops is None and isinstance(self.params, APNCCoefficients) \
                and not isinstance(self.params.landmarks, jax.core.Tracer):
            self._csr_ops = _csr_operands(self.params.landmarks)
        return sparse_embed(block, self.params, self._csr_ops, self.policy)

    @property
    def fused(self) -> bool:
        return self.fused_member is not None

    def step(self, block: Array, centroids: Array):
        """(Z, g, labels, cost) for one block under `centroids`. Y-mode also
        accepts a quantized `EncodedBlock` (the compressed staged cache's wire
        form): the payload + scale dequantize on device — in VMEM inside the
        fused dequant kernel under a Pallas policy (DESIGN.md §17). X-mode
        also accepts a `CSRBlock`: the sparse embed, then the Y-mode step."""
        if isinstance(block, CSRBlock):
            return _assign_stats_cost_y(self._sparse_y(block), centroids,
                                        self.discrepancy, self.policy)
        if self.params is None:
            if isinstance(block, EncodedBlock):
                return _dequant_assign_stats_cost(
                    block.payload, block.scale, centroids,
                    self.discrepancy, self.policy,
                )
            return _assign_stats_cost_y(block, centroids, self.discrepancy, self.policy)
        if self.fused:
            return fused_lloyd_step(block, self.params, centroids)
        return _embed_assign_block_cost(block, self.params, centroids, self.policy)

    def assign(self, block: Array, centroids: Array):
        """(labels, cost) for one block — the final / scoring pass. Y-mode
        accepts `EncodedBlock` like `step`, X-mode a `CSRBlock`."""
        if isinstance(block, CSRBlock):
            return _assign_cost_y(self._sparse_y(block), centroids,
                                  self.discrepancy, self.policy)
        if self.params is None:
            if isinstance(block, EncodedBlock):
                return _dequant_assign_cost(
                    block.payload, block.scale, centroids,
                    self.discrepancy, self.policy,
                )
            return _assign_cost_y(block, centroids, self.discrepancy, self.policy)
        if self.fused:
            _, _, labels, cost = fused_lloyd_step(block, self.params, centroids)
            return labels, cost
        return _embed_assign_cost_x(block, self.params, centroids, self.policy)

    def _instrumented(self, fn):
        sparse = isinstance(self.params, APNCCoefficients)
        if not (self.fused or sparse):
            return fn
        from repro import obs

        fused_dispatches = obs.counter("engine.fused_dispatches")
        csr_blocks = obs.counter("sparse.blocks")
        csr_nnz = obs.counter("sparse.nnz")
        csr_slots = obs.counter("sparse.slots")

        def wrapped(block):
            if isinstance(block, CSRBlock):
                with obs.span("lloyd.sparse_step", cat="lloyd",
                              rows=block.shape[0], nnz=block.nnz):
                    out = fn(block)
                csr_blocks.inc()
                csr_nnz.inc(block.nnz)
                csr_slots.inc(block.slots)
                return out
            if not self.fused:
                return fn(block)
            with obs.span("lloyd.fused_step", cat="lloyd", member=self.fused_member):
                out = fn(block)
            fused_dispatches.inc()
            return out

        return wrapped

    def block_map(self, centroids_cell: list):
        """Per-block stats map for the stream engine: closes over a 1-element
        centroids cell so drivers swap centroids between iterations without
        retracing. Output tuple follows the stats convention (labels at index
        2, cost at 3)."""
        return self._instrumented(lambda block: self.step(block, centroids_cell[0]))

    def assign_map(self, centroids_cell: list):
        """Per-block final-pass map: (labels, cost), labels at index 0."""
        return self._instrumented(lambda block: self.assign(block, centroids_cell[0]))


def lloyd_step_plan(
    params=None,
    discrepancy: str | None = None,
    *,
    policy: ComputePolicy | None = None,
) -> LloydStepPlan:
    """Build the plan. Pass embedding `params` for X-mode (raw blocks), or
    `params=None` with an explicit `discrepancy` for Y-mode (embedded blocks).
    """
    pol = resolve_policy(policy, owner="ops.lloyd_step_plan: ")
    if params is None:
        if discrepancy is None:
            raise ValueError("Y-mode plan (params=None) needs discrepancy=")
        member = None
    else:
        discrepancy = params.discrepancy
        member = fused_member(params) if pol.resolve_pallas() else None
    return LloydStepPlan(
        params=params, discrepancy=discrepancy, policy=pol, member=member
    )


def _deprecated_alias(name: str, replacement: str, fn):
    @wraps(fn)
    def wrapper(*args, **kwargs):
        warnings.warn(
            f"ops.{name} is deprecated; use ops.{replacement} instead",
            DeprecationWarning, stacklevel=2,
        )
        return fn(*args, **kwargs)

    return wrapper


# Legacy names from when APNC was the only family member; thin warning shims
# over the same functions (bit-exact — they delegate without touching args).
apnc_embed_block_map = _deprecated_alias(
    "apnc_embed_block_map", "embed_block_map", embed_block_map
)
apnc_embed_assign_block = _deprecated_alias(
    "apnc_embed_assign_block", "embed_assign_block", embed_assign_block
)
apnc_predict_block = _deprecated_alias(
    "apnc_predict_block", "predict_block", predict_block
)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    window: int = 0,
    bq: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """Causal flash attention over flat heads (Pallas kernel, TPU target).

    q/k/v: (B, S, H, Dh) with equal head counts (GQA repeat upstream).
    Pads S to tile multiples (padded key rows are masked out by causality since
    their positions exceed every query position) and Dh to the 128 lane.
    """
    from repro.kernels import flash_attention as _fa

    interpret = _auto_interpret(interpret)
    B, S, H, Dh = q.shape
    bq = bq or min(_fa.DEFAULT_BQ, max(8, S))
    bk = bk or min(_fa.DEFAULT_BK, max(8, S))
    tile = max(bq, bk)
    Sp = ((S + tile - 1) // tile) * tile
    Dp = ((Dh + _LANE - 1) // _LANE) * _LANE

    def prep(x):
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, Dp - Dh)))
        return x.transpose(0, 2, 1, 3).reshape(B * H, Sp, Dp)

    out = _fa.flash_attention_bhsd(
        prep(q), prep(k), prep(v), window=window, scale=Dh ** -0.5,
        bq=min(bq, Sp), bk=min(bk, Sp), interpret=interpret,
    )
    out = out.reshape(B, H, Sp, Dp).transpose(0, 2, 1, 3)
    return out[:, :S, :, :Dh]
