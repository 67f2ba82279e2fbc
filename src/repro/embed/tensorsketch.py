"""TensorSketch as a first-class family member ("tensorsketch").

Pham-Pagh count-sketch of the degree-p tensor product: for the polynomial
kernel (x'z + c)^p,

    ts(x) = ifft( prod_{i=1..p} fft( CountSketch_i(x~) ) ),   x~ = [x, sqrt(c)]

with p independent count-sketches (hash h_i: [d] -> [m], sign s_i: [d] -> ±1)
so that E[<ts(x), ts(z)>] = (x'z + c)^p. This opens the paper's MNIST-style
polynomial-kernel workloads to every execution regime (stream, shard_map,
serving) without landmarks or an l x l eigensolve — the interchangeable-sketch
argument of Pourkamali-Anaraki & Becker (1608.07597).

The count-sketches are stored DENSE — S (p, d~, m) with S[i, j, h_i(j)] =
s_i(j) — so the per-level sketch is one MXU-friendly matmul and the params
serialize as a single array. Degree-1 sketches are (affine-)linear in the
input, so the member declares P4.1 linearity exactly when p == 1.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.kernels_fn import Kernel
from repro.embed.base import Embedding, EmbeddingProps, register_embedding
from repro.policy import MATMUL_PRECISION

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TensorSketchParams:
    """The fitted sketch: p dense count-sketch matrices over the (possibly
    constant-augmented) input, plus the polynomial kernel for provenance."""

    S: Array  # (p, d_aug, m) with exactly one ±1 entry per (level, input) row
    kernel: Kernel = dataclasses.field(metadata=dict(static=True))

    @property
    def m(self) -> int:  # embedding dimensionality
        return self.S.shape[2]

    @property
    def d(self) -> int:  # input dimensionality (before constant augmentation)
        return self.S.shape[1] - (1 if self.kernel.coef0 > 0 else 0)

    @property
    def discrepancy(self) -> str:
        return "l2"


def tensorsketch_transform(params: TensorSketchParams, X: Array) -> Array:
    """Reference map: (n, d) -> (n, m) f32 (FFT runs in f32 regardless of the
    requested compute precision — jnp.fft has no bf16 path)."""
    if params.kernel.coef0 > 0:  # (x~'z~) = x'z + c
        const = jnp.full(
            (X.shape[0], 1), jnp.sqrt(params.kernel.coef0), dtype=X.dtype
        )
        X = jnp.concatenate([X, const], axis=-1)
    C = jnp.einsum(  # p count-sketches
        "nd,pdm->pnm", X, params.S.astype(X.dtype), precision=MATMUL_PRECISION
    )
    F = jnp.prod(jnp.fft.fft(C.astype(jnp.float32), axis=-1), axis=0)
    return jnp.fft.ifft(F).real.astype(jnp.float32)


@register_embedding
class TensorSketchEmbedding(Embedding):
    name = "tensorsketch"
    params_cls = TensorSketchParams
    landmark_free = True
    kernel_families = ("poly",)

    def fit(self, key, data, kernel, *, l, m, t=None, q=1) -> TensorSketchParams:
        """Draw the p count-sketches for kernel (x'z + coef0)^degree. `l` and
        `t` are landmark knobs of the kernelized members and are ignored."""
        if kernel.name != "poly":
            raise ValueError(
                "the tensorsketch embedding targets polynomial kernels; got "
                f"kernel {kernel.name!r} (use method='rff' for rbf, "
                "'nystrom'/'sd' for arbitrary kernels)"
            )
        if q != 1:
            raise ValueError("tensorsketch is not blockwise; q must be 1")
        if m < 1 or kernel.degree < 1:
            raise ValueError(f"need m >= 1 and degree >= 1, got {m}, {kernel.degree}")
        if kernel.coef0 < 0:
            raise ValueError(
                f"tensorsketch needs coef0 >= 0 (the constant augments x as "
                f"sqrt(coef0)), got {kernel.coef0}"
            )
        d_aug = data.shape[-1] + (1 if kernel.coef0 > 0 else 0)
        eye = jnp.eye(m, dtype=jnp.float32)

        def one_level(k):
            kh, ks = jax.random.split(k)
            h = jax.random.randint(kh, (d_aug,), 0, m)
            s = jax.random.rademacher(ks, (d_aug,), jnp.float32)
            return s[:, None] * eye[h]  # (d_aug, m), one ±1 per row

        S = jax.vmap(one_level)(jax.random.split(key, kernel.degree))
        return TensorSketchParams(S=S, kernel=kernel)

    def transform(self, params: TensorSketchParams, X: Array) -> Array:
        return tensorsketch_transform(params, X)

    def props(self, params: TensorSketchParams) -> EmbeddingProps:
        return EmbeddingProps(
            # degree 1 makes ts() (affine-)linear in x, which commutes with
            # row means — the testable P4.1 statement.
            linear=params.kernel.degree == 1,
            discrepancy="l2",
            blockwise=False,
            landmark_free=self.landmark_free,
        )
