"""ComputePolicy: one object for every "how should this math execute" knob.

Before this module existed, a raw ``use_pallas: bool`` was threaded through
~20 call sites across core/, stream/ and kernels/ops.py, and the single-program
and online paths could silently disagree (APNCConfig.use_pallas governed
fit_predict while predict took its own defaulted-False flag). Every driver now
resolves execution through one frozen, hashable dataclass — hashable so it can
ride through ``jax.jit`` as a static argument unchanged.

The old ``use_pallas=`` keywords survive as deprecated shims: passing them
emits a DeprecationWarning and folds the boolean into a ComputePolicy here, in
exactly one place.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Literal

import jax

Precision = Literal["f32", "bf16"]
CacheDtype = Literal["f32", "bf16", "int8"]

# f32 means f32 on every backend. At DEFAULT precision a TPU runs an f32
# matmul as one bf16 pass (about three significant digits): on a v5e that
# moved the rbf gram's cross term, the RFF phases and the l2 distances enough
# to change labels and inertia against an f32 reference. Every matmul on the
# clustering path, jnp and Pallas alike (Mosaic takes DEFAULT or HIGHEST
# only), asks for this precision.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ComputePolicy:
    """Execution policy shared by every backend and driver.

    One frozen, hashable value object answers every "how should this math
    execute" question — it rides through ``jax.jit`` as a static argument, so
    two calls under the same policy share one trace.

    Args:
        pallas: Route the APNC hot loops (embed / assign) through the Pallas
            kernels. ``None`` = auto: Pallas on TPU, jnp reference elsewhere.
        precision: Compute precision for the jnp embedding path (``"f32"`` |
            ``"bf16"``); outputs are always materialized as f32. The Pallas
            kernels accumulate in f32 regardless.
        prefetch: Block prefetch depth of the stream engine (0 = synchronous).
        sstep: Communication-avoiding s-step factor for the ``stream_shard``
            lockstep scheduler: each device runs ``sstep`` Lloyd iterations
            on device-LOCAL (Z, g) sufficient stats between cross-device
            reductions (DESIGN.md §16). 1 = exact classic Lloyd (the
            default; every other backend ignores the knob).
        cache_dtype: Storage codec for the staged embedding cache (the
            host-resident Y blocks of ``stream_embed`` / the sweep engine):
            ``"f32"`` passthrough (default, bitwise-exact), ``"bf16"``, or
            per-column-scaled symmetric ``"int8"`` (DESIGN.md §17). Compressed
            blocks travel to the device in wire form and are dequantized
            inside the fused assign path; decoded f32 Y never round-trips
            through HBM. The resident local path (``y_array``) stays f32.

    Returns:
        A frozen dataclass; use ``dataclasses.replace`` to derive variants.

    Example:
        >>> from repro.api import ComputePolicy
        >>> pol = ComputePolicy(prefetch=4, cache_dtype="int8")
        >>> pol.resolve_pallas() in (True, False)
        True
    """

    pallas: bool | None = None
    precision: Precision = "f32"
    prefetch: int = 2
    sstep: int = 1
    cache_dtype: CacheDtype = "f32"

    def __post_init__(self):
        """Validate field values (raises ValueError on unknown settings)."""
        if self.precision not in ("f32", "bf16"):
            raise ValueError(f"unknown precision {self.precision!r}")
        if self.prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {self.prefetch}")
        if not isinstance(self.sstep, int) or self.sstep < 1:
            raise ValueError(f"sstep must be an int >= 1, got {self.sstep!r}")
        if self.cache_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"unknown cache_dtype {self.cache_dtype!r}: "
                "expected 'f32', 'bf16' or 'int8'"
            )

    def resolve_pallas(self) -> bool:
        """Concrete kernel routing: explicit wins, else Pallas on TPU only.

        Returns:
            bool: whether the Pallas kernels serve this policy's hot loops.
        """
        if self.pallas is None:
            return jax.default_backend() == "tpu"
        return bool(self.pallas)


def as_policy(policy: "ComputePolicy | bool | None") -> ComputePolicy:
    """Coerce legacy values: None -> defaults, bool -> pallas flag (deprecated).

    Args:
        policy: A ``ComputePolicy`` (returned unchanged), ``None`` (the
            default policy), or a bare bool (deprecated ``use_pallas``
            shorthand — warns and folds into ``ComputePolicy(pallas=...)``).

    Returns:
        The resolved ``ComputePolicy``.
    """
    if policy is None:
        return ComputePolicy()
    if isinstance(policy, ComputePolicy):
        return policy
    if isinstance(policy, (bool, int)):
        warnings.warn(
            "passing a bare use_pallas bool is deprecated; pass "
            "policy=ComputePolicy(pallas=...) instead",
            DeprecationWarning, stacklevel=3,
        )
        return ComputePolicy(pallas=bool(policy))
    raise TypeError(f"expected ComputePolicy, bool or None, got {type(policy)!r}")


def resolve_policy(
    policy: ComputePolicy | None = None,
    use_pallas: bool | None = None,
    *,
    owner: str = "",
) -> ComputePolicy:
    """The single shim point for the deprecated ``use_pallas=`` keywords.

    ``use_pallas`` wins over ``policy.pallas`` when both are given (the
    explicit legacy keyword is what old call sites meant), but warns either
    way.

    Args:
        policy: The caller's ``ComputePolicy``, or ``None`` for defaults.
        use_pallas: Deprecated legacy keyword; ``None`` means "not passed".
        owner: Prefix naming the deprecated call site in the warning text.

    Returns:
        The resolved ``ComputePolicy`` with ``pallas`` overridden when the
        legacy keyword was passed.
    """
    if use_pallas is not None:
        warnings.warn(
            f"{owner}use_pallas= is deprecated; pass "
            "policy=ComputePolicy(pallas=...) instead",
            DeprecationWarning, stacklevel=3,
        )
        return dataclasses.replace(policy or ComputePolicy(), pallas=bool(use_pallas))
    return policy if policy is not None else ComputePolicy()
