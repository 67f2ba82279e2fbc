"""Production mesh construction. A FUNCTION, not a module constant — importing
this module never touches jax device state (dry-run sets XLA_FLAGS first)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips with a
    leading "pod" axis (DCN-ish links; gradients + nothing else cross it)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh over the forced host CPU devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, max(1, n // model))
    return make_mesh((data, model), ("data", "model"))
