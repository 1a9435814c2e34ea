"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) (data, model) = 256 chips.
    Multi-pod: (2, 16, 16) (pod, data, model) = 512 chips; ``pod`` is an
    outer data axis crossing DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    return jax.make_mesh(
        shape, axes,
        devices=jax.devices()[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / smoke runs)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
