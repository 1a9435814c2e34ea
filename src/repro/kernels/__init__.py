"""Pallas kernels for the hot spots, each with ``kernel.py`` + ``ops.py`` +
a pure-jnp ``ref.py`` oracle.

Every Pallas wrapper takes ``interpret: bool | None = None`` and resolves it
through ``resolve_interpret``: compiled on an accelerator, interpreted only
on the CPU backend, where Mosaic cannot compile.
"""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: ``None`` means interpreted iff the backend is
    the CPU; an explicit bool is returned as given."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
