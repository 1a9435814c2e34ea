"""Public ops for the entropy kernel: jit'd wrappers with a backend switch.

``column_entropy_masked(codes, weights, bins)`` is the weighted-histogram
primitive: per-column entropy of the weighted (membership-masked) rows.
``population_histogram`` is the Gen-DST batch primitive: per-candidate
(M, B) histograms for a whole GA population in one call — on the Pallas
path the population axis is folded into the column axis, so the entire
population recompute is a single ``masked_histogram_pallas`` launch.

Backend selection:
  * ``backend="jnp"``     — XLA scatter-add reference (`ref.py`); the
    production path on CPU and the correctness oracle everywhere.
  * ``backend="pallas"``  — the VMEM one-hot histogram kernel
    (`kernel.py`).  The default ``interpret=None`` compiles it on the chip
    and interprets it only on the CPU backend, which validates semantics
    but is slow — never the CPU prod path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import masked_histogram_pallas
from .ref import masked_histogram_ref, entropy_from_hist

__all__ = [
    "masked_histogram",
    "column_entropy_masked",
    "population_histogram",
]


def masked_histogram(
    codes: jax.Array,
    weights: jax.Array,
    bins: int,
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    if use_pallas:
        return masked_histogram_pallas(codes, weights, bins, interpret=interpret)
    return masked_histogram_ref(codes, weights, bins)


def column_entropy_masked(
    codes: jax.Array,
    weights: jax.Array,
    bins: int,
    **kw,
) -> jax.Array:
    """(M,) per-column entropy of the masked rows."""
    return entropy_from_hist(masked_histogram(codes, weights, bins, **kw))


@functools.partial(jax.jit, static_argnames=("bins", "backend", "interpret"))
def population_histogram(
    sub_codes: jax.Array,        # (P, n, M) int32 — gathered candidate subsets
    bins: int,
    *,
    backend: str = "jnp",
    interpret: bool | None = None,
) -> jax.Array:
    """Per-candidate histograms: out[p, m, b] = |{i : sub_codes[p, i, m] == b}|.

    The Pallas route reshapes the population into the column axis —
    (P, n, M) -> (n, P*M) — so one kernel launch covers every candidate
    (each candidate's columns are independent; uniform weights).
    """
    P, n, M = sub_codes.shape
    ones = jnp.ones((n,), jnp.float32)
    if backend == "pallas":
        flat = sub_codes.transpose(1, 0, 2).reshape(n, P * M)
        hist = masked_histogram_pallas(flat, ones, bins, interpret=interpret)
        return hist.reshape(P, M, bins)
    if backend != "jnp":
        raise ValueError(f"unknown histogram backend: {backend!r}")
    return jax.vmap(lambda c: masked_histogram_ref(c, ones, bins))(sub_codes)
