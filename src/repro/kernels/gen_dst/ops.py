"""Public ops for the fused Gen-DST generation kernel (DESIGN.md §16).

``fused_delta_fitness`` is the one primitive ``_gen_dst_core`` calls per
generation on the ``backend="pallas_fused"`` path: delta-update the
per-candidate (M, B) count tensor after a one-row mutation and reduce it
to the masked-entropy fitness, in a single launch.  Crossover generations
pass ``applied = 0`` (zero delta), so the same launch also serves as the
fitness reduction over freshly recomputed histograms.

Backend selection mirrors ``kernels/entropy/ops.py``:
  * ``backend="jnp"``          — scatter-add + entropy reference
    (`ref.py`); the production CPU path and the bit-level oracle.
  * ``backend="pallas_fused"`` — the VMEM-resident fused kernel
    (`kernel.py`).  The default ``interpret=None`` compiles it on the
    chip and interprets it only on the CPU backend, which validates
    semantics but is slow — never the CPU prod path.

Leading axes: inputs may carry any leading shape (Gen-DST calls with
``(islands, phi, ...)``); everything is flattened to one candidate axis
for the launch and restored on return.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jaxprof import note_trace
from .kernel import fused_delta_fitness_pallas
from .ref import fused_delta_fitness_ref

__all__ = ["fused_delta_fitness"]


@functools.partial(
    jax.jit, static_argnames=("backend", "interpret", "tile_p")
)
def fused_delta_fitness(
    counts: jax.Array,        # (..., M, B) f32 per-candidate histograms
    old_codes: jax.Array,     # (..., M) int32 codes of the evicted row
    new_codes: jax.Array,     # (..., M) int32 codes of the inserted row
    applied: jax.Array,       # (...,) bool — row mutations that fired
    col_mask: jax.Array,      # (..., M) bool column membership
    f_ref: jax.Array,         # scalar F(D)
    *,
    backend: str = "jnp",
    interpret: bool | None = None,
    tile_p: int = 8,
):
    """``(counts', fitness)``: one fused Gen-DST generation update.

    ``counts'[p]`` is ``counts[p]`` with row ``old→new`` swapped where
    ``applied[p]``; ``fitness[p] = -|F(d_p) - F(D)|`` from the updated
    counts under ``col_mask[p]``.
    """
    note_trace("kernels.gen_dst.fused_delta_fitness")
    lead = old_codes.shape[:-1]
    M, B = counts.shape[-2:]
    cf = counts.reshape(-1, M, B)
    of = old_codes.reshape(-1, M)
    nf = new_codes.reshape(-1, M)
    af = applied.reshape(-1)
    mf = col_mask.reshape(-1, M)
    if backend == "pallas_fused":
        c2, fit = fused_delta_fitness_pallas(
            cf, of, nf, af, mf, f_ref, bins=B, tile_p=tile_p,
            interpret=interpret,
        )
    elif backend == "jnp":
        c2, fit = fused_delta_fitness_ref(cf, of, nf, af, mf, f_ref)
    else:
        raise ValueError(f"unknown fused Gen-DST backend: {backend!r}")
    return c2.reshape(*lead, M, B), fit.reshape(lead)
