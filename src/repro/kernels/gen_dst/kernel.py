"""Pallas TPU kernel: fused Gen-DST generation step (DESIGN.md §16).

One launch per mutation-only generation replaces the scatter-add
``_row_delta`` + ``_counts_fitness`` round trip of the jnp path: each grid
step holds a (TP candidates × M columns × B bins) slab of the population
count tensor in VMEM and, without writing intermediates back to HBM,

  1. applies the one-row mutation delta as a one-hot compare against a
     bin iota (``counts += w * (onehot(new) - onehot(old))`` — VPU work,
     no scatter, exact ±1.0 adds on integer-valued f32 counts), and
  2. reduces the updated slab straight to the masked-entropy fitness
     (normalize → p·log2 p → column-mask average → -|f_d - F(D)|).

The jnp path reads the (P, M, B) counts from HBM twice per generation
(scatter-add pass + entropy pass) and round-trips the updated tensor in
between; the fused kernel reads it once, writes it once (in-place via
``input_output_aliases``), and emits the (P,) fitness from the same
residency.  Crossover (full-recompute) generations route the histogram
rebuild through ``kernels/entropy``'s histogram kernel and then this one with
``applied = 0`` — a zero delta — so *every* generation's fitness comes
from one code path.

Grid: (P/TP,) over candidate tiles; M and B stay whole inside a block.
At B = 256 bins a block of 8 candidates fits Mosaic's 16 MiB of scoped
VMEM up to about 840 columns: the paper's tables (dozens of columns) use
a small share of it, AMLB's Fashion-MNIST (785 columns) nearly all (see
§16.2 for the budget arithmetic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

__all__ = ["fused_delta_fitness_kernel", "fused_delta_fitness_pallas"]


def fused_delta_fitness_kernel(
    counts_ref,      # (TP, M, B) f32
    oldc_ref,        # (TP, M) int32
    newc_ref,        # (TP, M) int32
    w_ref,           # (TP, 1) f32 — 1.0 where the row mutation fired
    cols_ref,        # (TP, M) f32 column mask
    fref_ref,        # (1, 1) f32 — F(D)
    counts_out_ref,  # (TP, M, B) f32, aliased onto counts_ref's buffer
    fit_ref,         # (TP, 1) f32
    *,
    bins: int,
):
    counts = counts_ref[...]
    oldc = oldc_ref[...]
    newc = newc_ref[...]
    w = w_ref[...]                                   # (TP, 1)
    cm = cols_ref[...]                               # (TP, M)
    f_ref = fref_ref[0, 0]

    tp, m = oldc.shape
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (tp, m, bins), 2)
    delta = ((newc[:, :, None] == iota_b).astype(jnp.float32)
             - (oldc[:, :, None] == iota_b).astype(jnp.float32))
    counts = counts + w[:, :, None] * delta          # exact ±1.0 adds

    total = jnp.maximum(jnp.sum(counts, axis=-1, keepdims=True), 1e-12)
    p = counts / total
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0),
                 axis=-1)                            # (TP, M)
    f_d = jnp.sum(h * cm, axis=-1) / jnp.maximum(jnp.sum(cm, axis=-1), 1.0)

    counts_out_ref[...] = counts
    fit_ref[...] = (-jnp.abs(f_d - f_ref))[:, None]


@functools.partial(
    jax.jit, static_argnames=("bins", "tile_p", "interpret")
)
def fused_delta_fitness_pallas(
    counts: jax.Array,        # (P, M, B) f32
    old_codes: jax.Array,     # (P, M) int32
    new_codes: jax.Array,     # (P, M) int32
    applied: jax.Array,       # (P,) bool/f32
    col_mask: jax.Array,      # (P, M) bool
    f_ref: jax.Array,         # scalar f32
    *,
    bins: int,
    tile_p: int = 8,
    interpret: bool | None = None,   # None: interpreted only on the CPU
):
    P, M, B = counts.shape
    assert B == bins
    tile_p = min(tile_p, max(1, P))
    pad_p = (-P) % tile_p
    # padded candidates: zero counts / zero mask / zero delta weight — their
    # fitness lane is computed but sliced off below
    counts_p = jnp.pad(counts, ((0, pad_p), (0, 0), (0, 0)))
    oldc_p = jnp.pad(old_codes, ((0, pad_p), (0, 0)))
    newc_p = jnp.pad(new_codes, ((0, pad_p), (0, 0)))
    w_p = jnp.pad(applied.astype(jnp.float32), (0, pad_p))[:, None]
    cols_p = jnp.pad(col_mask.astype(jnp.float32), ((0, pad_p), (0, 0)))
    Pp = P + pad_p

    counts_out, fit = pl.pallas_call(
        functools.partial(fused_delta_fitness_kernel, bins=bins),
        grid=(Pp // tile_p,),
        in_specs=[
            pl.BlockSpec((tile_p, M, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_p, M), lambda i: (i, 0)),
            pl.BlockSpec((tile_p, M), lambda i: (i, 0)),
            pl.BlockSpec((tile_p, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_p, M), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p, M, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_p, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Pp, M, B), jnp.float32),
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        ],
        # the count tensor is updated in place: one HBM read + one write
        # per generation instead of the jnp path's read/write/read
        input_output_aliases={0: 0},
        interpret=resolve_interpret(interpret),
    )(counts_p, oldc_p, newc_p, w_p, cols_p,
      jnp.asarray(f_ref, jnp.float32).reshape(1, 1))
    return counts_out[:P], fit[:P, 0]
