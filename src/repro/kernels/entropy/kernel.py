"""Pallas TPU kernel: masked per-column histogram (Gen-DST fitness hotspot).

Computes ``hist[m, b] = sum_n w[n] * (codes[n, m] == b)`` without ever
materializing the (N, B) one-hot in HBM: each grid step loads a
(TN rows × TM cols) code tile + TN weights into VMEM, forms the weighted
(TN, TM, B) one-hot slab there, and reduces it over the row axis into the
(TM, B) output block.

Tiling follows the TPU's (8, 128) vreg layout: ``tile_n`` is a multiple of
8 (sublanes) and ``tile_m`` of 128 (lanes), and the bin axis is padded to a
multiple of 128, so every block's last two dimensions are aligned whatever
the column count — Gen-DST folds its population into the column axis, so
that count is ``P * M`` (e.g. 100 × 23 = 2300).  Padded rows carry weight
0; padded columns and bins are sliced off.  At the defaults (8, 128) and
B = 256 the one-hot slab is 8 × 128 × 256 × 4 B = 1 MiB, well inside the
v5e's 16 MiB of scoped VMEM.

Grid: (Mp/TM, Np/TN) — the row-tile axis is innermost (sequential on TPU), so
the output block accumulates correctly across row tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

__all__ = ["masked_histogram_kernel", "masked_histogram_pallas"]

_SUBLANES = 8
_LANES = 128


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def masked_histogram_kernel(codes_ref, w_ref, out_ref, *, bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                                   # (TN, TM) int32
    tn, tm = codes.shape
    w = jnp.broadcast_to(w_ref[...], (tn, tm))               # (TN, 1) -> (TN, TM)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (tn, tm, bins), 2)
    hit = codes[:, :, None] == iota_b                        # (TN, TM, B)
    out_ref[...] += jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=0)


@functools.partial(
    jax.jit, static_argnames=("bins", "tile_n", "tile_m", "interpret")
)
def masked_histogram_pallas(
    codes: jax.Array,            # (N, M) int32
    weights: jax.Array,          # (N,) float
    bins: int,
    *,
    tile_n: int = 8,
    tile_m: int = 128,
    interpret: bool | None = None,   # None: interpreted only on the CPU
) -> jax.Array:
    if tile_n % _SUBLANES or tile_m % _LANES:
        raise ValueError(
            f"tile_n must be a multiple of {_SUBLANES} and tile_m of "
            f"{_LANES}; got ({tile_n}, {tile_m})")
    N, M = codes.shape
    tile_n = min(tile_n, _round_up(N, _SUBLANES))
    tile_m = min(tile_m, _round_up(M, _LANES))
    Np, Mp = _round_up(N, tile_n), _round_up(M, tile_m)
    Bp = _round_up(bins, _LANES)
    codes_p = jnp.pad(codes, ((0, Np - N), (0, Mp - M)))
    # padded rows: w = 0, so they add nothing to any bin
    w_p = jnp.pad(weights.astype(jnp.float32), (0, Np - N))[:, None]

    out = pl.pallas_call(
        functools.partial(masked_histogram_kernel, bins=Bp),
        grid=(Mp // tile_m, Np // tile_n),
        in_specs=[
            pl.BlockSpec((tile_n, tile_m), lambda m, n: (n, m)),
            pl.BlockSpec((tile_n, 1), lambda m, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, Bp), lambda m, n: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Bp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(codes_p, w_p)
    return out[:M, :bins]
