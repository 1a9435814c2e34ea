"""Pallas TPU kernel: Mamba-2 SSD chunked scan (the SSM-family hotspot).

Grid (B*H, S/Q) with the chunk axis innermost (sequential on TPU): the
(P, N) inter-chunk state lives in VMEM scratch and is carried across chunk
steps; within a chunk the output is the masked decay-weighted quadratic
contraction (two (Q,Q)x(Q,P) MXU matmuls) — HBM sees only the chunk inputs
and outputs, never the (Q,Q) attention-like intermediates.

Per-head layout (the ops.py wrapper folds (B, H) and broadcasts groups):
  x  (BH, S, P)   dt (BH, S)   A (BH,)   Bm/Cm (BH, S, N)  ->  y (BH, S, P)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

__all__ = ["ssd_scan_pallas"]


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, h_scr, *, block_q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q,)
    a = a_ref[0].astype(jnp.float32)          # scalar (negative)
    bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    la = jnp.cumsum(dt * a)                   # (Q,) log-decay
    u = x * dt[:, None]                       # discretized input

    # intra-chunk: masked decay-weighted quadratic form
    cb = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)     # (Q, Q)
    decay = jnp.exp(la[:, None] - la[None, :])
    qq = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1)
    att = jnp.where(qq, cb * decay, 0.0)
    y = jnp.dot(att, u, preferred_element_type=jnp.float32)        # (Q, P)

    # inter-chunk: contribution of the carried state
    h = h_scr[...]                                                  # (P, N)
    y += jnp.dot(cm * jnp.exp(la)[:, None], h.T,
                 preferred_element_type=jnp.float32)

    # state update: h' = h * exp(la_Q) + sum_t exp(la_Q - la_t) u_t B_t^T
    seg = jnp.exp(la[-1] - la)                                      # (Q,)
    h_scr[...] = h * jnp.exp(la[-1]) + jnp.dot(
        u.T, bm * seg[:, None], preferred_element_type=jnp.float32
    )
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,     # (BH, S, P)
    dt: jax.Array,    # (BH, S)
    a: jax.Array,     # (BH,) negative decay rates
    bm: jax.Array,    # (BH, S, N)
    cm: jax.Array,    # (BH, S, N)
    *,
    block_q: int = 128,
    interpret: bool | None = None,   # None: interpreted only on the CPU
) -> jax.Array:
    BH, S, P = x.shape
    N = bm.shape[-1]
    block_q = min(block_q, S)
    assert S % block_q == 0, "pad sequence to a chunk multiple"

    return pl.pallas_call(
        functools.partial(_ssd_kernel, block_q=block_q),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, block_q), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((1,), lambda bh, ci: (bh,)),
            pl.BlockSpec((1, block_q, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, block_q, N), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, P), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, a, bm, cm)
