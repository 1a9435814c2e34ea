"""Pallas TPU kernel: causal GQA flash attention (train/prefill hotspot).

Online-softmax tiling: grid (batch*q_heads, Sq/TQ, Skv/TK) with the KV tile
axis innermost (sequential on TPU).  Running max / sum / accumulator live in
VMEM scratch; fully-masked KV tiles short-circuit via pl.when.  GQA is
expressed in the K/V index_map (query head h reads kv head h // group_size)
— no K/V duplication in HBM.

VMEM per step ~ TQ*hd + 2*TK*hd + TQ*TK floats; defaults (TQ=TK=128,
hd<=256) stay well under the ~16 MB v5e VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip tiles strictly above the diagonal
    run = True
    if causal:
        run = (kv_idx * block_k) <= (q_idx * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (TQ, hd)
        k = k_ref[0].astype(jnp.float32)                # (TK, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (TQ, TK)
        if causal:
            qpos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,     # (B, Sq, H, hd)
    k: jax.Array,     # (B, Skv, K, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,   # None: interpreted only on the CPU
) -> jax.Array:
    B, Sq, H, hd = q.shape
    _, Skv, Kh, _ = k.shape
    group = H // Kh
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, "pad seq to block multiple"

    # layout: (B*H, S, hd) with heads folded into the grid's first axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kh, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kh, Skv, hd)

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * Kh + h // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=hd ** -0.5, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(B * H, Sq // block_q, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
