"""Gradient compression: error-feedback int8 quantization + a compressed
all-reduce built from shard_map collectives.

``compressed_psum`` implements the classic int8 ring-style all-reduce:
  1. split the (flattened) gradient into one chunk per device;
  2. ``all_to_all`` the *quantized* chunks (wire bytes / 4 vs f32);
  3. locally dequantize + reduce the owned chunk;
  4. re-quantize and ``all_gather`` the reduced chunks (again int8).
Wire traffic ~ 0.5x tensor size vs 2x for a plain f32 ring all-reduce.

``ErrorFeedback`` keeps the classic residual so the quantization error is
re-injected next step (convergence-preserving; Karimireddy et al.).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["quantize_int8", "dequantize_int8", "ErrorFeedback",
           "ef_compress", "compressed_psum"]


def quantize_int8(x: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


class ErrorFeedback(NamedTuple):
    residual: jax.Array


def ef_compress(g: jax.Array, ef: ErrorFeedback):
    """Error-feedback quantize: returns (q, scale, new_ef)."""
    corrected = g.astype(jnp.float32) + ef.residual
    q, scale = quantize_int8(corrected)
    new_res = corrected - dequantize_int8(q, scale)
    return q, scale, ErrorFeedback(new_res)


def compressed_psum(x: jax.Array, axis_name: str):
    """int8-transport all-reduce over ``axis_name`` (call inside shard_map).

    x: (N,) f32 with N divisible by the axis size."""
    k = jax.lax.axis_size(axis_name)
    n = x.shape[0]
    chunks = x.reshape(k, n // k)
    q, scale = quantize_int8(chunks)                       # int8 (k, n/k)
    # each device receives everyone's copy of its owned chunk
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)                  # (k, n/k) int8
    scales = jax.lax.all_gather(scale, axis_name)          # (k,)
    owned = jnp.sum(q_t.astype(jnp.float32) * scales[:, None], axis=0)  # (n/k,)
    q2, s2 = quantize_int8(owned)
    gathered = jax.lax.all_gather(q2, axis_name)           # (k, n/k) int8
    s_all = jax.lax.all_gather(s2, axis_name)              # (k,)
    return (gathered.astype(jnp.float32) * s_all[:, None]).reshape(n)
