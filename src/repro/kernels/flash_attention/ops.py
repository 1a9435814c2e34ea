"""Public flash-attention op with backend switch (pallas TPU target vs
pure-jnp XLA path used on CPU / in the dry-run)."""
from __future__ import annotations

import jax

from .kernel import flash_attention_pallas
from .ref import attention_ref

__all__ = ["flash_attention"]


def flash_attention(q, k, v, *, causal=True, use_pallas=False, interpret=None):
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal, interpret=interpret)
    return attention_ref(q, k, v, causal=causal)
