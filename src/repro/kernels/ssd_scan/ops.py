"""Public SSD-scan op: reshapes model-layout tensors to the kernel's
per-head layout and broadcasts B/C groups; backend switch as elsewhere."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import ssd_scan_pallas
from .ref import ssd_scan_ref

__all__ = ["ssd_scan"]


def ssd_scan(x, dt, a, bm, cm, *, use_pallas=False, interpret=None,
             block_q: int = 128):
    """Model layout: x (B,S,H,P), dt (B,S,H), a (H,), bm/cm (B,S,G,N)."""
    B, S, H, P = x.shape
    G, N = bm.shape[2], bm.shape[3]
    rep = H // G
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.tile(a, B)
    bmh = jnp.repeat(bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cmh = jnp.repeat(cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    if use_pallas:
        y = ssd_scan_pallas(xf, dtf, af, bmh, cmh, block_q=block_q,
                            interpret=interpret)
    else:
        y = ssd_scan_ref(xf, dtf, af, bmh, cmh)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
