"""Pure-jnp oracle for the fused ELM-stats kernel (paper Eq. 3/4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def elm_stats_ref(h, t, mask=None):
    """U = Hᵀ diag(mask) H, V = Hᵀ diag(mask) T. ``mask=None`` means all-ones;
    row weights enter ONCE (the left operand), so binary masks drop rows and
    fractional masks weight them — never square them."""
    hf = h.astype(jnp.float32)
    tf = t.astype(jnp.float32)
    hm = hf if mask is None else hf * mask.astype(jnp.float32)[:, None]
    # contract the row axis in place: after an explicit transpose XLA lays
    # H out anew for a one-member batch (a mesh shard) and its GEMM then
    # sums in another order than the k-member batch on one device
    rows = (((0,), (0,)), ((), ()))
    return (jax.lax.dot_general(hm, hf, rows),
            jax.lax.dot_general(hm, tf, rows))
