"""Extreme Learning Machine core (paper §2.2, Eq. 1-5).

The ELM readout solves the ridge-regularised least squares
    β = (I/λ + UᵀU)⁻¹ V,   U = HᵀH,  V = HᵀT            (Eq. 2-5)
where H is the hidden-feature matrix (here: the CNN's last pooled map, or
any backbone's features) after the paper's optimal-tanh activation
1.7159·tanh(2/3·H).

Because U and V are sums over rows of H, ELM training is exactly
decomposable over data shards — the E²LM MapReduce (repro.core.e2lm).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels.elm_stats import ops as stats_ops
from repro.layers.norms import optimal_tanh


class ELMStats(NamedTuple):
    """Sufficient statistics of one (partial) dataset."""
    u: jax.Array  # (L, L) f32
    v: jax.Array  # (L, C) f32
    n: jax.Array  # () f32 — row count (for weighted reduce bookkeeping)


def zero_stats(num_features: int, num_classes: int) -> ELMStats:
    return ELMStats(jnp.zeros((num_features, num_features), jnp.float32),
                    jnp.zeros((num_features, num_classes), jnp.float32),
                    jnp.zeros((), jnp.float32))


def zero_stats_stacked(k: int, num_features: int, num_classes: int) -> ELMStats:
    """Zero stats for k members stacked on a leading dim."""
    return ELMStats(
        jnp.zeros((k, num_features, num_features), jnp.float32),
        jnp.zeros((k, num_features, num_classes), jnp.float32),
        jnp.zeros((k,), jnp.float32))


def batch_stats(h, t, *, activation: bool = True, mask=None,
                use_pallas: Optional[bool] = None) -> ELMStats:
    """Map step: stats of one batch. h: (n, L) raw features, t: (n, C).

    ``mask`` (broadcastable to (n,), optional) weights rows into U, V AND n:
    a zero entry drops the row entirely, which is how the padded stacked Map
    phase cancels padding batches (mask = the per-batch validity bit
    broadcast over the batch's rows)."""
    if activation:
        h = optimal_tanh(h)
    if mask is None:
        u, v = stats_ops.elm_stats(h, t, use_pallas=use_pallas)
        return ELMStats(u, v, jnp.asarray(h.shape[0], jnp.float32))
    mask = jnp.broadcast_to(jnp.asarray(mask, jnp.float32), (h.shape[0],))
    u, v = stats_ops.elm_stats(h, t, mask=mask, use_pallas=use_pallas)
    return ELMStats(u, v, jnp.sum(mask))


def add_stats(a: ELMStats, b: ELMStats) -> ELMStats:
    return ELMStats(a.u + b.u, a.v + b.v, a.n + b.n)


def downdate_stats(a: ELMStats, b: ELMStats) -> ELMStats:
    """Rank-DOWNdate: remove ``b``'s contribution from ``a``.

    U and V are plain sums over rows of H, so forgetting a chunk is exact
    subtraction of that chunk's recorded stats — the sliding-window
    streaming Map phase (``repro.stream.window``) evicts old chunks this
    way instead of recomputing the window from scratch. Subtraction in f32
    is not bit-exact against never-adding (float add is not associative),
    which is why the window carries an equivalence gate
    (``SlidingWindowStats.verify``) instead of an equality assert."""
    return ELMStats(a.u - b.u, a.v - b.v, a.n - b.n)


def _cho_solve_beta(u, v, lam: float) -> jax.Array:
    """β = (I/λ + U)⁻¹ V: one Cholesky factorisation, reused for both
    triangular solves. Accepts unbatched (L, L)/(L, C) or member-stacked
    (k, L, L)/(k, L, C) operands.

    The solve always runs through the BATCHED lowering (a unit batch dim is
    added when unbatched): XLA's batched triangular solve differs from the
    unbatched LAPACK path by O(eps) per solve, which compounds over
    per-batch SGD steps — one shared lowering keeps the sequential reference
    and the vmapped stacked Map phase numerically identical."""
    L = u.shape[-1]
    with jax.named_scope(scopes.BETA_SOLVE):
        a = u + jnp.eye(L, dtype=jnp.float32) / lam
        batched = a.ndim == 3
        if not batched:
            a, v = a[None], v[None]
        f = jax.lax.linalg.cholesky(a)
        y = jax.lax.linalg.triangular_solve(f, v, left_side=True, lower=True)
        b = jax.lax.linalg.triangular_solve(f, y, left_side=True, lower=True,
                                            transpose_a=True)
        return b if batched else b[0]


def solve_beta(stats: ELMStats, lam: float) -> jax.Array:
    """Reduce step, Eq. 5: β = (I/λ + U)⁻¹ V via Cholesky (SPD for λ>0).
    Accepts member-stacked stats (u (k, L, L), v (k, L, C) -> β (k, L, C)):
    one batched Cholesky dispatch for all members instead of k round-trips."""
    return _cho_solve_beta(stats.u, stats.v, lam)


def elm_loss(h, beta, t, *, activation: bool = True):
    """Paper Eq. 16: J = 1/2 ||H(z)β − T||² (mean over batch)."""
    if activation:
        h = optimal_tanh(h)
    r = h.astype(jnp.float32) @ beta - t.astype(jnp.float32)
    return 0.5 * jnp.mean(jnp.sum(jnp.square(r), axis=-1))


def predict(h, beta, *, activation: bool = True):
    with jax.named_scope(scopes.READOUT):
        if activation:
            h = optimal_tanh(h)
        return h.astype(jnp.float32) @ beta


def accuracy(scores, labels):
    return jnp.mean((jnp.argmax(scores, axis=-1) == labels).astype(jnp.float32))
