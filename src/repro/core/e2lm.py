"""E²LM — Elastic ELM via MapReduce (paper §2.2, Eq. 3-5; Xin et al. 2015).

Three reduce flavours, matching how the framework is deployed:

* ``reduce_stats``      — host-level sum over a list of per-shard stats
                          (the literal MapReduce of the paper).
* ``psum_stats``        — in-SPMD reduce over a mesh axis: every device
                          computes stats of its local rows, one all-reduce
                          yields the global U, V. Exact, one collective.
                          The stacked Map-phase executor builds its global
                          readout on this (``executor.StackedExecutor
                          .e2lm_global_beta``: psum the members' final
                          stats over the member mesh, solve once — the
                          no-partition β straight from the Map phase).
* ``OSELMState``        — OS-ELM (Liang et al. 2006) sequential/streaming
                          update via Sherman-Morrison-Woodbury, referenced
                          by the paper as the block-sequential alternative.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.elm import ELMStats, add_stats, solve_beta, zero_stats
from repro.layers.norms import optimal_tanh


def reduce_stats(shards: Sequence[ELMStats]) -> ELMStats:
    out = shards[0]
    for s in shards[1:]:
        out = add_stats(out, s)
    return out


def psum_stats(local: ELMStats, axis_name) -> ELMStats:
    """Cross-member stats sum over one named axis or a tuple of axes (the
    hierarchical ('host', 'pod') member mesh) — ``jax.lax.psum`` takes
    both forms."""
    return ELMStats(jax.lax.psum(local.u, axis_name),
                    jax.lax.psum(local.v, axis_name),
                    jax.lax.psum(local.n, axis_name))


def mapreduce_solve(shards: Sequence[ELMStats], lam: float):
    """The full E²LM pipeline at host level: reduce then solve."""
    return solve_beta(reduce_stats(shards), lam)


# ---------------------------------------------------------------------------
# OS-ELM: streaming block updates (the non-MapReduce baseline the paper cites)
# ---------------------------------------------------------------------------

class OSELMState(NamedTuple):
    p: jax.Array     # (L, L) running (I/λ + HᵀH)⁻¹
    beta: jax.Array  # (L, C)


def oselm_init(num_features: int, num_classes: int, lam: float) -> OSELMState:
    return OSELMState(lam * jnp.eye(num_features, dtype=jnp.float32),
                      jnp.zeros((num_features, num_classes), jnp.float32))


def oselm_update(state: OSELMState, h, t, *, activation: bool = True) -> OSELMState:
    """Woodbury block update:
    P ← P − P Hᵀ (I + H P Hᵀ)⁻¹ H P;  β ← β + P Hᵀ (T − H β)."""
    if activation:
        h = optimal_tanh(h)
    h = h.astype(jnp.float32)
    t = t.astype(jnp.float32)
    ph = state.p @ h.T                                   # (L, n)
    gram = h @ ph + jnp.eye(h.shape[0], dtype=jnp.float32)
    cho = jax.scipy.linalg.cho_factor(gram)
    k = jax.scipy.linalg.cho_solve(cho, ph.T)            # (n, L)
    p_new = state.p - ph @ k
    beta_new = state.beta + p_new @ h.T @ (t - h @ state.beta)
    return OSELMState(p_new, beta_new)
