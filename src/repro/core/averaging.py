"""Weight averaging — the paper's Reduce step (Alg. 1 line 11, Alg. 2
lines 18-20): Ŵ = 1/k Σ Wᵢ for every parameter (CNN kernels, biases, ELM β,
and — in this framework — any backbone pytree).

Five deployment flavours:
* ``average_trees``       — host-level list-of-members mean.
* ``average_member_dim``  — members stacked on a leading dim (the multi-pod
                            layout: member dim sharded over the 'pod' axis;
                            the mean lowers to one all-reduce across pods).
* ``pmean_members``       — inside shard_map/pjit over a named axis, one
                            pmean per leaf.
* ``psum_weighted_mean_members`` — inside shard_map over the member axis:
                            the whole (weighted) tree mean as ONE collective
                            (flat psum) — the bit-reference for the
                            hierarchical flavour.
* ``hierarchical_psum_weighted_mean_members`` — the same weighted mean
                            staged over a multi-axis member mesh (e.g.
                            ``('host', 'pod')``): one intra-host partial
                            psum then one inter-host psum, so the sync
                            compiles to exactly TWO collectives regardless
                            of global fleet size; the stacked executor's
                            Reduce/sync on every member mesh (no psum
                            where one device holds the members).

Plus the DECENTRALIZED flavour behind ``ReduceConfig(strategy="gossip")``
(arXiv:1504.00981 — no fusion center, no global collective at all):
* ``gossip_member_dim``   — ring-neighbor consensus over the leading
                            member dim (the single-device emulation:
                            ``jnp.roll`` is the ring).
* ``gossip_ring_mix``     — the in-SPMD mixing loop over a named mesh
                            axis: each round is two ``lax.ppermute``
                            neighbor exchanges, zero all-reduces — the
                            stacked executor's gossip sync rides this on a
                            multi-device member mesh.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro import scopes


def average_trees(members: Sequence):
    """Uniform mean, accumulated in f32 regardless of leaf dtype: a bf16
    running sum rounds every add (≈7 mantissa bits), which for k members
    drifts O(k·2⁻⁸) off the true mean — the f32 accumulator keeps the
    uniform path consistent with ``weighted_average_trees``'s
    scale-in-f32."""
    k = float(len(members))
    out = jax.tree.map(lambda a: a.astype(jnp.float32), members[0])
    for m in members[1:]:
        out = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), out, m)
    return jax.tree.map(lambda a, r: (a / k).astype(r.dtype), out, members[0])


def weighted_average_trees(members: Sequence, weights: Sequence[float]):
    """Beyond-paper: shard-size-weighted mean (exact expectation when
    partitions are unequal — see EXPERIMENTS.md §Perf)."""
    total = float(sum(weights))
    scaled = [jax.tree.map(lambda a, w=w: a.astype(jnp.float32) * (w / total), m)
              for m, w in zip(members, weights)]
    out = scaled[0]
    for m in scaled[1:]:
        out = jax.tree.map(jnp.add, out, m)
    ref = members[0]
    return jax.tree.map(lambda a, r: a.astype(r.dtype), out, ref)


def average_member_dim(stacked_params, weights=None):
    """Mean over the leading member dim of every leaf (multi-pod Reduce).

    Optional ``weights`` (length k, any positive scale — normalised here)
    give the weighted mean, the member-dim analogue of
    ``weighted_average_trees``; accumulation is f32 either way. This is the
    Reduce applied both at the end of a run and at every multi-round sync
    (``trainer.make_average_step`` / ``runner.ReduceConfig(rounds=r)``)."""
    with jax.named_scope(scopes.REDUCE):
        if weights is None:
            return jax.tree.map(
                lambda a: jnp.mean(a.astype(jnp.float32),
                                   axis=0).astype(a.dtype),
                stacked_params)
        w = jnp.asarray(weights, jnp.float32)
        w = w / jnp.sum(w)
        return jax.tree.map(
            lambda a: jnp.tensordot(w, a.astype(jnp.float32),
                                    axes=1).astype(a.dtype),
            stacked_params)


def broadcast_member_dim(params, k: int):
    """Replicate averaged params back to all members (next round's init)."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), params)


def pmean_members(params, axis_name: str):
    return jax.tree.map(lambda a: jax.lax.pmean(a, axis_name), params)


def psum_weighted_mean_members(tree, local_weights, axis_name: str):
    """In-SPMD weighted mean over the GLOBAL member dim as ONE collective.

    Call inside shard_map with the member dim sharded over ``axis_name``:
    every leaf has local shape (k_local, ...) and ``local_weights`` is this
    device's (k_local,) slice of the member weight vector. The f32 weighted
    partial sums of every leaf AND the local weight total are raveled into
    a single flat vector and ``psum``-ed once — guaranteed one all-reduce
    in the compiled HLO, unlike a per-leaf ``pmean_members`` which leaves
    the collective count to XLA's combiner. Zero weights drop members
    entirely (the padded-member contract); weights need not be normalised
    (the global weight sum rides the same psum)."""
    parts = jax.tree.map(
        lambda a: jnp.tensordot(local_weights.astype(jnp.float32),
                                a.astype(jnp.float32), axes=1), tree)
    flat, unravel = ravel_pytree((parts, jnp.sum(local_weights,
                                                 dtype=jnp.float32)))
    parts, wsum = unravel(jax.lax.psum(flat, axis_name))
    return jax.tree.map(lambda s, ref: (s / wsum).astype(ref.dtype),
                        parts, tree)


def hierarchical_psum_weighted_mean_members(tree, local_weights,
                                            axis_names: Sequence[str]):
    """The weighted member mean staged over a multi-axis member mesh.

    Same contract as ``psum_weighted_mean_members`` — call inside shard_map
    with the member dim sharded over ``axis_names`` jointly — but the flat
    f32 partial-sum vector is reduced one mesh axis at a time, innermost
    first: on a ``('host', 'pod')`` mesh that is one INTRA-host psum over
    ``'pod'`` (devices sharing a host coordinate) followed by one
    INTER-host psum over ``'host'``. The two psums are data-dependent, so
    XLA's collective combiner cannot merge them: the compiled HLO carries
    exactly ``len(axis_names)`` all-reduces per sync, each scoped to one
    level of the physical hierarchy, regardless of global fleet size. The
    weight total rides the same flat vector, so zero-weight ghost members
    (pad-and-mask) stay arithmetically invisible at both levels.

    With a single axis name this degenerates to the flat one-collective
    reference (identical psum operand, identical summation order)."""
    parts = jax.tree.map(
        lambda a: jnp.tensordot(local_weights.astype(jnp.float32),
                                a.astype(jnp.float32), axes=1), tree)
    flat, unravel = ravel_pytree((parts, jnp.sum(local_weights,
                                                 dtype=jnp.float32)))
    for name in reversed(tuple(axis_names)):   # innermost (intra-host) first
        flat = jax.lax.psum(flat, name)
    parts, wsum = unravel(flat)
    return jax.tree.map(lambda s, ref: (s / wsum).astype(ref.dtype),
                        parts, tree)


# ---------------------------------------------------------------------------
# Gossip (decentralized ring consensus — arXiv:1504.00981)
# ---------------------------------------------------------------------------
#
# The consensus state each node n carries is the PAIR
# (num_n, den_n) = (w_n · x_n, w_n) — weighted numerator and weight mass.
# One mixing round applies the doubly-stochastic 3-point ring stencil
#     s_n <- (s_n + s_{n-1} + s_{n+1}) / 3
# to both. After T rounds node n's ESTIMATE is num_n/den_n; because the
# stencil is doubly stochastic the across-node SUMS of num and den are
# mixing-invariant, so the ratio of sums is the exact global weighted
# mean — that is the published readout, while each node's own iterate
# approaches it geometrically at the mixing matrix's second eigenvalue
# |λ₂| = max_{j≠0} |1 + 2·cos(2πj/p)| / 3 (p ring nodes).

_GOSSIP_EPS = 1e-30     # guards 0/0 on nodes the mixing has not reached


def gossip_mixing_lambda2(p: int) -> float:
    """|λ₂| of the 3-point ring stencil over ``p`` nodes — the geometric
    consensus rate the convergence gate checks against."""
    if p <= 1:
        return 0.0
    j = jnp.arange(1, p)
    return float(jnp.max(jnp.abs(1.0 + 2.0 * jnp.cos(2.0 * jnp.pi * j / p))
                         ) / 3.0)


def gossip_member_dim(stacked_params, weights, rounds: int):
    """Ring gossip over the leading member dim — the single-device
    emulation of the mesh ring (``jnp.roll`` along the member axis plays
    ``lax.ppermute``; node = member here, node = pod on the mesh).

    Returns ``(iterates, published)``: ``iterates`` keeps the member-dim
    layout, member i reset to ITS OWN consensus estimate after ``rounds``
    mixing rounds (the decentralized sync — members do NOT collapse to
    one shared row); ``published`` is the invariant-sum readout
    ``sum(num)/sum(den)`` with the member dim reduced away — the single
    model an operator polls out of the fleet. ``weights=None`` gossips
    the uniform mean. Accumulation is f32 throughout (the averaging
    contract)."""
    if rounds < 1:
        raise ValueError(f"gossip needs rounds >= 1, got {rounds}")
    k = jax.tree.leaves(stacked_params)[0].shape[0]
    w = (jnp.ones((k,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))

    def scale(a):
        return a.astype(jnp.float32) * w.reshape((k,) + (1,) * (a.ndim - 1))

    num = jax.tree.map(scale, stacked_params)
    den = w

    def mix(a):
        return (a + jnp.roll(a, 1, axis=0) + jnp.roll(a, -1, axis=0)) / 3.0

    for _ in range(rounds):
        num, den = jax.tree.map(mix, num), mix(den)
    d = jnp.maximum(den, _GOSSIP_EPS)
    iterates = jax.tree.map(
        lambda s, ref: (s / d.reshape((k,) + (1,) * (s.ndim - 1))
                        ).astype(ref.dtype), num, stacked_params)
    published = jax.tree.map(
        lambda s, ref: (jnp.sum(s, axis=0) / jnp.sum(den)).astype(ref.dtype),
        num, stacked_params)
    return iterates, published


def gossip_ring_mix(tree, local_weights, axis_name: str, rounds: int,
                    ring_size: int):
    """The in-SPMD mixing loop: call inside shard_map with the member dim
    sharded over ``axis_name`` (one ring node per device; this device's
    members pre-aggregate into its local weighted partial). Each of the
    ``rounds`` mixing rounds is exactly TWO ``lax.ppermute`` neighbor
    exchanges (right ring shift + left ring shift) on the flat consensus
    vector — the loop is unrolled so the compiled HLO carries literally
    ``2·rounds`` collective-permutes and ZERO all-reduces
    (``analysis.hlo.check_gossip_sync`` counts them).

    ``ring_size`` is the static size of ``axis_name`` (the permutation
    tables are built at trace time — nothing global is queried on
    device). Returns ``(num, den)``: this node's post-mixing f32
    numerator tree and scalar weight mass. Divide for the node's
    estimate; psum-free."""
    p = int(ring_size)  # repro: allow(host-concretization) — static ring size
    fwd = [(i, (i + 1) % p) for i in range(p)]
    bwd = [(i, (i - 1) % p) for i in range(p)]
    num = jax.tree.map(
        lambda a: jnp.tensordot(local_weights.astype(jnp.float32),
                                a.astype(jnp.float32), axes=1), tree)
    flat, unravel = ravel_pytree((num, jnp.sum(local_weights,
                                               dtype=jnp.float32)))
    for _ in range(rounds):
        left = jax.lax.ppermute(flat, axis_name, fwd)
        right = jax.lax.ppermute(flat, axis_name, bwd)
        flat = (flat + left + right) / 3.0
    return unravel(flat)
