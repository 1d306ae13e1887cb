"""Jitted wrapper: ELM sufficient statistics (U, V) from one data shard.

``use_pallas=None`` (auto, the default) runs the fused Pallas kernel
compiled on TPU and the XLA reference elsewhere; forcing ``use_pallas=True``
off-TPU runs the kernel in interpret mode. See ``repro.kernels``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro import scopes
from repro.kernels import resolve_interpret, resolve_use_pallas
from repro.kernels.elm_stats import ref
from repro.kernels.elm_stats.kernel import elm_stats as _pallas_stats


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _elm_stats(h, t, mask, *, use_pallas: bool, interpret: bool):
    with jax.named_scope(scopes.ELM_STATS):
        if use_pallas:
            return _pallas_stats(h, t, mask, interpret=interpret)
        return ref.elm_stats_ref(h, t, mask)


def elm_stats(h, t, *, mask=None, use_pallas: Optional[bool] = None):
    """h: (n, L) hidden features, t: (n, C) targets -> (U, V) in f32.
    ``mask``: optional (n,) per-row weights — U = Hᵀdiag(m)H, V = Hᵀdiag(m)T
    (zero weight drops the row; the padded stacked Map phase's contract).

    Policy (use_pallas and interpret) resolves outside the jit (resolved
    bools = static cache keys) so env overrides apply on the next call."""
    return _elm_stats(h, t, mask, use_pallas=resolve_use_pallas(use_pallas),
                      interpret=resolve_interpret(None))
