"""The pre-jitted, bucket-shaped ensemble scoring engine.

``BucketedScorer`` owns ONE fresh ``jax.jit`` instance of the stacked
k-member scoring program (the same vmap body as
``runner.Ensemble``'s ``_scores_stacked``) and only ever dispatches it at
``BucketLadder`` shapes, so its compile count is bounded by the ladder
length for the lifetime of the process — the compile-count guarantee
``docs/serving.md`` documents and ``tests/test_serve.py`` +
``benchmarks/serve_ensemble.py`` assert (``compile_count()`` reads the
jit cache directly; it is not a heuristic).

Weight hot-swap rides the same cache: ``swap_members`` replaces the
stacked params with a SHAPE-IDENTICAL tree (anything else is refused),
which hits the already-compiled programs — a live endpoint tracks a
training run's checkpoints with zero recompiles and zero dropped
requests (``repro.serve.hot_reload``).

Padding contract: a batch of n rows pads with zero rows up to
``bucket_for(n)``; every CNN-ELM score is row-independent (per-image
features, row-wise ELM readout), and the padded rows are sliced off the
(k, bucket, C) score block BEFORE any combine — so padding can never
vote, and the n real rows' scores are bit-equal across bucket choices
of the same compiled program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import scopes
from repro.core import elm
from repro.core.cnn_elm import StackedMembers
from repro.kernels import resolve_use_pallas
from repro.models import cnn
from repro.serve.bucketing import BucketLadder

COMBINES = ("mean", "vote")


def combine_block(scores: np.ndarray, combine: str,
                  num_classes: int) -> np.ndarray:
    """(k, n, C) member scores -> (n,) ensemble labels.

    ``"mean"`` — argmax of the mean member score. ``"vote"`` — majority
    vote over member argmaxes. BOTH resolve ties to the LOWEST class
    index (np.argmax convention) — the documented ``runner.Ensemble``
    rule, pinned by ``tests/test_serve.py`` through the padded path."""
    if combine == "mean":
        return scores.mean(axis=0).argmax(-1)
    if combine != "vote":
        raise ValueError(f"combine must be one of {COMBINES}, "
                         f"got {combine!r}")
    preds = scores.argmax(-1)                       # (k, n)
    k, n = preds.shape
    votes = np.zeros((n, num_classes), np.int64)
    np.add.at(votes, (np.tile(np.arange(n), k), preds.reshape(-1)), 1)
    return votes.argmax(-1)


@dataclass
class SwapRejected(ValueError):
    """A hot-swap candidate whose tree/shapes/dtypes differ from the
    serving weights — applying it would force a recompile (or crash) on
    the hot path, so the scorer refuses it."""
    reason: str

    def __str__(self):
        return self.reason


class BucketedScorer:
    """k stacked CNN-ELM members behind a compile-bounded scoring entry.

    Build via ``runner.Ensemble.bucketed_scorer(...)`` (or directly from
    a ``StackedMembers``). ``warmup()`` pre-compiles every bucket off the
    serving path; after it, NO call ever compiles again —
    ``assert_compile_budget()`` is the regression guard."""

    def __init__(self, cfg, members: StackedMembers, *,
                 max_batch: int = 64, ladder: Optional[BucketLadder] = None,
                 use_pallas: Optional[bool] = None):
        self.cfg = cfg
        self.ladder = ladder if ladder is not None \
            else BucketLadder(max_batch)
        self._use_pallas = resolve_use_pallas(use_pallas)
        self._members = members
        self._struct = self._signature(members)
        up = self._use_pallas

        def scores(cnn_params_k, beta_k, x):
            def one(p, b):
                h = cnn.features(cfg, p, x, use_pallas=up)
                return elm.predict(h, b)
            return jax.vmap(one)(cnn_params_k, beta_k)

        # the ONE sanctioned jit in repro.serve: this fresh instance IS
        # the budget-disciplined program — its cache holds exactly this
        # scorer's compiled programs, so compile_count() is exact
        # repro: allow(bare-jit-in-serve)
        self._fn = jax.jit(scores)

    # -- weights ------------------------------------------------------

    @staticmethod
    def _signature(members: StackedMembers):
        return jax.tree.map(lambda a: (jnp.shape(a), jnp.asarray(a).dtype),
                            (members.cnn_params, members.beta))

    @property
    def members(self) -> StackedMembers:
        return self._members

    @property
    def k(self) -> int:
        return self._members.k

    def validate_members(self, members: StackedMembers):
        """Raise ``SwapRejected`` unless ``members`` is shape/dtype/tree
        identical to the serving weights (the precondition for a
        zero-recompile swap)."""
        if self._signature(members) != self._struct:
            raise SwapRejected(
                "hot-swap refused: candidate weights do not match the "
                "serving tree (arch/k/shape/dtype change) — deploy a new "
                "scorer instead")

    def swap_members(self, members: StackedMembers):
        """Replace the serving weights. Shape/dtype-identical trees hit
        the already-compiled bucket programs — zero recompiles; anything
        else raises ``SwapRejected`` (a different arch or k is a new
        endpoint, not a hot swap)."""
        self.validate_members(members)
        self._members = members

    # -- scoring ------------------------------------------------------

    def warmup(self):
        """Compile every bucket shape now, off the serving path."""
        h, w, c = (self.cfg.image_size, self.cfg.image_size,
                   self.cfg.image_channels)
        shape = (h, w) if c == 1 else (h, w, c)
        for b in self.ladder.buckets:
            self.score_block(np.zeros((b,) + shape, np.float32))
        return self

    def score_block(self, x) -> np.ndarray:
        """(k, n, C) member scores of n <= max_batch images — ONE
        dispatch at the bucket shape, padded rows already sliced off."""
        with TraceAnnotation(scopes.SERVE_SCORE):
            with TraceAnnotation(scopes.SERVE_DISPATCH):
                padded, n = self.ladder.pad_block(np.asarray(x, np.float32))
                s = self._fn(self._members.cnn_params, self._members.beta,
                             jnp.asarray(padded))
            with TraceAnnotation(scopes.SERVE_FETCH):
                return np.asarray(s)[:, :n]

    def predict_block(self, x, combine: str = "mean") -> np.ndarray:
        """(n,) combined ensemble labels of one batch."""
        return combine_block(self.score_block(x), combine,
                             self.cfg.num_classes)

    # -- the compile-count guarantee ----------------------------------

    def compile_count(self) -> int:
        """Distinct compiled programs behind this scorer — read straight
        off the jit cache (one entry per dispatched shape signature)."""
        return int(self._fn._cache_size())

    def assert_compile_budget(self):
        """The regression guard: raise if the scorer ever compiled more
        programs than the ladder has buckets (i.e. some dispatch escaped
        the pad ladder). Delegates to the Tier-2 auditor so the serving
        check and the CI audit are the same predicate; the raised
        ``ContractViolation`` is an ``AssertionError`` subclass."""
        from repro.analysis.hlo import ContractViolation, \
            check_compile_budget
        check = check_compile_budget(self)
        if not check.ok:
            raise ContractViolation(
                f"bucketed scoring recompiled: {check.detail}")
        return self.compile_count()
