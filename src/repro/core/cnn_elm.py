"""Distributed Averaging CNN-ELM — the paper's Algorithm 2, faithful.

One member (machine i):
  for epoch j in 1..e:
      reset ΣU = 0, ΣV = 0                               (line 7)
      for batch p in partition i:
          H = CNN features of batch (optimal-tanh applied) (line 9)
          ΣU += HᵀH ; ΣV += HᵀT                          (lines 10-11)
          β = (I/λ + ΣU)⁻¹ ΣV                            (line 12)
          backprop ELM error J = ½||Hβ−T||² into CNN      (line 13)
          W ← W − α ∇W J ;  b ← b − α ∇b J               (line 14)

Note the faithful quirk: β on line 12 is solved from the *running* sums of
the current epoch, so early-epoch batches see a β fitted on little data.
At e=0 (Tables 2/4) no SGD happens at all: one pass accumulates U,V and β
is solved once — pure CNN-as-random-feature ELM.

Reduce (lines 18-20): average every Wᵢ, bᵢ, βᵢ across the k members.

This module is the MATH of the Map phase:

* ``train_member``        — the faithful sequential reference: a host-side
  Python batch loop, three jit dispatches per batch per member.
* ``stacked_epoch_scan``  — the pure stacked scan body: all k members'
  params and ELM stats on a leading member dim, the per-batch step
  ``vmap``-ed over members, the batch loop rolled into one ``lax.scan``.
  Unequal partitions ride through padding + a per-batch validity mask
  (masked batches contribute zero stats and skip the SGD update).
  With ``rows`` each step first gathers its batch on the device from the
  members' rows by row index (``gather_batch``).

HOW that body runs — the epoch/round loop, chunked double-buffered
host→device pipelining, multi-round syncs, member-mesh placement, and
telemetry — lives in ``repro.core.executor`` (``SequentialExecutor`` /
``StackedExecutor``, whose ``_stacked_epoch`` dispatches this body).
The supported entry point is ``repro.core.runner``
(``MapConfig``/``ReduceConfig``/``AveragingRun`` + the batched
``Ensemble`` scoring surface — docs/api.md). The pre-runner
``distributed_cnn_elm``/``evaluate``/``kappa`` shims are GONE — see the
migration table in docs/api.md.

Both Map paths reshuffle per epoch from one rng stream per member (epoch
e = the (e+1)-th permutation of ``default_rng(seed)`` — see
``data.partition``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.core import elm
from repro.core.averaging import (average_member_dim, average_trees,
                                  weighted_average_trees)
from repro.data.partition import Partition, batches
from repro.data.synthetic import one_hot
from repro.kernels import resolve_use_pallas
from repro.models import cnn


@dataclass
class CNNELMModel:
    cnn_params: dict
    beta: jax.Array          # (F, C)


def _bump(telemetry: Optional[dict], key: str = "dispatches", n: int = 1):
    """Count device dispatches into the caller's telemetry dict (runner
    RunResult bookkeeping). ``None`` keeps the engine overhead-free."""
    if telemetry is not None:
        telemetry[key] = telemetry.get(key, 0) + n


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _batch_stats(cfg, cnn_params, x, t, *, use_pallas: Optional[bool] = None):
    h = cnn.features(cfg, cnn_params, x, use_pallas=use_pallas)
    return elm.batch_stats(h, t, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _sgd_step(cfg, cnn_params, beta, x, t, lr, *,
              use_pallas: Optional[bool] = None):
    """Line 13-14: one SGD step on the ELM least-squares error."""
    def loss(p):
        h = cnn.features(cfg, p, x, use_pallas=use_pallas)
        return elm.elm_loss(h, beta, t)

    with jax.named_scope(scopes.SGD_UPDATE):
        val, grads = jax.value_and_grad(loss)(cnn_params)
        new = jax.tree.map(lambda p, g: p - lr * g, cnn_params, grads)
    return new, val


def train_member(cfg, cnn_params, part: Partition, *, epochs: int,
                 lr_schedule, batch_size: int, seed=0,
                 use_pallas: Optional[bool] = None,
                 telemetry: Optional[dict] = None,
                 return_stats: bool = False):
    """Algorithm 2 inner loop for one machine. epochs=0 -> ELM-only pass.
    Epoch e draws the (e+1)-th permutation of ``default_rng(seed)`` — a
    fresh shuffle every epoch, mirrored exactly by the stacked path
    (``seed`` may be a live ``np.random.Generator``, consumed in place —
    the elastic runner resumes a member's stream across round blocks that
    way). ``telemetry`` counts the host→device jit dispatches this loop
    issues (3 per batch with SGD: stats, β solve, SGD step).
    ``return_stats`` additionally returns the final-epoch ``ELMStats`` β
    was solved from — ``(model, stats)`` — for checkpointing and the
    E²LM/elastic stats merges."""
    F = cnn.feature_dim(cfg)
    C = cfg.num_classes
    use_pallas = resolve_use_pallas(use_pallas)

    # one live stream for all epochs: each one_pass draws the next
    # permutation (epoch e = the (e+1)-th draw of default_rng(seed))
    rng = np.random.default_rng(seed)

    def one_pass(params, solve_each_batch: bool, lr: Optional[float]):
        stats = elm.zero_stats(F, C)
        beta = jnp.zeros((F, C), jnp.float32)
        for x, y in batches(part, batch_size, seed=rng):
            t = jnp.asarray(one_hot(y, C))
            xj = jnp.asarray(x)
            stats = elm.add_stats(stats, _batch_stats(cfg, params, xj, t,
                                                      use_pallas=use_pallas))
            _bump(telemetry)
            if solve_each_batch:
                beta = elm.solve_beta(stats, cfg.elm_lambda)
                params, _ = _sgd_step(cfg, params, beta, xj, t,
                                      jnp.asarray(lr, jnp.float32),
                                      use_pallas=use_pallas)
                _bump(telemetry, n=2)
        return params, stats

    if epochs == 0:
        cnn_params, stats = one_pass(cnn_params, False, None)
    else:
        stats = None
        for e in range(epochs):
            cnn_params, stats = one_pass(cnn_params, True,
                                         float(lr_schedule(e)))
    _bump(telemetry)
    model = CNNELMModel(cnn_params, elm.solve_beta(stats, cfg.elm_lambda))
    return (model, stats) if return_stats else model


@dataclass
class StackedMembers:
    """All k members with every array stacked on a leading member dim."""
    cnn_params: dict         # leaves: (k, ...)
    beta: jax.Array          # (k, F, C)

    @property
    def k(self) -> int:
        return self.beta.shape[0]

    def member(self, i: int) -> CNNELMModel:
        return CNNELMModel(jax.tree.map(lambda a: a[i], self.cnn_params),
                           self.beta[i])

    def unstack(self) -> List[CNNELMModel]:
        return [self.member(i) for i in range(self.k)]

    def averaged(self) -> CNNELMModel:
        """Reduce: the mean over the member dim (one all-reduce when the
        member dim is sharded across pods)."""
        avg_cnn, avg_beta = average_member_dim((self.cnn_params, self.beta))
        return CNNELMModel(avg_cnn, avg_beta)


@dataclass
class StepRecord:
    """The members' CNN parameters at the start of every SGD step of a
    run's last epoch, in the order the steps ran: leaves (nb, k, ...)
    beside ``mask`` (nb, k), False on a padding step (a member with fewer
    batches than the longest, or the tail of a chunked epoch), where the
    params pass through unchanged. The params after the last step are the
    trained members' own."""
    params: dict
    mask: np.ndarray


def stack_models(models: Sequence[CNNELMModel]) -> StackedMembers:
    """Host-level models -> the stacked member layout (leaves gain a
    leading k dim) so they can ride the batched scoring surface."""
    cnn_k = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[m.cnn_params for m in models])
    beta_k = jnp.stack([jnp.asarray(m.beta) for m in models])
    return StackedMembers(cnn_k, beta_k)


def stacked_epoch_scan(cfg, params_k, stats_k, xb, tb, mb, lr, *,
                       solve_each_batch: bool, use_pallas: bool,
                       masked: bool, rows=None):
    """THE stacked scan body: one epoch chunk for ALL members in one
    program. Pure — ``executor._stacked_epoch`` dispatches it over the
    member mesh, each device scanning only its own members (the whole
    member dim on one device).

    xb: (nb, k, B, H, W[, C]) batches, tb: (nb, k, B, C) one-hot targets,
    mb: (nb, k) per-batch validity (1 = real, 0 = padding) — scan over nb,
    vmap over k. Per batch and member this replays Algorithm 2 lines 9-14
    exactly: accumulate stats, solve β from the running sums (one Cholesky
    factor, reused for the solve), SGD on the ELM least-squares error.
    With ``masked`` (static) a zero-mask batch contributes nothing to
    U/V/n and leaves the params untouched, so members with fewer real
    batches coast through their padding bit-identically; ``masked=False``
    (all shards equal, no chunk padding) keeps the mask out of the compute
    graph entirely.

    ``rows=(xs, ys)``: the members' flat rows and int labels, resident on
    the device, one array per member (``gather_batch``);
    ``xb`` and ``tb`` are then (nb, k, B) row indices of the batches and
    of their labels, and each step gathers its batch in the same
    program.

    Returns ``(params_k, stats_k)``; with ``solve_each_batch`` also the
    step record: every member's params at the start of every step, leaves
    (nb, k, ...), written under the ``step_record`` scope. Where the scan
    takes no SGD step it carries no record and writes nothing."""
    def member_step(params, stats, x, t, m):
        h = cnn.features(cfg, params, x, use_pallas=use_pallas)
        stats = elm.add_stats(stats, elm.batch_stats(
            h, t, mask=(m if masked else None), use_pallas=use_pallas))
        if solve_each_batch:
            beta = elm.solve_beta(stats, cfg.elm_lambda)

            def loss(p):
                hp = cnn.features(cfg, p, x, use_pallas=use_pallas)
                return elm.elm_loss(hp, beta, t)

            with jax.named_scope(scopes.SGD_UPDATE):
                grads = jax.grad(loss)(params)
                if masked:
                    params = jax.tree.map(
                        lambda p, g: jnp.where(m > 0, p - lr * g, p),
                        params, grads)
                else:
                    params = jax.tree.map(lambda p, g: p - lr * g, params,
                                          grads)
        return params, stats

    def body(carry, batch):
        p, s, rec = carry
        x, t, m, j = batch
        if rows is not None:
            x, t = gather_batch(*rows, x, t, m, cfg)
        if solve_each_batch:
            # written here, not emitted as the scan's ys: the scan's own
            # writes of ys carry no scope of the body
            with jax.named_scope(scopes.STEP_RECORD):
                rec = jax.tree.map(
                    lambda r, a: jax.lax.dynamic_update_index_in_dim(
                        r, a, j, 0), rec, p)
        p, s = jax.vmap(member_step)(p, s, x, t, m)
        return (p, s, rec), None

    nb = mb.shape[0]
    rec = steps = None
    if solve_each_batch:
        # every row is written before it is read; broadcasting the params
        # keeps their varying mesh axes inside a shard_map
        rec = jax.tree.map(lambda a: jnp.broadcast_to(a, (nb,) + a.shape),
                           params_k)
        steps = jnp.arange(nb)
    (params_k, stats_k, rec), _ = jax.lax.scan(
        body, (params_k, stats_k, rec), (xb, tb, mb, steps))
    return (params_k, stats_k) if rec is None else (params_k, stats_k, rec)


def gather_batch(xs, ys, xi, yi, m, cfg):
    """One scan step's batch for every member, gathered on the device:
    images x (k, B, H, W, C) and one-hot targets t (k, B, classes), the
    values of the host build (``partition.padded_stacked_epoch_batches``).
    ``xs``/``ys`` hold each member's rows, flat, and int labels: tuples
    of k arrays (n, H·W·C) and (n,), members of any size (on a mesh, the
    device's part of each member slot, ``executor._put_partitions``).
    ``xi``/``yi`` (k, B) index them (the executor passes the rows of
    ``partition.padded_epoch_indices`` as both). A padding batch (``m``
    0) is zeroed, rows and labels, as the host zero-fills it. Flat rows
    gather as whole rows; on a TPU v5e the (n, H, W) layout took 20x the
    device time (PERF.md)."""
    with jax.named_scope(scopes.EPOCH_GATHER):
        def take(rows, idx):
            return jnp.stack([r[idx[i]] for i, r in enumerate(rows)])

        real = (m > 0)[:, None]
        n = cfg.image_size
        x = take(xs, xi).reshape(xi.shape + (n, n, cfg.image_channels))
        x = jnp.where(real[..., None, None, None], x, 0)
        y = jnp.where(real, take(ys, yi), 0)
        return x, jax.nn.one_hot(y, cfg.num_classes, dtype=jnp.float32)


def average_models(models: Sequence[CNNELMModel],
                   weights: Optional[Sequence[float]] = None) -> CNNELMModel:
    """Reduce: lines 18-20 — average CNN weights, biases AND β. Optional
    ``weights`` (e.g. shard sizes) give the exact expectation over unequal
    partitions — the paper's 'training data distribution needs to be
    carefully selected' drawback."""
    if weights is not None:
        if len(weights) != len(models):
            raise ValueError(f"{len(weights)} weights for {len(models)} models")
        avg = weighted_average_trees(
            [(m.cnn_params, m.beta) for m in models], weights)
        return CNNELMModel(*avg)
    avg_cnn = average_trees([m.cnn_params for m in models])
    avg_beta = average_trees([m.beta for m in models])
    return CNNELMModel(avg_cnn, avg_beta)
