"""The Map-phase execution layer — how Algorithm 2's k members actually run.

``repro.core.cnn_elm`` owns the MATH (the per-batch step, the stacked scan
body, the β solve); this module owns the ORCHESTRATION: the epoch/round
loop, host→device chunk pipelining, telemetry, inter-round syncs and the
Reduce. The runner (``repro.core.runner``) selects an executor by name:

* ``SequentialExecutor`` (``backend="sequential"``) — the faithful
  reference: a host Python loop over ``cnn_elm.train_member``, three jit
  dispatches per batch per member.
* ``StackedExecutor`` (``backend="stacked"`` and ``"mesh"``) — all k
  members stacked on a leading member dim and placed on a MEMBER MESH:
  the member dim is split over the mesh's ``'pod'`` axis (``('host',
  'pod')`` jointly on the hierarchical mesh), one donated scan per epoch
  chunk, the β solve, the Reduce, the syncs and the validation scoring
  each ONE program. The two names differ only in the default mesh: the
  first device for ``"stacked"`` (one chip is the member mesh of one
  device), every device (``launch.mesh.make_member_mesh()``) for
  ``"mesh"``; a ``mesh`` passed in is used as given.

The member mesh. Each program reads the mesh from the context
(``jax.set_mesh``, set around every dispatch) and runs its body over each
device's own members: ``shard_map``-ed over the mesh, or called whole
where one device holds the members, so on one chip every program is the
plain jit of its body. The epoch scan holds ZERO collectives (members are
independent until the Reduce); the β Cholesky runs where the members
live; the Reduce — the final average and every ``rounds=r`` inter-round
sync — is ONE all-reduce on a flat mesh, TWO (intra-host, then
inter-host) on the ``('host', 'pod')`` mesh, and none on one device
(``averaging.hierarchical_psum_weighted_mean_members`` over the member
axes that span devices).

Member padding: k members on a mesh of p member devices are padded to
``k_pad = ceil(k/p)·p`` slots (``k_pad == k`` on one device). Padded
members carry zero batches under a zero validity mask (they never update
and accumulate zero stats) and weight 0 in every Reduce, so they are
arithmetically invisible; what the run hands back strips them. Simulate
devices on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see ``repro.launch.mesh.force_host_device_count`` /
``REPRO_HOST_DEVICES``).

Telemetry contract (a plain dict, shared with the runner's ``RunResult``):
``dispatches`` counts the Map phase's device programs (epoch chunks, β
solves, syncs, validation batches; the averaged model's Reduce is the
run's output and is not counted); ``round_syncs`` the inter-round
average+broadcast programs.

Epoch build: where the partitions, as the executor places them, fit
within half of each device's memory (``memory_stats()["bytes_limit"]``;
a backend that reports none, such as the CPU, counts as fitting),
``execute`` uploads them once and each epoch sends only its row indices:
each scan step gathers its batch on the device, in the same program
(``cnn_elm.gather_batch``). The rows go up per device from each member's
own arrays, one array per member slot (``_put_partitions``), so no member
is stacked on the host. Otherwise each epoch is built on the host and its
batches are uploaded chunk by chunk, so data larger than the device still
runs with ``chunk_batches``. Both feed the scan the same values.
Telemetry counts ``device_epoch_builds`` or ``host_epoch_builds`` per
epoch.

Tracing: the programs carry the device scopes of ``repro.scopes``, and
``StackedExecutor.execute`` opens the host spans of the stacked loop.

Fault tolerance (``plan.checkpoint`` / ``plan.start_round`` /
``plan.completed``): the stacked executor saves one atomic
``checkpoint.run_state`` round file per averaging round — the pre-sync
member snapshot + final-epoch stats + averaged model, and (non-final
rounds) the post-sync params every member was reset to. Resume places
those post-sync params as the shared init, skips the completed rounds and
fast-forwards each member's rng stream by the skipped epochs' permutation
draws, which reproduces the uninterrupted run bit-for-bit (the sync
broadcasts one identical row to every member slot, so the saved row IS
the device state). The sequential reference checkpoints per MEMBER
instead (its unit of work); ``plan.completed`` hands restored members
back in and only the missing ones train.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.profiler import TraceAnnotation
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import scopes
from repro.checkpoint import run_state
from repro.core import elm
from repro.core.averaging import (broadcast_member_dim, gossip_member_dim,
                                  gossip_ring_mix,
                                  hierarchical_psum_weighted_mean_members)
from repro.core.cnn_elm import (CNNELMModel, StackedMembers, StepRecord,
                                _bump, average_models, stack_models,
                                stacked_epoch_scan, train_member)
from repro.core.e2lm import psum_stats
from repro.data.partition import (chunk_scan_major, padded_epoch_indices,
                                  padded_stacked_epoch_batches)
from repro.data.synthetic import one_hot
from repro.kernels import resolve_use_pallas
from repro.launch.mesh import make_member_mesh
from repro.models import cnn

BACKENDS = ("sequential", "stacked", "mesh")


# ---------------------------------------------------------------------------
# Plan + outcome
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CheckpointConfig:
    """Per-round checkpoint policy (``repro.checkpoint.run_state`` files).

    ``dir`` — where the atomic ``round-<r>.npz`` (and, on the sequential
    backend, ``member-<i>.npz``) files land. ``every`` — save round r when
    ``(r + 1) % every == 0``; the final round always saves. ``after_save``
    — fault-injection hook ``(unit, index, path)`` called the moment a
    checkpoint is durably renamed into place (``unit`` is ``"round"`` or
    ``"member"``); ``repro.core.faults`` raises ``InjectedCrash`` from it
    to simulate preemption at the tightest possible point."""
    dir: str
    every: int = 1
    after_save: Optional[Callable] = None

    def __post_init__(self):
        if self.every < 1:
            raise ValueError(f"every must be >= 1, got {self.every}")


@dataclass(frozen=True)
class ExecutionPlan:
    """Everything one Map/Reduce execution needs, backend-agnostic.

    ``on_round(r, snapshot, averaged)`` fires after each round's epochs AND
    its sync bookkeeping with two lazy, cached zero-arg closures:
    ``snapshot()`` → the round's pre-sync ``StackedMembers`` (β solved on
    first call — rounds nobody snapshots skip the Cholesky), ``averaged()``
    → the round's (weighted) averaged ``CNNELMModel`` via the executor's
    Reduce (the host mean on sequential, the member-mesh Reduce program
    on stacked).
    ``reduce_weights`` drive BOTH the inter-round syncs and ``averaged()``.

    Fault-tolerance fields: ``checkpoint`` turns on per-round (stacked
    layouts) / per-member (sequential) saving; ``start_round`` resumes a
    stacked run at that round — ``init_params`` must then be the restored
    post-sync params and the skipped rounds' rng draws are burned so the
    continuation is bit-identical; ``completed`` hands the sequential
    backend already-trained members ``{i: (model, stats)}`` to skip.
    ``member_seeds`` overrides the positional ``seed + i`` rule and
    ``start_epochs`` fast-forwards each member's stream by that many
    permutation draws — the elastic runner's stream-continuation contract
    (a member keeps ONE rng stream across round blocks).
    ``member_init`` gives each member its OWN initial params (a k-list of
    trees) instead of broadcasting the shared ``init_params`` — the
    streaming runner's block-continuation contract (members diverge
    between syncs).

    Reduce-strategy fields (``repro.core.reduce_strategies``):
    ``weight_fn(r, snapshot, val_errors)`` resolves the round's member
    weights LAZILY from trained state — ``snapshot``/``val_errors`` are
    the round's cached closures (``val_errors()`` scores ``validation``,
    an (x, y) held-out slice, with the member-mesh scoring program
    ``_val_predict`` — and returns the (k,)
    misclassification rates). When ``weight_fn`` is None the static
    ``reduce_weights`` apply, bit-identical to the pre-registry path.
    ``gossip_rounds`` switches the COMBINE: every sync and ``averaged()``
    runs the decentralized ring-consensus program instead of the global
    weighted mean — members keep their own consensus iterates between
    rounds (so per-round checkpointing, whose resume contract assumes
    one shared post-sync row, is rejected), and the published model is
    the mixing-invariant ratio-of-sums readout.
    """
    epochs: int = 0
    lr_schedule: Optional[Callable[[int], float]] = None
    batch_size: int = 32
    seed: int = 1000                 # member i's stream = default_rng(seed+i)
    use_pallas: Optional[bool] = None
    chunk_batches: Optional[int] = None
    rounds: int = 1
    reduce_weights: Optional[Sequence[float]] = None
    on_round: Optional[Callable] = None
    telemetry: Optional[dict] = None
    checkpoint: Optional[CheckpointConfig] = None
    start_round: int = 0
    completed: Optional[dict] = None
    member_seeds: Optional[Sequence[int]] = None
    start_epochs: Optional[Sequence[int]] = None
    member_init: Optional[Sequence] = None
    weight_fn: Optional[Callable] = None
    validation: Optional[tuple] = None      # (x, y) held-out slice
    gossip_rounds: Optional[int] = None


@dataclass
class MapOutcome:
    """What an executor hands back: the k trained members, the live
    ``StackedMembers`` on the stacked executor (None on sequential), and
    the final-epoch ``ELMStats`` of every member (host, member-stacked,
    padding stripped) — what β was solved from, for checkpointing and the
    elastic/E²LM stats merges. ``record``: the stacked executor's step
    record of the last SGD epoch (None where no SGD step ran, and on
    sequential)."""
    members: List[CNNELMModel]
    stacked: Optional[StackedMembers]
    stats: Optional[elm.ELMStats] = None
    record: Optional[StepRecord] = None


def make_executor(backend: str, mesh=None):
    """Executor registry: ``backend`` ∈ ``BACKENDS``. ``mesh`` is the
    member mesh (axis ``'pod'`` required) of the stacked executor, which
    both ``"stacked"`` and ``"mesh"`` build; None gives each name's
    default. ``"sequential"`` ignores it."""
    if backend == "sequential":
        return SequentialExecutor()
    if backend in ("stacked", "mesh"):
        return StackedExecutor(mesh=mesh, backend=backend)
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")


# ---------------------------------------------------------------------------
# Shared per-member stream plumbing
# ---------------------------------------------------------------------------

def _member_seeds(plan: ExecutionPlan, k: int) -> List[int]:
    if plan.member_seeds is None:
        return [plan.seed + i for i in range(k)]
    seeds = list(plan.member_seeds)
    if len(seeds) != k:
        raise ValueError(f"{len(seeds)} member_seeds for {k} partitions")
    return seeds


def _member_inits(plan: ExecutionPlan, k: int) -> Optional[List]:
    """Validated per-member init trees, or None for the shared init."""
    if plan.member_init is None:
        return None
    inits = list(plan.member_init)
    if len(inits) != k:
        raise ValueError(f"{len(inits)} member_init trees for "
                         f"{k} partitions")
    return inits


def _stream_burns(plan: ExecutionPlan, k: int, per_round: int) -> List[int]:
    """Permutation draws to fast-forward each member stream by before the
    first epoch: explicit per-member ``start_epochs`` (elastic
    continuation), else the skipped ``start_round`` rounds (resume)."""
    if plan.start_epochs is None:
        return [plan.start_round * per_round] * k
    burns = list(plan.start_epochs)
    if len(burns) != k:
        raise ValueError(f"{len(burns)} start_epochs for {k} partitions")
    return burns


# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# Sequential: the faithful host-loop reference
# ---------------------------------------------------------------------------

class SequentialExecutor:
    """One ``cnn_elm.train_member`` host loop per member — the Algorithm 2
    reference every fast path is tested against. No sync points between
    members, so multi-round averaging is unsupported; fault tolerance is
    per MEMBER instead (each member's training is self-contained, so a
    member checkpoint is a complete unit of restartable work)."""

    name = "sequential"
    supports_rounds = False

    def execute(self, cfg, init_params, partitions, plan: ExecutionPlan
                ) -> MapOutcome:
        if plan.rounds > 1:
            # direct-drive callers get the same guard the runner applies —
            # silently running rounds=1 would misreport parallel-SGD runs
            raise ValueError(
                "rounds > 1 needs a stacked layout (StackedExecutor) — "
                "the sequential reference has no sync point between "
                "members")
        if plan.start_round:
            raise ValueError(
                "start_round resume is a stacked-layout contract; the "
                "sequential backend resumes via plan.completed member "
                "checkpoints")
        if plan.gossip_rounds is not None:
            raise ValueError(
                "the gossip combine mixes a member/pod ring — the "
                "sequential reference has no stacked member dim to mix "
                "over; use backend='stacked' or 'mesh'")
        k = len(partitions)
        seeds = _member_seeds(plan, k)
        burns = _stream_burns(plan, k, 0)
        inits = _member_inits(plan, k)
        ck = plan.checkpoint
        done = dict(plan.completed or {})
        meta = run_state.run_fingerprint(
            self.name, partitions, seed=plan.seed, epochs=plan.epochs,
            rounds=plan.rounds, batch_size=plan.batch_size)
        members: List[CNNELMModel] = []
        all_stats = []
        for i, p in enumerate(partitions):
            if i in done:
                model, stats = done[i]
            else:
                rng = np.random.default_rng(seeds[i])
                for _ in range(burns[i]):
                    rng.permutation(len(p.x))
                model, stats = train_member(
                    cfg, init_params if inits is None else inits[i], p,
                    epochs=plan.epochs,
                    lr_schedule=plan.lr_schedule,
                    batch_size=plan.batch_size, seed=rng,
                    use_pallas=plan.use_pallas, telemetry=plan.telemetry,
                    return_stats=True)
                if ck is not None:
                    path = run_state.save_member(ck.dir, i, model, stats,
                                                 {**meta, "member": i})
                    if ck.after_save is not None:
                        ck.after_save("member", i, path)
            members.append(model)
            all_stats.append(stats)
        stats_k = run_state.stack_stats(all_stats)
        cache: dict = {}

        def snapshot():
            if "sm" not in cache:
                cache["sm"] = stack_models(members)
            return cache["sm"]

        def val_errors():
            # the sequential boosted path scores through the SAME stacked
            # program as the stacked executor (eval only — training stays
            # the faithful host loop), so the weights agree bit-for-bit
            if "err" not in cache:
                sm = snapshot()
                up = resolve_use_pallas(plan.use_pallas)
                cache["err"] = _validation_errors(
                    plan.validation, lambda x: _val_predict(
                        cfg, sm.cnn_params, sm.beta, x, use_pallas=up),
                    k, plan.telemetry)
            return cache["err"]

        def weights():
            if "w" not in cache:
                cache["w"] = (plan.weight_fn(0, snapshot, val_errors)
                              if plan.weight_fn is not None
                              else plan.reduce_weights)
            return cache["w"]

        def averaged():
            if "avg" not in cache:
                cache["avg"] = average_models(members, weights=weights())
            return cache["avg"]

        if ck is not None:
            path = run_state.save_round(
                ck.dir, 0, members=snapshot(), stats=stats_k,
                averaged=averaged(),
                meta={**meta, "round": 0, "epochs_done": plan.epochs,
                      "final": True})
            if ck.after_save is not None:
                ck.after_save("round", 0, path)
        if plan.on_round is not None:
            plan.on_round(0, snapshot, averaged)
        return MapOutcome(members, None, stats_k)


# ---------------------------------------------------------------------------
# The member mesh and its programs
# ---------------------------------------------------------------------------

def _member_axes(mesh) -> tuple:
    """The mesh axes carrying the member dim: ``('host', 'pod')`` on the
    hierarchical 2-D topology, ``('pod',)`` on the flat one."""
    return ("host", "pod") if "host" in mesh.shape else ("pod",)


def _member_entry(mesh):
    """The PartitionSpec entry for the member dim on ``mesh`` — the tuple
    ``('host', 'pod')`` or the bare ``'pod'``."""
    axes = _member_axes(mesh)
    return axes if len(axes) > 1 else axes[0]


def _member_devices(mesh) -> int:
    """How many devices hold members: the product of the member axes'
    sizes (1 on the empty mesh of a call outside any ``jax.set_mesh``)."""
    return math.prod(mesh.shape.get(a, 1) for a in _member_axes(mesh))


def _sum_axes(mesh) -> tuple:
    """The member axes a Reduce sums over: none where one device holds
    every member."""
    return _member_axes(mesh) if _member_devices(mesh) > 1 else ()


def _over_members(body, in_specs, out_specs):
    """``body`` over each device's own members of the context's member
    mesh: ``shard_map``-ed over the mesh, or, where one device holds the
    members (or no mesh is set), called whole. On a v5e the one-device
    epoch compiles to the same program either way (PERF.md); called
    whole, a Reduce needs no one-participant all-reduce."""
    if _member_devices(jax.sharding.get_abstract_mesh()) == 1:
        return body
    return jax.shard_map(body, in_specs=in_specs, out_specs=out_specs)


def _specs():
    """The specs of member-stacked arrays on the context mesh: the member
    dim first (params, stats, β, weights), or second (scan-major batches
    and the step record)."""
    e = _member_entry(jax.sharding.get_abstract_mesh())
    return P(e), P(None, e)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "solve_each_batch", "use_pallas",
                                    "masked"),
                   donate_argnames=("params_k", "stats_k"))
def _stacked_epoch(cfg, params_k, stats_k, xb, tb, mb, lr, *,
                   solve_each_batch: bool, use_pallas: bool, masked: bool,
                   rows=None):
    """One epoch chunk of ``cnn_elm.stacked_epoch_scan`` for every member,
    each device scanning only its own members: ZERO collectives. The
    donated carry keeps params and stats resident. ``rows`` (see
    ``StackedExecutor._put_partitions``) hold each device's own members'
    rows, so no row crosses a chip."""
    member, batch = _specs()

    def local(p, s, x, t, m, lr_, r):
        return stacked_epoch_scan(cfg, p, s, x, t, m, lr_,
                                  solve_each_batch=solve_each_batch,
                                  use_pallas=use_pallas, masked=masked,
                                  rows=r)

    out = (member, member) + ((batch,) if solve_each_batch else ())
    return _over_members(
        local, (member, member, batch, batch, batch, P(), member), out)(
        params_k, stats_k, xb, tb, mb, lr, rows)


@functools.partial(jax.jit, static_argnames=("lam",))
def _solve(stats_k, lam):
    """β for every member, solved where the member lives: each device
    Cholesky-factorises only its own (k_local, F, F) stats."""
    member, _ = _specs()
    return _over_members(lambda s: elm.solve_beta(s, lam),
                         (member,), member)(stats_k)


def _weighted_mean(tree, weights):
    """Inside the member map: the weighted mean over the GLOBAL member
    dim — local partial sums and weight total in one flat f32 vector,
    psum-ed over each member axis that spans devices (one flat, two
    hierarchical, none on one device). Zero weights drop padding."""
    return hierarchical_psum_weighted_mean_members(
        tree, weights, _sum_axes(jax.sharding.get_abstract_mesh()))


@jax.jit
def _reduce(tree, weights):
    """The Reduce: the (weighted) member mean of ``tree``, replicated.
    ``weights`` is the full padded member-weight vector."""
    member, _ = _specs()

    def local(t, w):
        with jax.named_scope(scopes.REDUCE):
            return _weighted_mean(t, w)

    return _over_members(local, (member, member), P())(tree, weights)


@jax.jit
def _sync(params_k, weights):
    """The inter-round sync, the Reduce's collective budget: the weighted
    mean broadcast straight back to every member slot — params never
    leave the devices between rounds. NOT donated: the round's lazy
    snapshot/averaged closures may still read the pre-sync params after
    the sync fires."""
    member, _ = _specs()

    def local(p, w):
        with jax.named_scope(scopes.REDUCE):
            avg = _weighted_mean(p, w)
            return broadcast_member_dim(avg, jax.tree.leaves(p)[0].shape[0])

    return _over_members(local, (member, member), member)(params_k, weights)


def _gossip_mix(tree, weights, rounds: int, publish: bool):
    """Inside the member map: ``rounds`` of ring gossip. Where one device
    holds every member the ring's nodes are the members
    (``gossip_member_dim`` rolls the member dim); on a multi-device mesh
    they are the pods — each pod pre-aggregates its members and mixes
    with its neighbours by ``ppermute`` (``gossip_ring_mix``: two per
    round, no all-reduce). Returns each member's own consensus iterate
    (the sync), or with ``publish`` the mixing-invariant ratio of sums
    (the Reduce; one psum across pods)."""
    mesh = jax.sharding.get_abstract_mesh()
    if _member_devices(mesh) == 1:
        return gossip_member_dim(tree, weights, rounds)[1 if publish else 0]
    num, den = gossip_ring_mix(tree, weights, "pod", rounds,
                               mesh.shape["pod"])
    ref = jax.tree.map(lambda a: a[0], tree)
    if publish:
        flat, unravel = ravel_pytree((num, den))
        num, den = unravel(jax.lax.psum(flat, "pod"))
    est = jax.tree.map(
        lambda s, t: (s / jnp.maximum(den, 1e-30)).astype(t.dtype), num, ref)
    return est if publish else broadcast_member_dim(
        est, jax.tree.leaves(tree)[0].shape[0])


@functools.partial(jax.jit, static_argnames=("rounds", "publish"))
def _gossip(tree, weights, *, rounds: int, publish: bool):
    """The gossip combine (``_gossip_mix``): the sync resets every member
    to its OWN consensus iterate — the decentralized regime, not one
    broadcast row; ``publish`` gives the Reduce's single model."""
    member, _ = _specs()

    def local(t, w):
        with jax.named_scope(scopes.REDUCE):
            return _gossip_mix(t, w, rounds, publish)

    return _over_members(local, (member, member),
                         P() if publish else member)(tree, weights)


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _val_predict(cfg, params_k, beta_k, x, *, use_pallas: Optional[bool]):
    """(k, n) argmax labels of ONE validation batch under every member —
    the boosted strategy's scoring program: each device scores the
    replicated batch under its own members, zero collectives (the
    error-rate mean happens on the host in f64 so the weights agree
    bit-for-bit across backends)."""
    member, _ = _specs()

    def local(p, b, xv):
        def one(pp, bb):
            h = cnn.features(cfg, pp, xv, use_pallas=use_pallas)
            return jnp.argmax(elm.predict(h, bb), axis=-1)

        return jax.vmap(one)(p, b)

    return _over_members(local, (member, member, P()), member)(
        params_k, beta_k, x)


@functools.partial(jax.jit, static_argnames=("lam",))
def _e2lm_beta(stats_k, lam):
    """E²LM cross-member Reduce (``e2lm.psum_stats``): every member's
    sufficient statistics summed over the member mesh and solved into
    ONE global β — the exact no-partition ELM readout, computed from the
    Map phase's stats without gathering them. Padded members hold zero
    stats, so they vanish from the sums."""
    member, _ = _specs()
    axes = _sum_axes(jax.sharding.get_abstract_mesh())

    def local(s):
        loc = type(s)(s.u.sum(0), s.v.sum(0), s.n.sum(0))
        return elm.solve_beta(psum_stats(loc, axes) if axes else loc, lam)

    return _over_members(local, (member,), P())(stats_k)


_VAL_BATCH = 512       # validation slices score in bounded device batches


def _validation_errors(validation, predict, k: int, telemetry) -> np.ndarray:
    """(k,) misclassification rates of the members on the held-out
    ``validation=(x, y)`` slice, scored in device batches by ``predict``
    (x → (k_pad, n) labels) — the error mean is float64 host math, so
    the weights agree bit-for-bit across executors."""
    if validation is None:
        raise ValueError(
            "per-member validation errors need a held-out slice — set "
            "plan.validation (the runner wires ReduceConfig.validation "
            "through)")
    xv, yv = validation
    preds = []
    for i in range(0, len(xv), _VAL_BATCH):
        preds.append(np.asarray(predict(jnp.asarray(xv[i:i + _VAL_BATCH]))))
        _bump(telemetry)
    preds = np.concatenate(preds, axis=1)[:k]
    return np.asarray(preds != np.asarray(yv)[None, :],
                      np.float64).mean(axis=1)


def _flat_rows(x: np.ndarray) -> np.ndarray:
    """Images as (n, H·W·C) rows, the layout ``cnn_elm.gather_batch``
    gathers from (a view of a contiguous array)."""
    return np.reshape(x, (len(x), -1))


def _bytes_limit(device) -> Optional[int]:
    """The device's memory in bytes; None where the backend reports none
    (the CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


# ---------------------------------------------------------------------------
# The stacked executor
# ---------------------------------------------------------------------------

class StackedExecutor:
    """The k members stacked on a member mesh: one donated scan dispatch
    per epoch chunk, then the β solve, and the Reduce or the inter-round
    sync, one program each (see the module docstring).

    ``mesh`` must carry a ``'pod'`` axis; None builds the default of
    ``backend``: the first device (``"stacked"``) or a 1-D ``('pod',)``
    mesh over every device (``"mesh"``). With an additional ``'host'``
    axis (``make_member_mesh(hosts=...)``) the member dim shards over
    ``('host', 'pod')`` jointly and every Reduce/sync stages
    hierarchically: intra-host psum then inter-host psum. The per-round
    cost model: epochs/rounds scan dispatches with zero collectives, then
    one program for the sync (or the final Reduce) with zero (one
    device), one (flat) or two (hierarchical) all-reduces, regardless of
    fleet size. See docs/perf.md §Mesh scaling.

    What the run hands back — members, averaged model, step record —
    lives on one device, where the eval and serving surfaces run (XLA
    cannot partition a Pallas kernel over a mesh): on one device it stays
    where it is; off a larger mesh it is gathered, padding stripped."""

    supports_rounds = True

    def __init__(self, mesh=None, backend: str = "stacked"):
        if mesh is None:
            mesh = make_member_mesh(1 if backend == "stacked" else None)
        if "pod" not in mesh.shape:
            raise ValueError(
                f"the stacked executor needs a mesh with a 'pod' axis, got "
                f"axes {tuple(mesh.shape)}")
        self.name, self.mesh = backend, mesh
        self._devices = _member_devices(mesh)

    # ---- the round/epoch loop --------------------------------------------

    def execute(self, cfg, init_params, partitions, plan: ExecutionPlan
                ) -> MapOutcome:
        if plan.chunk_batches is not None and plan.chunk_batches < 1:
            raise ValueError(
                f"chunk_batches must be >= 1, got {plan.chunk_batches}")
        if plan.rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {plan.rounds}")
        if plan.rounds > 1 and plan.epochs == 0:
            raise ValueError(
                "rounds > 1 needs SGD epochs to interleave with averaging; "
                "epochs=0 is the single closed-form pass")
        if plan.rounds > 1 and plan.epochs % plan.rounds:
            raise ValueError(f"epochs ({plan.epochs}) must split evenly "
                             f"into rounds ({plan.rounds})")
        if plan.start_round and not 0 < plan.start_round < plan.rounds:
            raise ValueError(
                f"start_round {plan.start_round} outside this plan's "
                f"resumable rounds (1..{plan.rounds - 1}); a finished run "
                f"resumes from its final checkpoint, not through execute")
        if plan.completed:
            raise ValueError("plan.completed is the sequential backend's "
                             "resume contract; stacked layouts resume via "
                             "start_round")
        k = len(partitions)
        F, C = cnn.feature_dim(cfg), cfg.num_classes
        use_pallas = resolve_use_pallas(plan.use_pallas)
        telemetry = plan.telemetry
        self._begin(cfg, k)
        if plan.gossip_rounds is not None:
            if plan.gossip_rounds < 1:
                raise ValueError(f"gossip_rounds must be >= 1, "
                                 f"got {plan.gossip_rounds}")
            if plan.checkpoint is not None:
                raise ValueError(
                    "gossip syncs leave each member on its OWN consensus "
                    "iterate; the per-round checkpoint/resume contract "
                    "assumes one shared post-sync row — run gossip "
                    "without checkpointing")
            if "host" in self.mesh.shape:
                raise ValueError(
                    "gossip rides the flat 1-D 'pod' ring — the "
                    "hierarchical ('host', 'pod') mesh has no single ring "
                    "axis; build the flat member mesh (make_member_mesh()) "
                    "for gossip syncs")
        per_round = plan.epochs // plan.rounds
        # live per-member streams: each epoch's builder call draws the next
        # permutation (mirrors train_member's stream, no epoch replay);
        # resume / elastic continuation fast-forwards by burning the
        # already-consumed epochs' draws — one permutation per epoch
        rngs = [np.random.default_rng(s) for s in _member_seeds(plan, k)]
        for rng, p, burn in zip(rngs, partitions,
                                _stream_burns(plan, k, per_round)):
            for _ in range(burn):
                rng.permutation(len(p.x))
        inits = _member_inits(plan, k)
        pad = self._k_pad - k
        if inits is None:
            params_k = broadcast_member_dim(init_params, self._k_pad)
        else:
            # per-member trees stacked on the member dim — the streaming
            # block-continuation init; padded slots repeat member 0
            params_k = jax.tree.map(lambda *xs: jnp.stack(
                [jnp.asarray(x) for x in xs + xs[:1] * pad]), *inits)
        params_k = self._put(params_k)

        round_passes = [[(False, 0.0)]] if plan.epochs == 0 else [
            [(True, float(plan.lr_schedule(r * per_round + e)))
             for e in range(per_round)] for r in range(plan.rounds)]
        sm = None
        stats_k = None
        record = None       # the last SGD epoch's (record chunks, mask)
        step_record = None
        ck = plan.checkpoint
        ck_meta = (run_state.run_fingerprint(
            self.name, partitions, seed=plan.seed, epochs=plan.epochs,
            rounds=plan.rounds, batch_size=plan.batch_size)
            if ck is not None else None)

        def put(chunk):
            # device_put is async: issuing chunk i+1 while chunk i scans
            # double-buffers the host→device pipeline
            with TraceAnnotation(scopes.MAP_PUT):
                return self._put(chunk, member_dim=1)

        # the members' rows go up once when they fit; each epoch then
        # sends its indices and the device gathers the batches
        data = None
        if self._fits_devices(partitions):
            with TraceAnnotation(scopes.MAP_PUT):
                data = self._put_partitions(partitions)
        built = "host_epoch_builds" if data is None else "device_epoch_builds"

        def gather(stats_k):
            with TraceAnnotation(scopes.MAP_GATHER):
                return elm.ELMStats(*(np.asarray(a)[:k] for a in stats_k))

        for r, passes in enumerate(round_passes):
            if r < plan.start_round:
                continue        # completed before the resume point; the
            stats_k = None      # rng draws were burned above
            for solve_each_batch, lr in passes:
                with TraceAnnotation(scopes.MAP_EPOCH_BUILD):
                    arrays, chunk = self._epoch_arrays(
                        partitions, plan.batch_size, rngs, C,
                        plan.chunk_batches, gather=data is not None)
                _bump(telemetry, key=built)
                masked = bool(np.any(arrays[-1] == 0.0))
                stats_k = self._put(elm.zero_stats_stacked(self._k_pad, F, C))
                chunks = chunk_scan_major(arrays, chunk)
                lr_dev = jnp.asarray(lr, jnp.float32)
                if solve_each_batch:
                    record = ([], arrays[-1])
                nxt = put(chunks[0])
                for i in range(len(chunks)):
                    cur, nxt = nxt, (put(chunks[i + 1])
                                     if i + 1 < len(chunks) else None)
                    if data is not None:
                        # the batches' and the labels' row indices
                        cur = (cur[0], cur[0], cur[1])
                    with TraceAnnotation(scopes.MAP_DISPATCH):
                        params_k, stats_k, *rec = self._call(
                            _stacked_epoch, cfg, params_k, stats_k, *cur,
                            lr_dev, solve_each_batch=solve_each_batch,
                            use_pallas=use_pallas, masked=masked, rows=data)
                    if solve_each_batch:
                        record[0].extend(rec)
                    _bump(telemetry)
            last = r == len(round_passes) - 1
            snapshot, averaged, weights = self._round_closures(
                cfg, params_k, stats_k, plan, r, use_pallas, telemetry)
            if last:
                sm = snapshot()
                if record is not None:
                    step_record = self._step_record(*record)
            else:
                w = self._weights(weights())
                with TraceAnnotation(scopes.MAP_REDUCE):
                    params_k = (
                        self._call(_sync, params_k, w)
                        if plan.gossip_rounds is None else
                        self._call(_gossip, params_k, w, publish=False,
                                   rounds=plan.gossip_rounds))
                # the sync is a device dispatch too — counted toward the
                # total AND tallied separately, before on_round closes this
                # round's books, so per-round telemetry prices its own sync
                _bump(telemetry)
                _bump(telemetry, key="round_syncs")
            if ck is not None and (last or (r + 1) % ck.every == 0):
                resume = None
                if not last:
                    # the sync broadcast one identical row into every
                    # member slot — row 0 of the POST-sync params IS the
                    # resume point: placing it via the normal broadcast
                    # reproduces the device state bit-for-bit
                    resume = jax.tree.map(lambda a: np.asarray(a)[0],
                                          params_k)
                path = run_state.save_round(
                    ck.dir, r, members=snapshot(),
                    stats=gather(stats_k), averaged=averaged(),
                    resume_params=resume,
                    step_record=step_record,
                    meta={**ck_meta, "round": r,
                          "epochs_done": (r + 1) * per_round,
                          "final": last})
                if ck.after_save is not None:
                    ck.after_save("round", r, path)
            if plan.on_round is not None:
                plan.on_round(r, snapshot, averaged)
        return MapOutcome(sm.unstack(), sm, gather(stats_k), step_record)

    def _step_record(self, chunks, mb) -> StepRecord:
        """The epoch's record chunks joined in step order, padded member
        slots dropped, beside the steps' mask."""
        params = jax.tree.map(
            lambda *c: c[0] if len(c) == 1 else jnp.concatenate(c),
            *chunks)
        return StepRecord(self._handed_back(params, member_dim=1),
                          np.asarray(mb)[:, :self._k] > 0)

    def _round_closures(self, cfg, params_k, stats_k, plan, r, use_pallas,
                        telemetry):
        """Lazy, cached snapshot/averaged/weights over THIS round's
        pre-sync state. The β solve is shared between them and only runs
        if somebody asks (the final round always; intermediate rounds
        only under a hook). ``weights()`` resolves the round's member
        weights: the static ``plan.reduce_weights``, or — under a
        ``plan.weight_fn`` strategy (boosted) — from the round's trained
        members, with ``val_errors()`` scoring ``plan.validation`` on the
        member mesh, all at most once per round."""
        cache: dict = {}

        def solved_beta():
            if "beta" not in cache:
                _bump(telemetry)
                self._last_stats = stats_k          # for e2lm_global_beta
                cache["beta"] = self._call(_solve, stats_k, cfg.elm_lambda)
            return cache["beta"]

        def snapshot():
            if "sm" not in cache:
                cache["sm"] = StackedMembers(*self._handed_back(
                    (params_k, solved_beta()), member_dim=0))
            return cache["sm"]

        def val_errors():
            if "err" not in cache:
                cache["err"] = _validation_errors(
                    plan.validation, lambda x: self._call(
                        _val_predict, cfg, params_k, solved_beta(), x,
                        use_pallas=use_pallas), self._k, telemetry)
            return cache["err"]

        def weights():
            if "w" not in cache:
                cache["w"] = (plan.weight_fn(r, snapshot, val_errors)
                              if plan.weight_fn is not None
                              else plan.reduce_weights)
            return cache["w"]

        def averaged():
            if "avg" not in cache:
                tree, w = (params_k, solved_beta()), self._weights(weights())
                with TraceAnnotation(scopes.MAP_REDUCE):
                    avg = (self._call(_reduce, tree, w)
                           if plan.gossip_rounds is None else
                           self._call(_gossip, tree, w, publish=True,
                                      rounds=plan.gossip_rounds))
                cache["avg"] = CNNELMModel(*self._handed_back(avg))
            return cache["avg"]

        return snapshot, averaged, weights

    # ---- placement -------------------------------------------------------

    def _begin(self, cfg, k: int):
        """Per-run layout: k members in ``k_pad`` slots, a multiple of the
        devices holding members."""
        self._cfg, self._k = cfg, k
        self._k_pad = -(-k // self._devices) * self._devices

    def _call(self, program, *args, **kw):
        """``program`` dispatched on this executor's member mesh."""
        with jax.set_mesh(self.mesh):
            return program(*args, **kw)

    def _sharding(self, member_dim: int = 0):
        """Where member-stacked arrays go: the member dim (at
        ``member_dim``) split over the member axes; on a mesh of one
        device, that device — plain single-device arrays, as every other
        program of the process makes them, so what the run hands back
        meets the eval and serving programs' jit caches as it always
        has."""
        if self.mesh.size == 1:
            return SingleDeviceSharding(self.mesh.devices.flat[0])
        return NamedSharding(self.mesh, P(*(None,) * member_dim,
                                          _member_entry(self.mesh)))

    def _put(self, tree, member_dim: int = 0):
        """``tree`` on the mesh, its member dim (at ``member_dim``) split
        over the member axes."""
        return jax.device_put(tree, self._sharding(member_dim))

    def _weights(self, weights):
        """The padded member-weight vector on the mesh: ``weights`` (or
        1) on the real members, 0 on padding."""
        w = np.zeros(self._k_pad, np.float32)
        w[:self._k] = 1.0 if weights is None else np.asarray(weights,
                                                             np.float32)
        return self._put(w)

    def _handed_back(self, tree, member_dim: Optional[int] = None):
        """``tree`` as the run hands it back, on one device: as it stands
        where the mesh is one device; else gathered off the mesh with the
        padded member slots (at ``member_dim``) stripped."""
        if self.mesh.size == 1:
            return tree

        def take(a):
            a = np.asarray(a)
            if member_dim is not None:
                a = a[(slice(None),) * member_dim + (slice(self._k),)]
            return jnp.asarray(a)

        return jax.tree.map(take, tree)

    def _slots(self, partitions):
        """Per member slot j, the members it holds, one per device: member
        d·k_local + j on device d (None on padding)."""
        k_local = self._k_pad // self._devices
        return [[partitions[m] if m < self._k else None
                 for m in range(j, self._k_pad, k_local)]
                for j in range(k_local)]

    def _fits_devices(self, partitions) -> bool:
        """Whether the partitions, as ``_put_partitions`` places them, fit
        within half of the memory of each device they go to."""
        p = partitions[0]
        row = (p.x.nbytes + p.y.nbytes) // len(p.x)
        need = row * sum(max(len(q.x) for q in slot if q is not None)
                         for slot in self._slots(partitions))
        limits = [_bytes_limit(d) for d in self.mesh.devices.flat]
        return all(need <= lim // 2 for lim in limits if lim is not None)

    def _put_partitions(self, partitions):
        """The members' flat rows and int labels on the devices that hold
        them: per member slot j one array of devices·n_j rows split over
        the member axes, device d's n_j rows its member of the slot
        (``_slots``), a shorter member zero-padded within the slot. Each
        device's part is its member's own array, so nothing is stacked on
        the host; on one device slot j is member j's rows as they are.
        Returns (xs, ys), tuples of k_local arrays, (n_j, H·W·C) and
        (n_j,) as each device sees them."""
        sharding = self._sharding()

        def slot_array(members, field):
            arrays = [None if p is None else field(p) for p in members]
            a0 = arrays[0]
            n = max(len(a) for a in arrays if a is not None)

            def rows(index):
                a = arrays[(index[0].start or 0) // n]
                if a is not None and len(a) == n:
                    return a
                out = np.zeros((n,) + a0.shape[1:], a0.dtype)
                if a is not None:
                    out[:len(a)] = a
                return out

            return jax.make_array_from_callback(
                (self._devices * n,) + a0.shape[1:], sharding, rows)

        slots = self._slots(partitions)
        return (tuple(slot_array(s, lambda p: _flat_rows(p.x))
                      for s in slots),
                tuple(slot_array(s, lambda p: p.y) for s in slots))

    def _epoch_arrays(self, partitions, batch_size, rngs, num_classes,
                      chunk_batches, *, gather: bool):
        """One epoch's scan-major padded arrays on the HOST, plus the
        chunk length (nb itself when not chunking). With ``gather``: row
        indices idx (nb, k_pad, B) and validity mb (nb, k_pad), for the
        device to gather the batches from; else the batches themselves:
        xb (nb, k_pad, B, ...), tb (nb, k_pad, B, C) one-hot and mb.
        Padded member slots get zeros. Each call consumes one permutation
        per member stream. nb is rounded up to a chunk multiple so every
        chunk shares one fixed shape (= one jit cache entry)."""
        nb = max(len(p.x) // batch_size for p in partitions)
        chunk, num_batches = nb, None
        if chunk_batches is not None and 0 < chunk_batches < nb:
            chunk = chunk_batches
            num_batches = -(-nb // chunk) * chunk
        if gather:
            arrays = padded_epoch_indices(partitions, batch_size, rngs,
                                          num_batches=num_batches)
        else:
            xs, ys, mk = padded_stacked_epoch_batches(
                partitions, batch_size, rngs, num_batches=num_batches)
            tb = one_hot(ys.reshape(-1),
                         num_classes).reshape(*ys.shape, num_classes)
            arrays = tuple(np.swapaxes(a, 0, 1) for a in (xs, tb, mk))
        pad = self._k_pad - self._k
        if pad:
            arrays = tuple(np.concatenate(
                [a, np.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)],
                axis=1) for a in arrays)
        return arrays, chunk

    def e2lm_global_beta(self):
        """After ``execute``: the E²LM global readout — ONE
        ``e2lm.psum_stats`` reduce of every member's final-epoch stats,
        solved into the single β a no-partition ELM would produce."""
        if not hasattr(self, "_last_stats"):
            raise RuntimeError("e2lm_global_beta needs a completed execute()"
                               " (the final-round solve records the stats)")
        return self._call(_e2lm_beta, self._last_stats,
                          self._cfg.elm_lambda)
