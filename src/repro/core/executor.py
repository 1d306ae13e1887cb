"""The Map-phase execution layer — how Algorithm 2's k members actually run.

``repro.core.cnn_elm`` owns the MATH (the per-batch step, the stacked scan
body, the β solve); this module owns the ORCHESTRATION: the epoch/round
loop, host→device chunk pipelining, telemetry, inter-round syncs and the
Reduce. The runner (``repro.core.runner``) selects an executor by name:

* ``SequentialExecutor`` (``backend="sequential"``) — the faithful
  reference: a host Python loop over ``cnn_elm.train_member``, three jit
  dispatches per batch per member.
* ``StackedExecutor`` (``backend="stacked"``) — the single-device fast
  path: all k members stacked on a leading member dim, one donated
  vmap+scan dispatch per epoch chunk. An optional ``mesh`` places the
  member dim via ``sharding.member_dim_shardings`` and lets GSPMD
  partition the program implicitly.
* ``MeshExecutor`` (``backend="mesh"``) — the multi-pod path: the SAME
  stacked scan body, explicitly ``shard_map``-ed over the ``'pod'`` axis
  of a ``jax.sharding.Mesh``. Members are sharded via
  ``sharding.member_dim_shardings`` (pad-and-mask when k doesn't divide
  the pod count — see below), epoch chunks land member-sharded via
  ``sharding.stacked_batch_shardings``, the epoch scan contains ZERO
  collectives, the β Cholesky solve runs pod-sharded
  (each device factorises only its local members), and the Reduce — final
  average AND every ``rounds=r`` inter-round sync — is ONE in-mesh
  all-reduce (``averaging.psum_weighted_mean_members``: local weighted
  partial sums raveled flat, a single ``psum``, unravel + normalise).

Member padding (MeshExecutor): k members on a p-pod mesh are padded to
``k_pad = ceil(k/p)·p`` — this covers both a mesh larger than k (every pod
still holds ≥1 member slot) and k not divisible by p. Padded members carry
zero batches with a zero validity mask (they never update and accumulate
zero stats) and weight 0 in every Reduce, so they are arithmetically
invisible; the final snapshot strips them. Simulate pods on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
``repro.launch.mesh.force_host_device_count`` / ``REPRO_HOST_DEVICES``).

Telemetry contract (a plain dict, shared with the runner's ``RunResult``):
``dispatches`` counts every device program the executor launches (epoch
chunks, β solves, syncs); ``round_syncs`` the inter-round average+broadcast
programs.

Epoch build (stacked layouts): where the partitions, as the executor
places them, fit within half of each device's memory
(``memory_stats()["bytes_limit"]``; a backend that reports none, such as
the CPU, counts as fitting), ``execute`` uploads them once and each epoch
sends only its row indices: each scan step gathers its batch on the
device, in the same program (``cnn_elm.gather_batch``). Otherwise each
epoch is built on the host and its batches are uploaded chunk by chunk,
so data larger than the device still runs with ``chunk_batches``. Both
feed the scan the same values. Telemetry counts ``device_epoch_builds``
or ``host_epoch_builds`` per epoch.

Tracing: the programs carry the device scopes of ``repro.scopes``, and
``_StackedBase.execute`` opens the host spans of the stacked loop once,
for both stacked executors.

Fault tolerance (``plan.checkpoint`` / ``plan.start_round`` /
``plan.completed``): the stacked layouts save one atomic
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
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from jax.profiler import TraceAnnotation

from repro import scopes
from repro.checkpoint import run_state
from repro.core import elm
from repro.core.averaging import (average_member_dim, broadcast_member_dim,
                                  gossip_member_dim, gossip_ring_mix,
                                  hierarchical_psum_weighted_mean_members,
                                  psum_weighted_mean_members)
from repro.core.cnn_elm import (CNNELMModel, StackedMembers, StepRecord,
                                _bump, average_models, stack_models,
                                stacked_epoch_scan, train_member,
                                _stacked_epoch)
from repro.core.e2lm import psum_stats
from repro.data.partition import (chunk_scan_major, padded_epoch_indices,
                                  padded_stacked_epoch_batches)
from repro.data.synthetic import one_hot
from repro.distributed import sharding
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
    native Reduce (host mean / member-dim mean / one in-mesh all-reduce).
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
    between syncs); backends ``sequential`` and ``stacked`` only.

    Reduce-strategy fields (``repro.core.reduce_strategies``):
    ``weight_fn(r, snapshot, val_errors)`` resolves the round's member
    weights LAZILY from trained state — ``snapshot``/``val_errors`` are
    the round's cached closures (``val_errors()`` scores ``validation``,
    an (x, y) held-out slice, with the backend-native program: host
    stacked scorer or the in-mesh shard_map — and returns the (k,)
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
    ``StackedMembers`` on the stacked layouts (None on sequential), and
    the final-epoch ``ELMStats`` of every member (host, member-stacked,
    padding stripped) — what β was solved from, for checkpointing and the
    elastic/E²LM stats merges. ``record``: the stacked layouts' step
    record of the last SGD epoch (None where no SGD step ran, and on
    sequential)."""
    members: List[CNNELMModel]
    stacked: Optional[StackedMembers]
    stats: Optional[elm.ELMStats] = None
    record: Optional[StepRecord] = None


def make_executor(backend: str, mesh=None) -> "Executor":
    """Executor registry: ``backend`` ∈ ``BACKENDS``. ``mesh`` is the
    placement mesh (required axis ``'pod'`` for ``"mesh"``; optional GSPMD
    hint for ``"stacked"``; ignored by ``"sequential"``)."""
    if backend == "sequential":
        return SequentialExecutor()
    if backend == "stacked":
        return StackedExecutor(mesh=mesh)
    if backend == "mesh":
        return MeshExecutor(mesh=mesh)
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
                "rounds > 1 needs a stacked layout (StackedExecutor or "
                "MeshExecutor) — the sequential reference has no sync "
                "point between members")
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
            # program as the fast backends (eval only — training stays
            # the faithful host loop), so the weights agree bit-for-bit
            if "err" not in cache:
                if plan.validation is None:
                    raise ValueError(
                        "per-member validation errors need a held-out "
                        "slice — set plan.validation (the runner wires "
                        "ReduceConfig.validation through)")
                xv, yv = plan.validation
                sm = snapshot()
                up = resolve_use_pallas(plan.use_pallas)
                preds = []
                for j in range(0, len(xv), _VAL_BATCH):
                    preds.append(np.asarray(_member_predictions(
                        cfg, sm.cnn_params, sm.beta,
                        jnp.asarray(xv[j:j + _VAL_BATCH]),
                        use_pallas=up)))
                    _bump(plan.telemetry)
                cache["err"] = _val_error_rates(
                    np.concatenate(preds, axis=1), yv)
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
# The shared stacked round/epoch loop (StackedExecutor + MeshExecutor)
# ---------------------------------------------------------------------------

@jax.jit
def _round_sync(params_k, weights):
    """The single-device inter-round sync as ONE fused program: (weighted)
    mean over the member dim, broadcast back as every member's next-round
    init. Jitted so the one-dispatch-per-sync telemetry is literal."""
    k = jax.tree.leaves(params_k)[0].shape[0]
    with jax.named_scope(scopes.REDUCE):
        return broadcast_member_dim(
            average_member_dim(params_k, weights=weights), k)


@functools.partial(jax.jit, static_argnames=("rounds",))
def _gossip_round_sync(params_k, weights, *, rounds: int):
    """The single-device GOSSIP sync: ring mixing over the member dim,
    every member reset to its OWN consensus iterate (not one broadcast
    row — the decentralized regime)."""
    with jax.named_scope(scopes.REDUCE):
        return gossip_member_dim(params_k, weights, rounds)[0]


@functools.partial(jax.jit, static_argnames=("rounds",))
def _gossip_reduce(tree, weights, *, rounds: int):
    """The single-device gossip Reduce: the published ratio-of-sums
    readout after ``rounds`` mixing rounds (exact weighted mean up to
    f32 summation order — the mixing stencil is sum-invariant)."""
    with jax.named_scope(scopes.REDUCE):
        return gossip_member_dim(tree, weights, rounds)[1]


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _member_predictions(cfg, cnn_params_k, beta_k, x, *,
                        use_pallas: Optional[bool]):
    """(k, n) argmax labels of ONE validation batch under every member —
    the boosted strategy's scoring program on the host-stacked layouts
    (one vmap dispatch; the error-rate mean happens on the host in f64
    so the weights agree bit-for-bit across backends)."""
    def one(p, b):
        h = cnn.features(cfg, p, x, use_pallas=use_pallas)
        return jnp.argmax(elm.predict(h, b), axis=-1)

    return jax.vmap(one)(cnn_params_k, beta_k)


def _val_error_rates(preds_k: np.ndarray, yv) -> np.ndarray:
    """(k,) misclassification rates from (k, n) member predictions —
    float64 host math, shared by all three backends."""
    return np.asarray(
        preds_k != np.asarray(yv)[None, :], np.float64).mean(axis=1)


_VAL_BATCH = 512       # validation slices score in bounded device batches


def _flat_rows(x: np.ndarray) -> np.ndarray:
    """Images as (n, H·W·C) rows, the layout ``cnn_elm.gather_batch``
    gathers from (a view of a contiguous array)."""
    return np.reshape(x, (len(x), -1))


def _bytes_limit(device) -> Optional[int]:
    """The device's memory in bytes; None where the backend reports none
    (the CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


class _StackedBase:
    """Round/epoch/chunk orchestration over the stacked member layout.

    Subclasses fix the placement + dispatch details via hooks:
    ``_place_params`` / ``_zero_stats`` (where the carry lives),
    ``_partition_bytes`` / ``_put_partitions`` (the members' rows on the
    devices), ``_pad_epoch`` (member-dim padding), ``_put_chunk`` (how
    batches or indices reach devices), ``_epoch_dispatch`` (plain jit vs
    shard_map), ``_solve``, ``_snapshot``, ``_averaged`` and ``_sync``.
    The loop itself — round blocks, the per-epoch build (indices for a
    device gather, or host batches), double-buffered chunk pipeline, lazy
    snapshot/averaged closures, telemetry — is written once here.
    """

    supports_rounds = True

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
            self._check_gossip()
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
        params_k = (self._place_params(init_params) if inits is None
                    else self._place_member_params(inits))

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
            with TraceAnnotation(scopes.MAP_PUT):
                return self._put_chunk(chunk)

        # the members' rows go up once when they fit; each epoch then
        # sends its indices and the device gathers the batches
        data = None
        if self._fits_devices(partitions):
            with TraceAnnotation(scopes.MAP_PUT):
                data = self._put_partitions(partitions)
        built = "host_epoch_builds" if data is None else "device_epoch_builds"

        def gather(stats_k):
            with TraceAnnotation(scopes.MAP_GATHER):
                return self._host_stats(stats_k)

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
                stats_k = self._zero_stats(F, C)
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
                        params_k, stats_k, *rec = self._epoch_dispatch(
                            cfg, params_k, stats_k, cur, lr_dev,
                            solve_each_batch, use_pallas, masked, data)
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
                w = weights()
                with TraceAnnotation(scopes.MAP_REDUCE):
                    params_k = self._sync(params_k, w,
                                          gossip_rounds=plan.gossip_rounds)
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
        slots dropped (``_record_params``), beside the steps' mask."""
        params = jax.tree.map(
            lambda *c: c[0] if len(c) == 1 else jnp.concatenate(c),
            *chunks)
        return StepRecord(self._record_params(params),
                          np.asarray(mb)[:, :self._k] > 0)

    def _round_closures(self, cfg, params_k, stats_k, plan, r, use_pallas,
                        telemetry):
        """Lazy, cached snapshot/averaged/weights over THIS round's
        pre-sync state. The β solve is shared between them and only runs
        if somebody asks (the final round always; intermediate rounds
        only under a hook). ``weights()`` resolves the round's member
        weights: the static ``plan.reduce_weights``, or — under a
        ``plan.weight_fn`` strategy (boosted) — from the round's trained
        members, with ``val_errors()`` scoring ``plan.validation`` via
        the backend-native program, all at most once per round."""
        cache: dict = {}

        def solved_beta():
            if "beta" not in cache:
                _bump(telemetry)
                cache["beta"] = self._solve(cfg, stats_k)
            return cache["beta"]

        def snapshot():
            if "sm" not in cache:
                cache["sm"] = self._snapshot(params_k, solved_beta())
            return cache["sm"]

        def val_errors():
            if "err" not in cache:
                if plan.validation is None:
                    raise ValueError(
                        "per-member validation errors need a held-out "
                        "slice — set plan.validation (the runner wires "
                        "ReduceConfig.validation through)")
                cache["err"] = self._val_errors(
                    cfg, params_k, solved_beta(), plan.validation,
                    use_pallas, telemetry)
            return cache["err"]

        def weights():
            if "w" not in cache:
                cache["w"] = (plan.weight_fn(r, snapshot, val_errors)
                              if plan.weight_fn is not None
                              else plan.reduce_weights)
            return cache["w"]

        def averaged():
            if "avg" not in cache:
                beta_k, w = solved_beta(), weights()
                with TraceAnnotation(scopes.MAP_REDUCE):
                    cache["avg"] = self._averaged(
                        params_k, beta_k, w, telemetry,
                        gossip_rounds=plan.gossip_rounds)
            return cache["avg"]

        return snapshot, averaged, weights

    # ---- shared epoch building ------------------------------------------

    def _fits_devices(self, partitions) -> bool:
        """Whether the partitions, as ``_put_partitions`` places them, fit
        within half of the memory of each device they go to."""
        need = self._partition_bytes(partitions)
        limits = [_bytes_limit(d) for d in self._devices()]
        return all(need <= lim // 2 for lim in limits if lim is not None)

    def _epoch_arrays(self, partitions, batch_size, rngs, num_classes,
                      chunk_batches, *, gather: bool):
        """One epoch's scan-major padded arrays on the HOST, plus the
        chunk length (nb itself when not chunking). With ``gather``: row
        indices idx (nb, k, B) and validity mb (nb, k), for the device to
        gather the batches from; else the batches themselves: xb
        (nb, k, B, ...), tb (nb, k, B, C) one-hot and mb. Each call
        consumes one permutation per member stream. nb is rounded up to a
        chunk multiple so every chunk shares one fixed shape (= one jit
        cache entry)."""
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
        return self._pad_epoch(*arrays), chunk

    # ---- backend hooks ---------------------------------------------------

    def _begin(self, cfg, k):
        """Per-run setup (member counts, mesh checks)."""

    def _check_gossip(self):
        """Veto hook for the gossip combine (mesh topologies without a
        single ring axis reject it)."""

    def _val_errors(self, cfg, params_k, beta_k, validation, use_pallas,
                    telemetry) -> np.ndarray:
        """(k,) per-member misclassification rates on the held-out
        ``validation=(x, y)`` slice — backend-native scoring (argmax on
        device, f64 error mean on host), padding stripped."""
        raise NotImplementedError

    def _place_member_params(self, inits):
        raise ValueError(
            f"plan.member_init is not supported on backend {self.name!r} — "
            f"the mesh layout would re-pad and re-shard per-member trees "
            f"mid-run; streaming blocks run on 'sequential' or 'stacked'")

    def _pad_epoch(self, *arrays):
        return arrays

    def _host_stats(self, stats_k) -> elm.ELMStats:
        """Member-stacked stats on the host (mesh strips the padding)."""
        return elm.ELMStats(*(np.asarray(a) for a in stats_k))

    def _record_params(self, params):
        """The step record's (nb, k, ...) params as the run hands them
        back (mesh: gathered off the mesh like the snapshot)."""
        return params


class StackedExecutor(_StackedBase):
    """Today's single-device fast path: one donated vmap+scan jit dispatch
    per epoch chunk (``cnn_elm._stacked_epoch``). An optional ``mesh``
    device_puts the member dim via ``sharding.member_dim_shardings`` and
    leaves the partitioning to GSPMD — the implicit-SPMD variant;
    ``MeshExecutor`` is the explicit shard_map one."""

    name = "stacked"

    def __init__(self, mesh=None):
        self.mesh = mesh

    def _begin(self, cfg, k):
        self._k = k

    def _place_params(self, init_params):
        params_k = broadcast_member_dim(init_params, self._k)
        if self.mesh is not None:
            params_k = jax.device_put(
                params_k, sharding.member_dim_shardings(params_k, self.mesh))
        return params_k

    def _place_member_params(self, inits):
        # per-member trees stacked on the member dim — the streaming
        # block-continuation init (same placement rules as the broadcast)
        params_k = jax.tree.map(lambda *xs: jnp.stack(
            [jnp.asarray(x) for x in xs]), *inits)
        if self.mesh is not None:
            params_k = jax.device_put(
                params_k, sharding.member_dim_shardings(params_k, self.mesh))
        return params_k

    def _zero_stats(self, F, C):
        stats_k = elm.zero_stats_stacked(self._k, F, C)
        if self.mesh is not None:
            stats_k = jax.device_put(
                stats_k, sharding.member_dim_shardings(stats_k, self.mesh))
        return stats_k

    def _devices(self):
        return (jax.devices()[:1] if self.mesh is None
                else list(self.mesh.devices.flat))

    def _partition_bytes(self, partitions) -> int:
        # every device holds every member's rows (replicated on a mesh)
        return sum(p.x.nbytes + p.y.nbytes for p in partitions)

    def _put_partitions(self, partitions):
        # k arrays of flat rows, members of any size: no host copy
        where = None if self.mesh is None else NamedSharding(self.mesh, P())
        xs, ys = jax.device_put(([_flat_rows(p.x) for p in partitions],
                                 [p.y for p in partitions]), where)
        return tuple(xs), tuple(ys)

    def _put_chunk(self, chunk):
        # device_put is async: issuing chunk i+1 while chunk i scans
        # double-buffers the host→device pipeline
        if self.mesh is None:
            return jax.device_put(chunk)
        return jax.device_put(chunk, sharding.stacked_batch_shardings(
            chunk, self.mesh, member_axis=1))

    def _epoch_dispatch(self, cfg, params_k, stats_k, cur, lr,
                        solve_each_batch, use_pallas, masked, rows):
        return _stacked_epoch(cfg, params_k, stats_k, *cur, lr,
                              solve_each_batch=solve_each_batch,
                              use_pallas=use_pallas, masked=masked, rows=rows)

    def _solve(self, cfg, stats_k):
        return elm.solve_beta(stats_k, cfg.elm_lambda)

    def _snapshot(self, params_k, beta_k):
        return StackedMembers(params_k, beta_k)

    def _averaged(self, params_k, beta_k, weights, telemetry,
                  gossip_rounds=None):
        if gossip_rounds is not None:
            avg_cnn, avg_beta = _gossip_reduce(
                (params_k, beta_k),
                None if weights is None else jnp.asarray(weights,
                                                         jnp.float32),
                rounds=gossip_rounds)
        else:
            avg_cnn, avg_beta = average_member_dim((params_k, beta_k),
                                                   weights=weights)
        return CNNELMModel(avg_cnn, avg_beta)

    def _val_errors(self, cfg, params_k, beta_k, validation, use_pallas,
                    telemetry) -> np.ndarray:
        xv, yv = validation
        preds = []
        for i in range(0, len(xv), _VAL_BATCH):
            preds.append(np.asarray(_member_predictions(
                cfg, params_k, beta_k, jnp.asarray(xv[i:i + _VAL_BATCH]),
                use_pallas=use_pallas)))
            _bump(telemetry)
        return _val_error_rates(np.concatenate(preds, axis=1), yv)

    def _sync(self, params_k, weights, gossip_rounds=None):
        w = None if weights is None else jnp.asarray(weights, jnp.float32)
        params_k = (_gossip_round_sync(params_k, w, rounds=gossip_rounds)
                    if gossip_rounds is not None
                    else _round_sync(params_k, w))
        if self.mesh is not None:
            params_k = jax.device_put(
                params_k, sharding.member_dim_shardings(params_k, self.mesh))
        return params_k


# ---------------------------------------------------------------------------
# MeshExecutor: explicit shard_map over the 'pod' axis
# ---------------------------------------------------------------------------

def _member_specs(tree, mesh):
    """shard_map specs for member-stacked arrays — the spec twin of the
    ``member_dim_shardings`` placement contract (inside MeshExecutor the
    member count is always padded to a pod multiple, so the resolver's
    replication fallback never fires)."""
    return sharding.member_dim_specs(tree, mesh)


def _member_axes(mesh) -> tuple:
    """The mesh axes carrying the member dim: ``('host', 'pod')`` on the
    hierarchical 2-D topology, ``('pod',)`` on the flat 1-D one."""
    return ("host", "pod") if "host" in mesh.shape else ("pod",)


def _member_axis_entry(mesh):
    """The PartitionSpec entry for the member dim on ``mesh`` — the tuple
    ``('host', 'pod')`` or the bare ``'pod'``."""
    axes = _member_axes(mesh)
    return axes if len(axes) > 1 else axes[0]


def _psum_weighted_mean(tree, weights, mesh):
    """Mesh-topology dispatch: the flat ONE-collective psum on a 1-D
    member mesh (the bit-reference), the staged TWO-collective
    intra-host → inter-host psum on the 2-D ``('host', 'pod')`` mesh."""
    axes = _member_axes(mesh)
    if len(axes) == 1:
        return psum_weighted_mean_members(tree, weights, axes[0])
    return hierarchical_psum_weighted_mean_members(tree, weights, axes)


def _replicated_specs(tree):
    return jax.tree.map(lambda a: P(*([None] * a.ndim)), tree)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "solve_each_batch",
                                             "use_pallas", "masked"),
                   donate_argnames=("params_k", "stats_k"))
def _mesh_epoch(cfg, mesh, params_k, stats_k, xb, tb, mb, lr, *,
                solve_each_batch: bool, use_pallas: bool, masked: bool,
                rows=None):
    """One epoch chunk shard_map-ed over 'pod': each pod scans ONLY its
    local members — the identical ``cnn_elm.stacked_epoch_scan`` body on a
    k/p-member slice, ZERO collectives (members are independent until the
    Reduce). The donated carry keeps params/stats resident and sharded.
    With ``rows`` (member-sharded, resident across epochs) each pod
    gathers its batches from its OWN members' rows: no row crosses a
    chip."""
    pspecs = _member_specs(params_k, mesh)
    sspecs = _member_specs(stats_k, mesh)
    bspecs = sharding.stacked_batch_specs((xb, tb, mb), mesh, member_axis=1)
    # the step record (nb, k, ...) is member-sharded on its second dim
    out_specs = (pspecs, sspecs) + ((jax.tree.map(
        lambda s: P(None, *s), pspecs, is_leaf=lambda x: isinstance(x, P)),)
        if solve_each_batch else ())

    def local(p, s, x, t, m, lr_, r):
        return stacked_epoch_scan(cfg, p, s, x, t, m, lr_,
                                  solve_each_batch=solve_each_batch,
                                  use_pallas=use_pallas, masked=masked,
                                  rows=r)

    return shard_map(local, mesh=mesh,
                     in_specs=(pspecs, sspecs) + bspecs
                     + (P(), _member_specs(rows, mesh)),
                     out_specs=out_specs)(
        params_k, stats_k, xb, tb, mb, lr, rows)


@functools.partial(jax.jit, static_argnames=("mesh", "lam"))
def _mesh_solve(mesh, stats_k, lam):
    """β for every member, pod-sharded: each device Cholesky-factorises only
    its local (k/p, F, F) stats — the solve never gathers; only the final
    snapshot (or the one-collective Reduce) leaves the mesh."""
    def local(s):
        return elm.solve_beta(s, lam)

    return shard_map(local, mesh=mesh,
                     in_specs=(_member_specs(stats_k, mesh),),
                     out_specs=P(_member_axis_entry(mesh), None, None))(
        stats_k)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _mesh_reduce(mesh, tree, weights):
    """The Reduce as the minimum in-mesh collective count: weighted mean
    over the global member dim via one flat psum on a 1-D mesh (the
    bit-reference) or the staged intra-host → inter-host pair on the 2-D
    ``('host', 'pod')`` mesh — ONE or TWO all-reduces, never more,
    replicated output. ``weights`` is the full padded member-weight
    vector — zeros drop padded members exactly."""
    def local(t, w):
        with jax.named_scope(scopes.REDUCE):
            return _psum_weighted_mean(t, w, mesh)

    return shard_map(local, mesh=mesh,
                     in_specs=(_member_specs(tree, mesh),
                               P(_member_axis_entry(mesh))),
                     out_specs=_replicated_specs(
                         jax.tree.map(lambda a: a[0], tree)))(tree, weights)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _mesh_sync(mesh, params_k, weights):
    """The inter-round sync, same collective budget as ``_mesh_reduce``
    (one all-reduce flat, two hierarchical): the psum weighted mean
    broadcast straight back to the local member slots — params never
    leave the mesh between rounds. NOT donated: the round's lazy
    snapshot/averaged closures may still read the pre-sync params after
    the sync fires (same contract as ``_round_sync``)."""
    pspecs = _member_specs(params_k, mesh)

    def local(p, w):
        with jax.named_scope(scopes.REDUCE):
            avg = _psum_weighted_mean(p, w, mesh)
            k_local = jax.tree.leaves(p)[0].shape[0]
            return broadcast_member_dim(avg, k_local)

    return shard_map(local, mesh=mesh,
                     in_specs=(pspecs, P(_member_axis_entry(mesh))),
                     out_specs=pspecs)(params_k, weights)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "use_pallas"))
def _mesh_val_predict(cfg, mesh, params_k, beta_k, x, *,
                      use_pallas: Optional[bool]):
    """The boosted strategy's IN-MESH scoring program: each pod scores
    the replicated validation batch under only its local members (the
    same vmap body as ``_member_predictions``, shard_map-ed over the
    member axes) — k/p-parallel, ZERO collectives; the resulting (k,)
    error vector then rides the existing one-psum/two-psum Reduce as its
    weight vector."""
    pspecs = _member_specs(params_k, mesh)
    entry = _member_axis_entry(mesh)

    def local(p, b, xv):
        def one(pp, bb):
            h = cnn.features(cfg, pp, xv, use_pallas=use_pallas)
            return jnp.argmax(elm.predict(h, bb), axis=-1)

        return jax.vmap(one)(p, b)

    return shard_map(local, mesh=mesh,
                     in_specs=(pspecs, P(entry, None, None),
                               P(*([None] * x.ndim))),
                     out_specs=P(entry, None))(params_k, beta_k, x)


@functools.partial(jax.jit, static_argnames=("mesh", "rounds"))
def _mesh_gossip_sync(mesh, params_k, weights, *, rounds: int):
    """The GOSSIP inter-round sync: ring-neighbor consensus on the flat
    'pod' axis — each pod pre-aggregates its local members into one ring
    node, mixes with its two neighbors for ``rounds`` unrolled mixing
    rounds (two ``lax.ppermute`` collectives each, ZERO all-reduces —
    ``analysis.hlo.check_gossip_sync`` pins the budget), then resets its
    local member slots to its OWN consensus estimate. Members on
    different pods genuinely diverge between rounds — the decentralized
    regime, vs ``_mesh_sync``'s global broadcast."""
    pspecs = _member_specs(params_k, mesh)
    p = mesh.shape["pod"]

    def local(prm, w):
        with jax.named_scope(scopes.REDUCE):
            num, den = gossip_ring_mix(prm, w, "pod", rounds, p)
            ref = jax.tree.map(lambda a: a[0], prm)
            est = jax.tree.map(
                lambda s, t: (s / jnp.maximum(den, 1e-30)).astype(t.dtype),
                num, ref)
            k_local = jax.tree.leaves(prm)[0].shape[0]
            return broadcast_member_dim(est, k_local)

    return shard_map(local, mesh=mesh,
                     in_specs=(pspecs, P("pod")),
                     out_specs=pspecs)(params_k, weights)


@functools.partial(jax.jit, static_argnames=("mesh", "rounds"))
def _mesh_gossip_state(mesh, tree, weights, *, rounds: int):
    """Every pod's raw consensus state after ``rounds`` mixing rounds:
    the (p, ...)-stacked f32 numerator trees and (p,) weight masses,
    gathered off-mesh with NO global collective (the out-spec
    concatenates per-pod shards). The host divides per pod for the
    consensus iterates (the convergence gate's subject) and reads
    ``sum(num)/sum(den)`` for the published model — sums the mixing
    stencil leaves invariant."""
    def local(t, w):
        with jax.named_scope(scopes.REDUCE):
            num, den = gossip_ring_mix(t, w, "pod", rounds,
                                       mesh.shape["pod"])
            return jax.tree.map(lambda a: a[None], num), den[None]

    num_specs = jax.tree.map(
        lambda a: P(*(("pod",) + (None,) * (a.ndim - 1))), tree)
    return shard_map(local, mesh=mesh,
                     in_specs=(_member_specs(tree, mesh), P("pod")),
                     out_specs=(num_specs, P("pod")))(tree, weights)


@functools.partial(jax.jit, static_argnames=("mesh", "lam"))
def _mesh_e2lm_beta(mesh, stats_k, lam):
    """E²LM cross-member Reduce (``e2lm.psum_stats``): sum every member's
    sufficient statistics over the mesh (both member axes at once on the
    hierarchical topology) and solve ONE global β — the exact
    no-partition ELM readout, computed from the Map phase's stats without
    ever gathering them. Padded members hold zero stats, so they vanish
    from the sums by construction."""
    def local(s):
        loc = type(s)(s.u.sum(0), s.v.sum(0), s.n.sum(0))
        return elm.solve_beta(psum_stats(loc, _member_axes(mesh)), lam)

    return shard_map(local, mesh=mesh,
                     in_specs=(_member_specs(stats_k, mesh),),
                     out_specs=P(None, None))(stats_k)


class MeshExecutor(_StackedBase):
    """The multi-pod Map phase: stacked scan body shard_map-ed over the
    member mesh axes.

    ``mesh`` must carry a ``'pod'`` axis (default: a 1-D ``('pod',)`` mesh
    over every visible device — ``repro.launch.mesh.make_member_mesh``).
    With an additional ``'host'`` axis (``make_member_mesh(hosts=...)``)
    the member dim shards over ``('host', 'pod')`` jointly and every
    Reduce/sync stages hierarchically: intra-host psum then inter-host
    psum. Members pad to a device-count multiple (zero data, zero mask,
    zero Reduce weight — arithmetically invisible, stripped from the
    snapshot). The per-round cost model: epochs/rounds scan dispatches
    with zero collectives, then exactly ONE (flat 1-D) or TWO
    (hierarchical 2-D) all-reduces for the sync (or the final Reduce),
    regardless of fleet size. See docs/perf.md §Mesh scaling."""

    name = "mesh"

    def __init__(self, mesh=None):
        self.mesh = mesh

    def _begin(self, cfg, k):
        if self.mesh is None:
            self.mesh = make_member_mesh()
        if "pod" not in self.mesh.shape:
            raise ValueError(
                f"MeshExecutor needs a mesh with a 'pod' axis, got axes "
                f"{tuple(self.mesh.shape)}")
        self._cfg = cfg
        self._k = k
        slots = 1                               # devices holding members:
        for a in _member_axes(self.mesh):       # pods, or hosts x pods
            slots *= self.mesh.shape[a]
        self._k_pad = -(-k // slots) * slots    # ceil to a slot multiple
        self._k_local = self._k_pad // slots    # member slots per device
        spec = sharding.resolve_spec((self._k_pad,), ("member",), self.mesh)
        if spec[0] is None:      # padding guarantees divisibility, so the
            raise ValueError(    # fallback can only mean bad custom rules
                f"'member' did not resolve to a mesh axis for k_pad="
                f"{self._k_pad} on mesh {dict(self.mesh.shape)}")
        # the padded member-weight template: uniform weight 1 on real
        # members, 0 on padding (explicit weights overwrite the prefix)
        self._member_mask = np.array([1.0] * k + [0.0] * (self._k_pad - k),
                                     np.float32)

    def _weights_dev(self, weights):
        w = self._member_mask.copy()
        if weights is not None:
            w[:self._k] = np.asarray(weights, np.float32)
        return jax.device_put(
            jnp.asarray(w),
            NamedSharding(self.mesh, P(_member_axis_entry(self.mesh))))

    def _place_params(self, init_params):
        params_k = broadcast_member_dim(init_params, self._k_pad)
        return jax.device_put(
            params_k, sharding.member_dim_shardings(params_k, self.mesh))

    def _zero_stats(self, F, C):
        stats_k = elm.zero_stats_stacked(self._k_pad, F, C)
        return jax.device_put(
            stats_k, sharding.member_dim_shardings(stats_k, self.mesh))

    def _pad_epoch(self, *arrays):
        pad = self._k_pad - self._k
        if not pad:
            return arrays
        return tuple(np.concatenate(
            [a, np.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], axis=1)
            for a in arrays)

    def _devices(self):
        return list(self.mesh.devices.flat)

    def _partition_bytes(self, partitions) -> int:
        # each device holds its member slots, padded to the longest member
        p = partitions[0]
        row = (p.x.nbytes + p.y.nbytes) // len(p.x)
        return self._k_local * max(len(p.x) for p in partitions) * row

    def _put_partitions(self, partitions):
        # one (k_pad, n_max, ...) array per field, zero-padded on the host
        # and placed member-sharded: each pod receives its own members
        n = max(len(p.x) for p in partitions)

        def stacked(fields):
            out = np.zeros((self._k_pad, n) + fields[0].shape[1:],
                           fields[0].dtype)
            for i, a in enumerate(fields):
                out[i, :len(a)] = a
            return out

        data = (stacked([_flat_rows(p.x) for p in partitions]),
                stacked([p.y for p in partitions]))
        return jax.device_put(data,
                              sharding.member_dim_shardings(data, self.mesh))

    def _put_chunk(self, chunk):
        return jax.device_put(chunk, sharding.stacked_batch_shardings(
            chunk, self.mesh, member_axis=1))

    def _epoch_dispatch(self, cfg, params_k, stats_k, cur, lr,
                        solve_each_batch, use_pallas, masked, rows):
        return _mesh_epoch(cfg, self.mesh, params_k, stats_k, *cur, lr,
                           solve_each_batch=solve_each_batch,
                           use_pallas=use_pallas, masked=masked, rows=rows)

    def _solve(self, cfg, stats_k):
        self._last_stats = stats_k          # for e2lm_global_beta
        return _mesh_solve(self.mesh, stats_k, cfg.elm_lambda)

    def _snapshot(self, params_k, beta_k):
        """The final UNSHARDED snapshot: gather off-mesh, strip the padded
        member slots. With the averaged model (``_averaged``) the only
        points where member arrays leave the mesh: what the run hands back
        lives on one device, where the eval and serving surfaces run (a
        Pallas kernel cannot be partitioned over a mesh by XLA)."""
        take = lambda a: jnp.asarray(np.asarray(a)[:self._k])
        return StackedMembers(jax.tree.map(take, params_k), take(beta_k))

    def _host_stats(self, stats_k) -> elm.ELMStats:
        return elm.ELMStats(*(np.asarray(a)[:self._k] for a in stats_k))

    def _record_params(self, params):
        return jax.tree.map(
            lambda a: jnp.asarray(np.asarray(a)[:, :self._k]), params)

    def _check_gossip(self):
        if "host" in self.mesh.shape:
            raise ValueError(
                "gossip rides the flat 1-D 'pod' ring — the hierarchical "
                "('host', 'pod') mesh has no single ring axis; build the "
                "flat member mesh (make_member_mesh()) for gossip syncs")

    def _val_errors(self, cfg, params_k, beta_k, validation, use_pallas,
                    telemetry) -> np.ndarray:
        xv, yv = validation
        preds = []
        for i in range(0, len(xv), _VAL_BATCH):
            preds.append(np.asarray(_mesh_val_predict(
                cfg, self.mesh, params_k, beta_k,
                jnp.asarray(xv[i:i + _VAL_BATCH]), use_pallas=use_pallas)))
            _bump(telemetry)
        return _val_error_rates(
            np.concatenate(preds, axis=1)[:self._k], yv)

    def _averaged(self, params_k, beta_k, weights, telemetry,
                  gossip_rounds=None):
        _bump(telemetry)
        w = self._weights_dev(weights)
        if gossip_rounds is not None:
            num, den = _mesh_gossip_state(
                self.mesh, (params_k, beta_k), w, rounds=gossip_rounds)
            den = np.asarray(den, np.float32)
            read = lambda s, ref: jnp.asarray(
                (np.asarray(s, np.float32).sum(axis=0) / den.sum()
                 ).astype(ref.dtype))
            num_cnn, num_beta = num
            avg_cnn = jax.tree.map(read, num_cnn, params_k)
            avg_beta = read(num_beta, beta_k)
        else:
            # the Reduce's replicated result, gathered off the mesh
            avg_cnn, avg_beta = jax.tree.map(
                lambda a: jnp.asarray(np.asarray(a)),
                _mesh_reduce(self.mesh, (params_k, beta_k), w))
        return CNNELMModel(avg_cnn, avg_beta)

    def _sync(self, params_k, weights, gossip_rounds=None):
        w = self._weights_dev(weights)
        if gossip_rounds is not None:
            return _mesh_gossip_sync(self.mesh, params_k, w,
                                     rounds=gossip_rounds)
        return _mesh_sync(self.mesh, params_k, w)

    def e2lm_global_beta(self):
        """After ``execute``: the E²LM global readout — ONE
        ``e2lm.psum_stats`` reduce of every member's final-epoch stats,
        solved into the single β a no-partition ELM would produce."""
        if not hasattr(self, "_last_stats"):
            raise RuntimeError("e2lm_global_beta needs a completed execute()"
                               " (the final-round solve records the stats)")
        return _mesh_e2lm_beta(self.mesh, self._last_stats,
                               self._cfg.elm_lambda)
