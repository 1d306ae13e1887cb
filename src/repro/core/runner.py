"""Composable MapReduce runner — the paper's Algorithm 2 as explicit
config objects instead of one 8-kwarg entry point.

* ``MapConfig``    — everything the Map phase needs: epochs, lr schedule,
                     batch size, backend (an ``executor`` name:
                     ``"sequential"`` host loop, or ``"stacked"`` /
                     ``"mesh"`` — the vmap+scan fast path on a member
                     mesh of the first device / of every device), kernel
                     backend, member mesh, chunking, and THE member seed
                     rule.
* ``ReduceConfig`` — the Reduce strategy (any
                     ``repro.core.reduce_strategies`` registry entry:
                     uniform / shard_weighted / boosted / gossip /
                     explicit weights) and ``rounds``: ``rounds > 1``
                     interleaves Map epochs with
                     ``broadcast_member_dim(average_member_dim(...))`` —
                     the parallel-SGD regime (MapReduce-based Deep
                     Learning, arXiv:1510.02709); ``rounds = 1`` is the
                     paper's single final average.
* ``AveragingRun`` — binds a model config to the two phase configs;
                     ``.run(partitions, key)`` returns a ``RunResult`` with
                     members, the averaged model, per-round records
                     (wall-time, dispatch counts, eval-hook results) and
                     whole-run telemetry.
* ``Ensemble``     — batched serving surface over ``StackedMembers``:
                     k models scored in ONE vmap dispatch per eval batch,
                     with ``"mean"`` (mean-score) and ``"vote"`` (majority)
                     combination modes, per-member ``evaluate``/``kappa``,
                     and the vectorised confusion-matrix kappa.

Seed rule (shared by BOTH backends): member ``i`` draws its per-epoch batch
permutations from ``np.random.default_rng(MapConfig.seed + i)`` — see
``MapConfig.member_seed``. This replaces the sequential path's hardcoded
``1000 + i`` and the stacked path's ``seed_base`` with one documented rule,
so backend equivalence is by-construction (``MapConfig.seed`` defaults to
the historical 1000).

The execution layer behind ``MapConfig.backend`` lives in
``repro.core.executor`` (the pre-runner ``distributed_cnn_elm`` /
``evaluate`` / ``kappa`` shims are gone — docs/api.md has the migration
table; ``evaluate_model``/``kappa_model`` below are the single-model
entries).

Fault tolerance (this layer is what makes the run preemptible):

* ``CheckpointConfig`` — per-round atomic checkpoints
  (``repro.checkpoint.run_state``): pre-sync member snapshot +
  final-epoch ELMStats + averaged model + the post-sync resume params
  and the rng/round cursor. ``AveragingRun.resume(partitions, key, dir)``
  continues a killed run BIT-IDENTICALLY to the uninterrupted one (the
  sequential backend checkpoints/resumes per member instead of per
  round).
* ``ElasticSchedule``/``ElasticEvent`` on ``ReduceConfig.elastic`` — the
  paper's "trained asynchronously" Map phase meets real cluster churn:
  members JOIN at a round boundary from that boundary's average (Alg. 2
  line 3's shared-init rule applied mid-training) and LEAVE with their
  final weighted contribution kept in every later average — both backed
  by ``repro.core.elastic.ElasticGroup``, re-stacked per round block on
  every backend.
* ``repro.core.faults`` — injectable crashes (after any durable
  checkpoint) and straggler-drop schedules for exercising all of it.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import run_state
from repro.core import elastic, elm, reduce_strategies
from repro.core.cnn_elm import (CNNELMModel, StackedMembers,  # noqa: F401
                                StepRecord, stack_models)
from repro.core.executor import (BACKENDS, CheckpointConfig,  # noqa: F401
                                 ExecutionPlan, make_executor)
from repro.core.reduce_strategies import (ReduceContext,  # noqa: F401
                                          ReduceStrategy)
from repro.data.partition import Partition
from repro.kernels import resolve_use_pallas
from repro.models import cnn

COMBINES = ("mean", "vote")
SYNCS = ("rounds", "drift")


# ---------------------------------------------------------------------------
# Phase configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MapConfig:
    """Map-phase configuration (Alg. 2 lines 4-17, one member per shard).

    ``backend`` names an ``executor`` implementation:
    ``"sequential"`` — the faithful host-loop reference
    (``cnn_elm.train_member`` per member, 3 dispatches per batch);
    ``"stacked"`` and ``"mesh"`` — the stacked executor: all members
    vmapped into one donated scan per epoch chunk on a member mesh, each
    device scanning its own members (padded to a device multiple when k
    doesn't divide it), β solved where they live, the Reduce and every
    round sync one program with no collective on one device and ONE
    all-reduce (TWO on a ('host', 'pod') mesh) across devices. ``mesh``
    has one meaning: which devices hold the members (a ``'pod'`` axis
    required); None is the first device for ``"stacked"`` and a 1-D
    ('pod',) mesh over every visible device for ``"mesh"``.
    ``use_pallas`` forces the kernel backend on ANY path (None = auto
    policy); ``chunk_batches`` streams epochs as double-buffered chunks
    on the stacked executor."""
    epochs: int = 0
    lr_schedule: Optional[Callable[[int], float]] = None
    batch_size: int = 32
    backend: str = "stacked"
    use_pallas: Optional[bool] = None
    mesh: Any = None
    chunk_batches: Optional[int] = None
    seed: int = 1000

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.epochs < 0:
            raise ValueError(f"epochs must be >= 0, got {self.epochs}")
        if self.epochs > 0 and self.lr_schedule is None:
            raise ValueError("epochs > 0 needs an lr_schedule "
                             "(e.g. optim.schedules.dynamic_paper)")

    def member_seed(self, i: int) -> int:
        """THE seed rule: member i's rng stream is
        ``default_rng(seed + i)``; epoch e's batch order is that stream's
        (e+1)-th permutation. Both backends derive from this rule, so their
        equivalence is by-construction."""
        return self.seed + i


@dataclass(frozen=True)
class ElasticEvent:
    """One membership change, applied at the boundary AFTER round
    ``after_round``'s sync: ``leave`` names depart first (their final
    params/stats stay in the group as a retired weighted contribution),
    then the boundary average is taken, then each ``join`` partition
    enters as a new member starting from exactly that average."""
    after_round: int
    join: Tuple[Partition, ...] = ()
    leave: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.after_round < 0:
            raise ValueError(f"after_round must be >= 0, "
                             f"got {self.after_round}")
        if not (self.join or self.leave):
            raise ValueError("an ElasticEvent needs at least one join "
                             "partition or leave name")


@dataclass(frozen=True)
class ElasticSchedule:
    """The membership timeline of an elastic run: a tuple of
    ``ElasticEvent``s (any order; same-boundary events merge). Members are
    named ``m<id>`` in join order — the initial k partitions are
    ``m0..m<k-1>`` and every joiner takes the next id, which also pins its
    rng stream (seed rule: ``MapConfig.seed + id``, the positional rule
    extended to a stable identity so churn never reshuffles anyone's
    data order)."""
    events: Tuple[ElasticEvent, ...] = ()

    def __post_init__(self):
        for ev in self.events:
            if not isinstance(ev, ElasticEvent):
                raise ValueError(f"events must be ElasticEvent, got "
                                 f"{type(ev).__name__}")

    def at(self, boundary: int) -> Tuple[List[Partition], List[str]]:
        """(joins, leaves) applying at the boundary after round
        ``boundary``."""
        joins: List[Partition] = []
        leaves: List[str] = []
        for ev in self.events:
            if ev.after_round == boundary:
                joins.extend(ev.join)
                leaves.extend(ev.leave)
        return joins, leaves

    @property
    def last_boundary(self) -> int:
        return max((ev.after_round for ev in self.events), default=-1)


@dataclass(frozen=True)
class ReduceConfig:
    """Reduce-phase configuration (Alg. 2 lines 18-20 + beyond-paper knobs).

    ``strategy`` — any ``repro.core.reduce_strategies`` entry: a
    registered name (``"uniform"`` — the paper's mean,
    ``"shard_weighted"`` — weights = shard row counts, ``"boosted"`` —
    AdaBoost-style weights from held-out validation error, ``"gossip"``
    — decentralized ring-consensus averaging), a ``ReduceStrategy``
    INSTANCE (``Boosted(floor=...)``, ``Gossip(rounds=...)``,
    ``ExplicitWeights((...,))``), or — deprecated — a bare per-member
    weight sequence, normalised to ``ExplicitWeights`` under a
    ``DeprecationWarning``. The resolved object is ``strategy_obj``.

    ``validation`` — a held-out ``Partition`` scored by strategies that
    weigh members by trained quality (``"boosted"``): after each round's
    Map, every member predicts the slice (``executor._val_predict`` on
    the member mesh) and the per-member error rates become the
    averaging weights. Required by exactly those strategies and rejected
    otherwise (a silently ignored slice would misreport what the weights
    were computed from).

    ``rounds`` — how many averaging events the run's epochs split into.
    ``rounds=1``: train all epochs, average once (paper-faithful).
    ``rounds=r>1``: epochs split into r contiguous blocks; after every
    non-final block the members sync to the (weighted) average — the
    stacked executor only (one program, ``executor._sync``; params never
    leave the devices between rounds).

    ``sync`` — WHEN the averaging events fire. ``"rounds"`` (default) is
    everything above: a fixed count of evenly spaced syncs. ``"drift"``
    fires the same one-all-reduce average when a member's held-out score
    signals concept drift instead — the STREAMING policy: it needs the
    per-chunk drift detectors of ``repro.stream.StreamingRun``, so this
    batch runner (fixed partitions, no drift signal) rejects it with a
    pointer there.

    ``elastic`` — an ``ElasticSchedule`` of join/leave events applied at
    round boundaries (``repro.core.elastic.ElasticGroup`` semantics:
    joiners start from the boundary average, leavers keep a retired
    weighted contribution in every later average). Under elastic
    membership the averaging weights are CUMULATIVE work —
    ``"uniform"`` counts rounds survived, ``"shard_weighted"`` rows
    processed, ``"boosted"`` validation-quality alphas per block — so
    strategies without ``elastic_ok`` (explicit weight sequences, whose
    length would change mid-run, and gossip, whose ring topology has no
    churn story) are rejected. Backends ``"sequential"`` and
    ``"stacked"`` (re-stacked per round block); needs ``rounds >= 2``
    and SGD epochs."""
    strategy: Union[str, Sequence[float], ReduceStrategy] = "uniform"
    rounds: int = 1
    sync: str = "rounds"
    elastic: Optional[ElasticSchedule] = None
    validation: Optional[Partition] = None

    def __post_init__(self):
        strat = reduce_strategies.resolve(self.strategy, _warn_stacklevel=4)
        object.__setattr__(self, "_strategy_obj", strat)
        if self.sync not in SYNCS:
            raise ValueError(f"sync must be one of {SYNCS}, "
                             f"got {self.sync!r}")
        if self.rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {self.rounds}")
        if strat.requires_validation and self.validation is None:
            raise ValueError(
                f"strategy {strat.name!r} weighs members by held-out "
                f"validation error — pass "
                f"ReduceConfig(validation=Partition(xv, yv))")
        if self.validation is not None and not strat.requires_validation:
            raise ValueError(
                f"strategy {strat.name!r} does not score a validation "
                f"slice — drop ReduceConfig.validation (it would be "
                f"silently ignored)")
        if self.sync == "drift" and self.rounds != 1:
            raise ValueError(
                "sync='drift' replaces the rounds cadence — leave rounds=1 "
                "(drift-triggered syncs fire per chunk, not per round)")
        if self.sync == "drift" and self.elastic is not None:
            raise ValueError("sync='drift' does not combine with an elastic "
                             "schedule")
        if self.elastic is not None:
            if not isinstance(self.elastic, ElasticSchedule):
                raise ValueError("elastic must be an ElasticSchedule")
            if not strat.elastic_ok:
                if strat.name == "explicit":
                    raise ValueError(
                        "explicit weight sequences cannot follow membership "
                        "changes — use 'uniform', 'shard_weighted' or "
                        "'boosted' with an elastic schedule")
                raise ValueError(
                    f"strategy {strat.name!r} does not extend to "
                    f"membership churn (elastic_ok=False) — use "
                    f"'uniform', 'shard_weighted' or 'boosted' with an "
                    f"elastic schedule")
            if self.rounds < 2:
                raise ValueError("an elastic schedule needs rounds >= 2 — "
                                 "events apply between rounds")
            if self.elastic.last_boundary > self.rounds - 2:
                raise ValueError(
                    f"elastic event after round "
                    f"{self.elastic.last_boundary} has no following round "
                    f"(rounds={self.rounds}; boundaries are "
                    f"0..{self.rounds - 2})")

    @property
    def strategy_obj(self) -> ReduceStrategy:
        """The resolved ``ReduceStrategy`` behind ``strategy``."""
        return self._strategy_obj

    def resolve_weights(self, partitions: Sequence[Partition]
                        ) -> Optional[List[float]]:
        """The static per-member weights for these partitions: None for
        uniform, shard row counts, explicit weights, ... — whatever
        ``strategy_obj.weights`` resolves from the partition shapes.
        Strategies that weigh by trained-member quality (``boosted``)
        cannot resolve statically — the runner routes them through the
        per-round ``ExecutionPlan.weight_fn`` path instead."""
        return self._strategy_obj.weights(ReduceContext(
            num_members=len(partitions),
            rows=tuple(len(p.x) for p in partitions)))


# ---------------------------------------------------------------------------
# Run result
# ---------------------------------------------------------------------------

@dataclass
class RoundRecord:
    """Telemetry for one averaging round: the global epoch span it covered,
    its wall time, how many device dispatches it issued, and whatever the
    caller's ``round_hook(round, averaged)`` returned (None without one)."""
    round: int
    epoch_start: int
    epoch_end: int
    wall_time_s: float
    dispatches: int
    hook: Any = None


@dataclass
class RunResult:
    """Everything a Map/Reduce run produced. ``stacked`` is the live
    ``StackedMembers`` on the stacked backend (None on sequential);
    ``rounds`` has one ``RoundRecord`` per averaging round; ``dispatches``
    counts jit round-trips the Map engine issued (the stacked/sequential
    ratio is exactly the dispatch saving docs/perf.md describes);
    ``device_epoch_builds``/``host_epoch_builds`` count the stacked
    layouts' epochs gathered on the device from partitions uploaded once,
    or built on the host because the partitions do not fit.
    ``step_record`` (``cnn_elm.StepRecord``): on the stacked layouts, every
    member's params at the start of each step of the last SGD epoch,
    (nb, k, ...) per leaf, beside the steps' padding mask; None where no
    SGD step ran (``epochs=0``) and on sequential. The params after the
    last step are ``stacked``'s."""
    cfg: Any
    members: List[CNNELMModel]
    averaged: CNNELMModel
    stacked: Optional[StackedMembers]
    rounds: List[RoundRecord]
    wall_time_s: float
    dispatches: int
    backend: str
    round_syncs: int = 0     # inter-round average+broadcast dispatches
                             # (rounds - 1 on the stacked backend)
    resumed: bool = False    # True when rebuilt/continued from a checkpoint
    device_epoch_builds: int = 0
    host_epoch_builds: int = 0
    step_record: Optional[StepRecord] = None

    def ensemble(self, combine: str = "mean") -> "Ensemble":
        """The k members as a batched scoring surface."""
        if self.stacked is not None:
            return Ensemble(self.cfg, self.stacked, combine=combine)
        return Ensemble.from_models(self.cfg, self.members, combine=combine)


@dataclass
class ElasticRoundRecord:
    """One round of an elastic run: who was in it, who changed at its
    boundary, wall time, and the round_hook result (hooks see the BOUNDARY
    average — leave contributions in, joiners not yet trained)."""
    round: int
    members: List[str]
    joined: List[str]
    left: List[str]
    wall_time_s: float
    hook: Any = None


@dataclass
class ElasticRunResult:
    """An elastic run's output. ``members`` are the SURVIVING members by
    name; ``averaged`` is the ``ElasticGroup`` Reduce — survivors' final
    models plus every retired member's frozen weighted contribution;
    ``group`` is the live ``ElasticGroup`` (retired params/stats, cumulative
    step weights) for anything deeper, e.g. ``group.solve_head(lam)`` — the
    E²LM readout over every member's recorded stats."""
    cfg: Any
    members: Dict[str, CNNELMModel]
    averaged: CNNELMModel
    group: elastic.ElasticGroup
    rounds: List[ElasticRoundRecord]
    wall_time_s: float
    dispatches: int
    backend: str
    resumed: bool = False    # True when rebuilt/continued from a checkpoint

    def ensemble(self, combine: str = "mean") -> "Ensemble":
        """The surviving members as a batched scoring surface."""
        return Ensemble.from_models(self.cfg, list(self.members.values()),
                                    combine=combine)


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------

@dataclass
class AveragingRun:
    """One distributed-averaging experiment: model config + Map config +
    Reduce config. ``run(partitions, key)`` executes Algorithm 2 (init once,
    Map every shard, Reduce by averaging — ``rounds`` times; with
    ``ReduceConfig.elastic`` set, membership changes apply between rounds
    and the result is an ``ElasticRunResult``). ``resume(partitions, key,
    ckpt_dir)`` continues a checkpointed run bit-identically."""
    cfg: Any
    map_cfg: MapConfig = field(default_factory=MapConfig)
    reduce_cfg: ReduceConfig = field(default_factory=ReduceConfig)

    def run(self, partitions: Sequence[Partition], key, *,
            round_hook: Optional[Callable[[int, CNNELMModel], Any]] = None,
            checkpoint: Optional[CheckpointConfig] = None):
        """``round_hook(r, averaged)`` (optional) is evaluated after every
        round's Reduce with the round index and that round's averaged model;
        its return value lands in ``RunResult.rounds[r].hook`` — the
        per-round eval surface (accuracy curves across communication
        rounds, early stopping, ...). ``checkpoint`` turns on per-round
        (stacked layouts) / per-member (sequential) atomic checkpointing;
        checkpointed intermediate rounds pay their β solve + averaged-model
        build (they are saved), where hook-less uncheckpointed rounds
        skip both."""
        if self.reduce_cfg.sync == "drift":
            raise ValueError(
                "ReduceConfig(sync='drift') is the streaming policy — it "
                "needs per-chunk drift detectors, so drive it through "
                "repro.stream.StreamingRun; this batch runner syncs on "
                "the rounds cadence")
        if self.reduce_cfg.elastic is not None:
            return self._run_elastic(partitions, key, round_hook,
                                     checkpoint=checkpoint)
        return self._run(partitions, key, round_hook=round_hook,
                         checkpoint=checkpoint)

    def resume(self, partitions: Sequence[Partition], key, ckpt_dir: str, *,
               round_hook: Optional[Callable] = None,
               every: int = 1) -> RunResult:
        """Continue a checkpointed run from ``ckpt_dir`` — bit-identical to
        the uninterrupted run. Pass the SAME partitions and key the
        original run got (the checkpoint fingerprint refuses anything
        else). A finished run's final checkpoint rebuilds the result
        without recomputation; otherwise the remaining rounds (stacked
        layouts) or members (sequential) execute, checkpointing into the
        same directory — pass the original ``CheckpointConfig.every`` to
        keep its cadence (and its skipped-round β-solve savings) — and
        ``RunResult.rounds`` covers only the re-run rounds."""
        m, rc = self.map_cfg, self.reduce_cfg
        if rc.elastic is not None:
            return self._resume_elastic(partitions, key, ckpt_dir,
                                        round_hook=round_hook, every=every)
        expected = self._fingerprint(partitions)
        # the newest VALID round: a torn round-<r>.npz (writer killed
        # mid-save without the atomic rename, torn copy on a shared fs)
        # means that round never durably completed — resume from the
        # newest readable one and let its re-run overwrite the wreckage
        latest = run_state.latest_ready_round(ckpt_dir)
        if latest is not None:
            state = run_state.restore_round(ckpt_dir, latest)
            run_state.check_fingerprint(state.meta, expected)
            if state.final:
                # the run completed before the kill: its artifacts ARE the
                # result — rebuild, bit-identical by construction. A
                # round_hook still fires for the restored final round (on
                # the saved averaged model) so hook-driven pipelines see
                # their record; earlier rounds were not saved and stay
                # silent.
                members = state.members.unstack()
                stacked = None if m.backend == "sequential" \
                    else state.members
                records: List[RoundRecord] = []
                if round_hook is not None:
                    per_round = m.epochs // rc.rounds
                    records.append(RoundRecord(
                        state.round, state.round * per_round,
                        (state.round + 1) * per_round if m.epochs else 0,
                        0.0, 0, round_hook(state.round, state.averaged)))
                return RunResult(self.cfg, members, state.averaged, stacked,
                                 records, 0.0, 0, m.backend, 0,
                                 resumed=True, step_record=state.step_record)
            return self._run(
                partitions, key, round_hook=round_hook,
                checkpoint=CheckpointConfig(dir=ckpt_dir, every=every),
                start_round=state.round + 1,
                init_override=state.resume_params, resumed=True)
        if m.backend == "sequential":
            done = {}
            for i in run_state.completed_members(ckpt_dir):
                model, stats, meta = run_state.restore_member(ckpt_dir, i)
                run_state.check_fingerprint(meta, expected)
                done[i] = (model, stats)
            if done:
                return self._run(
                    partitions, key, round_hook=round_hook,
                    checkpoint=CheckpointConfig(dir=ckpt_dir, every=every),
                    completed=done, resumed=True)
        raise FileNotFoundError(f"no resumable checkpoint in {ckpt_dir}")

    def _fingerprint(self, partitions) -> dict:
        m, rc = self.map_cfg, self.reduce_cfg
        return run_state.run_fingerprint(
            m.backend, partitions, seed=m.seed, epochs=m.epochs,
            rounds=rc.rounds, batch_size=m.batch_size)

    def _run(self, partitions: Sequence[Partition], key, *,
             round_hook: Optional[Callable] = None,
             checkpoint: Optional[CheckpointConfig] = None,
             start_round: int = 0, init_override=None,
             completed: Optional[dict] = None,
             resumed: bool = False) -> RunResult:
        m, rc = self.map_cfg, self.reduce_cfg
        executor = make_executor(m.backend, mesh=m.mesh)
        if rc.rounds > 1 and not executor.supports_rounds:
            raise ValueError("rounds > 1 requires MapConfig(backend="
                             "'stacked') or 'mesh' — the sequential "
                             "reference has no sync point between members")
        if checkpoint is not None and \
                not isinstance(checkpoint, CheckpointConfig):
            raise ValueError("checkpoint must be a CheckpointConfig")
        strat = rc.strategy_obj
        gossip_rounds = (strat.rounds if strat.combine == "gossip"
                         else None)
        weights = weight_fn = None
        if strat.requires_validation:
            # quality-weighted strategies resolve per ROUND from trained
            # members: the executor hands weight_fn the round's lazy
            # snapshot/val_errors closures (backend-native scoring)
            rows = tuple(len(p.x) for p in partitions)
            k = len(partitions)

            def weight_fn(r, snapshot, val_errors):
                return strat.weights(ReduceContext(
                    num_members=k, rows=rows, round=r,
                    val_errors=val_errors))
        else:
            weights = rc.resolve_weights(partitions)
        validation = (None if rc.validation is None
                      else (rc.validation.x, rc.validation.y))
        init = (cnn.init_params(self.cfg, key) if init_override is None
                else init_override)
        telemetry: dict = {"dispatches": 0}
        records: List[RoundRecord] = []
        t0 = time.perf_counter()
        per_round = m.epochs // rc.rounds
        state = {"t": t0, "d": 0, "avg": None}

        def on_round(r: int, snapshot, averaged):
            # per-round Reduce through the EXECUTOR's native path (host
            # mean / member-dim mean / one in-mesh all-reduce) with the
            # same weights the inter-round sync applies, so the hook's
            # averaged model is the model members were actually reset to.
            # Both closures are lazy+cached: hook-less intermediate rounds
            # never pay the β solve or the averaged-model build.
            hooked = None
            if round_hook is not None or r == rc.rounds - 1:
                state["avg"] = averaged()
                if round_hook is not None:
                    hooked = round_hook(r, state["avg"])
            now = time.perf_counter()
            records.append(RoundRecord(
                r, r * per_round, (r + 1) * per_round if m.epochs else 0,
                now - state["t"], telemetry["dispatches"] - state["d"],
                hooked))
            state["t"], state["d"] = now, telemetry["dispatches"]

        plan = ExecutionPlan(
            epochs=m.epochs, lr_schedule=m.lr_schedule,
            batch_size=m.batch_size, seed=m.seed, use_pallas=m.use_pallas,
            chunk_batches=m.chunk_batches, rounds=rc.rounds,
            reduce_weights=weights, on_round=on_round, telemetry=telemetry,
            checkpoint=checkpoint, start_round=start_round,
            completed=completed, weight_fn=weight_fn,
            validation=validation, gossip_rounds=gossip_rounds)
        outcome = executor.execute(self.cfg, init, partitions, plan)
        return RunResult(self.cfg, outcome.members, state["avg"],
                         outcome.stacked, records,
                         time.perf_counter() - t0, telemetry["dispatches"],
                         m.backend, telemetry.get("round_syncs", 0),
                         resumed=resumed,
                         device_epoch_builds=telemetry.get(
                             "device_epoch_builds", 0),
                         host_epoch_builds=telemetry.get(
                             "host_epoch_builds", 0),
                         step_record=outcome.record)

    def _resume_elastic(self, partitions, key, ckpt_dir: str, *,
                        round_hook: Optional[Callable],
                        every: int) -> ElasticRunResult:
        """Continue a checkpointed ELASTIC run — bit-identical to the
        uninterrupted one. The checkpoint holds the full post-boundary
        ``ElasticGroup`` + membership map; joiner PARTITIONS are not
        serialised — they are re-derived by replaying the (caller-held)
        ``ElasticSchedule``, which the fingerprint pins to the original
        run's shape."""
        expected = {**self._fingerprint(partitions), "mode": "elastic"}
        latest = run_state.latest_ready_elastic_round(ckpt_dir)
        if latest is None:
            raise FileNotFoundError(
                f"no resumable elastic checkpoint in {ckpt_dir}")
        state = run_state.restore_elastic_round(ckpt_dir, latest)
        run_state.check_fingerprint(state.meta, expected)
        if state.final:
            # finished before the kill: the group IS the result — rebuild
            # without recomputation (same contract as the fixed-membership
            # final-round rebuild)
            group = state.group
            boundary_model = CNNELMModel(*group.reduce_params())
            members = {n: CNNELMModel(*group.members[n].params)
                       for n in state.living}
            records: List[ElasticRoundRecord] = []
            if round_hook is not None:
                records.append(ElasticRoundRecord(
                    state.round, state.living, [], [], 0.0,
                    round_hook(state.round, boundary_model)))
            return ElasticRunResult(self.cfg, members, boundary_model,
                                    group, records, 0.0, 0,
                                    self.map_cfg.backend, resumed=True)
        return self._run_elastic(
            partitions, key, round_hook,
            checkpoint=CheckpointConfig(dir=ckpt_dir, every=every),
            restored=state, resumed=True)

    def _run_elastic(self, partitions: Sequence[Partition], key,
                     round_hook: Optional[Callable], *,
                     checkpoint: Optional[CheckpointConfig] = None,
                     restored: Optional["run_state.ElasticRoundState"] = None,
                     resumed: bool = False) -> ElasticRunResult:
        """The rounds contract under membership churn: each round is one
        re-stacked executor block over the CURRENT members, and every
        boundary is an ``ElasticGroup`` event — record each member's block
        output with its round weight, retire the leavers (final params +
        stats stay as a frozen weighted contribution), ``sync()`` everyone
        to the boundary average, admit the joiners from exactly that
        average. Member identity (name ``m<id>``) pins the rng stream
        ``default_rng(MapConfig.seed + id)``, fast-forwarded per block by
        the epochs that member has already consumed — a member's data
        order is identical whether or not anyone else churned."""
        m, rc = self.map_cfg, self.reduce_cfg
        sched = rc.elastic
        # all three backends run elastic rounds: each round block is one
        # re-stacked executor.execute() over the CURRENT members, and the
        # stacked executor re-pads its member mesh's slots per block —
        # ghost members are pad-and-mask invisible, so
        # joiners/leavers only change the padded k and the weight vector
        if m.epochs <= 0:
            raise ValueError("elastic membership needs SGD epochs "
                             "(epochs > 0) to split into rounds")
        if m.epochs % rc.rounds:
            raise ValueError(f"epochs ({m.epochs}) must split evenly into "
                             f"rounds ({rc.rounds})")
        if checkpoint is not None and \
                not isinstance(checkpoint, CheckpointConfig):
            raise ValueError("checkpoint must be a CheckpointConfig")
        per_round = m.epochs // rc.rounds
        executor = make_executor(m.backend, mesh=m.mesh)
        telemetry: dict = {"dispatches": 0}
        t0 = time.perf_counter()
        init = cnn.init_params(self.cfg, key)

        strat = rc.strategy_obj

        def block_weights(names, outcome) -> List[float]:
            """Each member's weight for THIS round block — the increment
            of its cumulative ``ElasticGroup`` mass (uniform: 1 per block
            survived; shard_weighted: rows processed; boosted: the
            validation-quality alpha of the member's block output, so a
            leaver's retained contribution carries the quality of the
            work it actually did)."""
            rows = tuple(len(living[n].x) for n in names)
            if strat.requires_validation:
                errs = 1.0 - Ensemble.from_models(
                    self.cfg, outcome.members).evaluate(
                        rc.validation.x, rc.validation.y,
                        use_pallas=m.use_pallas)
                return strat.weights(ReduceContext(
                    num_members=len(names), rows=rows,
                    val_errors=lambda: np.asarray(errs, np.float64)))
            w = strat.weights(ReduceContext(num_members=len(names),
                                            rows=rows))
            return [1.0] * len(names) if w is None else list(w)

        # id -> partition, schedule replayed in boundary order: member ids
        # are assigned by join order, so the replay reproduces the exact
        # id every joiner got in the original run — this is how a RESUME
        # recovers joiner partitions without serialising their data
        parts_by_id: Dict[int, Partition] = dict(enumerate(partitions))
        nid = len(partitions)
        for b in range(rc.rounds - 1):
            for p_new in sched.at(b)[0]:
                parts_by_id[nid] = p_new
                nid += 1
        ck = checkpoint
        ck_meta = ({**run_state.run_fingerprint(
            m.backend, partitions, seed=m.seed, epochs=m.epochs,
            rounds=rc.rounds, batch_size=m.batch_size), "mode": "elastic"}
            if ck is not None else None)
        if restored is None:
            group = elastic.ElasticGroup()
            living: Dict[str, Partition] = {}
            joined_round: Dict[str, int] = {}
            member_id: Dict[str, int] = {}
            beta0 = jnp.zeros((cnn.feature_dim(self.cfg),
                               self.cfg.num_classes), jnp.float32)
            for i, p in enumerate(partitions):
                name = f"m{i}"
                group.join(name, init_params=(init, beta0))
                living[name], joined_round[name], member_id[name] = p, 0, i
            next_id = len(partitions)
            cur_init = init
            start_round = 0
        else:
            group = restored.group
            joined_round = dict(restored.joined_round)
            member_id = dict(restored.member_id)
            living = {n: parts_by_id[member_id[n]] for n in restored.living}
            next_id = restored.next_id
            cur_init = restored.cur_init
            start_round = restored.round + 1
        last_stats: Dict[str, elm.ELMStats] = {}
        records: List[ElasticRoundRecord] = []
        for r in range(start_round, rc.rounds):
            rt = time.perf_counter()
            names = sorted(living, key=member_id.get)      # join order
            plan = ExecutionPlan(
                epochs=per_round,
                lr_schedule=(lambda e, off=r * per_round:
                             m.lr_schedule(off + e)),
                batch_size=m.batch_size, seed=m.seed,
                use_pallas=m.use_pallas, chunk_batches=m.chunk_batches,
                rounds=1, telemetry=telemetry,
                member_seeds=[m.seed + member_id[n] for n in names],
                start_epochs=[(r - joined_round[n]) * per_round
                              for n in names])
            outcome = executor.execute(self.cfg, cur_init,
                                       [living[n] for n in names], plan)
            bw = block_weights(names, outcome)
            for i, n in enumerate(names):
                model = outcome.members[i]
                group.record_step(n, (model.cnn_params, model.beta),
                                  n=bw[i])
                last_stats[n] = elm.ELMStats(
                    outcome.stats.u[i], outcome.stats.v[i],
                    outcome.stats.n[i])
            joined_names: List[str] = []
            left_names: List[str] = []
            if r < rc.rounds - 1:
                joins, leaves = sched.at(r)
                for n in dict.fromkeys(leaves):            # dedup, in order
                    if n not in living:
                        raise ValueError(
                            f"elastic leave {n!r} at boundary {r} is not a "
                            f"living member (living: {sorted(living)})")
                    group.record_stats(n, last_stats.pop(n))
                    group.leave(n)
                    del living[n]
                    left_names.append(n)
                if not living:
                    raise ValueError(
                        f"the leaves at boundary {r} would empty the group")
                # the boundary sync: every survivor restarts from the
                # group average (leave contributions already retired in)
                avg = group.sync()
                boundary_model = CNNELMModel(*avg)
                for p_new in joins:
                    n = f"m{next_id}"
                    # the joiner starts from EXACTLY the boundary average
                    group.join(n, init_params=avg)
                    living[n], joined_round[n] = p_new, r + 1
                    member_id[n] = next_id
                    next_id += 1
                    joined_names.append(n)
                cur_init = avg[0]
            else:
                for n in names:
                    group.record_stats(n, last_stats[n])
                boundary_model = CNNELMModel(*group.reduce_params())
            last = r == rc.rounds - 1
            if ck is not None and (last or (r + 1) % ck.every == 0):
                # post-boundary snapshot: leavers retired, sync applied,
                # joiners admitted — exactly the state round r+1 starts
                # from, so the continuation is bit-identical
                path = run_state.save_elastic_round(
                    ck.dir, r, group=group, cur_init=cur_init,
                    joined_round=joined_round, member_id=member_id,
                    next_id=next_id,
                    meta={**ck_meta, "round": r, "final": last})
                if ck.after_save is not None:
                    ck.after_save("round", r, path)
            hooked = (round_hook(r, boundary_model)
                      if round_hook is not None else None)
            records.append(ElasticRoundRecord(
                r, names, joined_names, left_names,
                time.perf_counter() - rt, hooked))
        members = {n: CNNELMModel(*group.members[n].params)
                   for n in sorted(living, key=member_id.get)}
        return ElasticRunResult(self.cfg, members, boundary_model, group,
                                records, time.perf_counter() - t0,
                                telemetry["dispatches"], m.backend,
                                resumed=resumed)


# ---------------------------------------------------------------------------
# Batched ensemble scoring
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _scores_stacked(cfg, cnn_params_k, beta_k, x, *,
                    use_pallas: Optional[bool] = None):
    """ELM scores of ONE eval batch under ALL k members — a single device
    dispatch (vmap over the member dim) instead of k host round-trips."""
    def one(p, b):
        h = cnn.features(cfg, p, x, use_pallas=use_pallas)
        return elm.predict(h, b)

    return jax.vmap(one)(cnn_params_k, beta_k)


def confusion_matrix(y, preds, num_classes: int) -> np.ndarray:
    """(C, C) confusion matrix via one ``np.add.at`` scatter — O(n) numpy,
    no interpreter loop over samples. Rows = true label, cols = predicted."""
    cm = np.zeros((num_classes, num_classes), np.int64)
    np.add.at(cm, (np.asarray(y, np.int64), np.asarray(preds, np.int64)), 1)
    return cm


def kappa_from_confusion(cm: np.ndarray) -> float:
    """Cohen's kappa from a confusion matrix (paper Table 1c's metric)."""
    cm = cm.astype(np.float64)
    n = cm.sum()
    po = np.trace(cm) / n
    pe = float((cm.sum(0) * cm.sum(1)).sum()) / (n * n)
    return float((po - pe) / (1 - pe + 1e-12))


@dataclass
class Ensemble:
    """k CNN-ELM models behind one batched scoring surface.

    Every public method walks the eval set once in ``batch_size`` slices and
    issues ONE ``_scores_stacked`` dispatch per slice — the k-model analogue
    of the stacked Map phase, closing the ensemble-serving scenario.

    ``combine`` picks the ensemble decision rule for ``predict``/
    ``accuracy``/``kappa_combined``:
    * ``"mean"`` — argmax of the mean member score (prediction averaging;
      for these linear readouts it equals scoring the weight-averaged model
      when members share CNN features, and is the stronger rule when not);
    * ``"vote"`` — majority vote over member argmaxes (ties resolve to the
      LOWEST class index, np.argmax convention — the pinned rule; it
      survives the bucketed/padded serving path too, where padded rows
      are sliced off before any combine and therefore never vote; see
      docs/serving.md and tests/test_serve.py).

    For a production endpoint (continuous batching under a latency SLO,
    bounded compile count, checkpoint hot-reload) see ``bucketed_scorer``
    and ``repro.serve``.
    """
    cfg: Any
    members: StackedMembers
    combine: str = "mean"

    def __post_init__(self):
        if self.combine not in COMBINES:
            raise ValueError(f"combine must be one of {COMBINES}, "
                             f"got {self.combine!r}")

    @classmethod
    def from_models(cls, cfg, models: Sequence[CNNELMModel],
                    combine: str = "mean") -> "Ensemble":
        return cls(cfg, stack_models(models), combine=combine)

    @property
    def k(self) -> int:
        return self.members.k

    def _batched_scores(self, x, batch_size: int,
                        use_pallas: Optional[bool]):
        """Yield (k, B, C) score blocks, one stacked dispatch per block.
        ``use_pallas`` resolves per call like every other eval entry."""
        use_pallas = resolve_use_pallas(use_pallas)
        for i in range(0, len(x), batch_size):
            yield np.asarray(_scores_stacked(
                self.cfg, self.members.cnn_params, self.members.beta,
                jnp.asarray(x[i:i + batch_size]), use_pallas=use_pallas))

    def member_scores(self, x, batch_size: int = 512,
                      use_pallas: Optional[bool] = None) -> np.ndarray:
        """(k, n, C) raw ELM scores for every member."""
        return np.concatenate(
            list(self._batched_scores(x, batch_size, use_pallas)), axis=1)

    def member_predictions(self, x, batch_size: int = 512,
                           use_pallas: Optional[bool] = None) -> np.ndarray:
        """(k, n) argmax labels for every member."""
        return np.concatenate(
            [s.argmax(-1) for s in
             self._batched_scores(x, batch_size, use_pallas)], axis=1)

    def predict(self, x, batch_size: int = 512,
                use_pallas: Optional[bool] = None) -> np.ndarray:
        """(n,) combined ensemble labels under the ``combine`` rule."""
        if self.combine == "mean":
            mean_scores = np.concatenate(
                [s.mean(axis=0) for s in
                 self._batched_scores(x, batch_size, use_pallas)], axis=0)
            return mean_scores.argmax(-1)
        preds = self.member_predictions(x, batch_size, use_pallas)
        C = self.cfg.num_classes
        n = preds.shape[1]
        votes = np.zeros((n, C), np.int64)
        np.add.at(votes, (np.tile(np.arange(n), self.k), preds.reshape(-1)), 1)
        return votes.argmax(-1)

    def evaluate(self, x, y, batch_size: int = 512,
                 use_pallas: Optional[bool] = None,
                 preds: Optional[np.ndarray] = None) -> np.ndarray:
        """(k,) per-member accuracy — equals the per-member ``evaluate``
        loop, computed in 1/k the dispatches. Pass ``preds`` (a
        ``member_predictions`` result) to reuse one scoring pass across
        several metrics."""
        if preds is None:
            preds = self.member_predictions(x, batch_size, use_pallas)
        elif preds.ndim != 2:
            raise ValueError("evaluate takes member_predictions-shaped "
                             f"(k, n) preds, got shape {preds.shape}")
        return (preds == np.asarray(y)[None, :]).mean(axis=1)

    def kappa(self, x, y, batch_size: int = 512,
              use_pallas: Optional[bool] = None,
              preds: Optional[np.ndarray] = None) -> np.ndarray:
        """(k,) per-member Cohen's kappa (vectorised confusion matrices;
        ``preds`` reuses a prior ``member_predictions`` pass)."""
        if preds is None:
            preds = self.member_predictions(x, batch_size, use_pallas)
        elif preds.ndim != 2:
            raise ValueError("kappa takes member_predictions-shaped "
                             f"(k, n) preds, got shape {preds.shape}")
        C = self.cfg.num_classes
        return np.array([kappa_from_confusion(confusion_matrix(y, p, C))
                         for p in preds])

    def accuracy(self, x, y, batch_size: int = 512,
                 use_pallas: Optional[bool] = None,
                 preds: Optional[np.ndarray] = None) -> float:
        """Combined-decision accuracy under the ``combine`` rule. Pass
        ``preds`` (a ``predict`` result) to reuse one scoring pass across
        several metrics instead of re-scoring the set per call."""
        if preds is None:
            preds = self.predict(x, batch_size, use_pallas)
        elif preds.ndim != 1:
            raise ValueError("accuracy takes predict-shaped (n,) preds, "
                             f"got shape {preds.shape}")
        return float((preds == np.asarray(y)).mean())

    def kappa_combined(self, x, y, batch_size: int = 512,
                       use_pallas: Optional[bool] = None,
                       preds: Optional[np.ndarray] = None) -> float:
        """Combined-decision Cohen's kappa under the ``combine`` rule
        (``preds`` reuses a prior ``predict`` pass, as in ``accuracy``)."""
        if preds is None:
            preds = self.predict(x, batch_size, use_pallas)
        elif preds.ndim != 1:
            raise ValueError("kappa_combined takes predict-shaped (n,) "
                             f"preds, got shape {preds.shape}")
        return kappa_from_confusion(
            confusion_matrix(y, preds, self.cfg.num_classes))

    def averaged(self) -> CNNELMModel:
        """The paper's Reduce over these members (uniform mean)."""
        return self.members.averaged()

    def bucketed_scorer(self, max_batch: int = 64, *,
                        use_pallas: Optional[bool] = None):
        """The pre-jitted SERVING entry over these members: a
        ``repro.serve.BucketedScorer`` that only ever dispatches at
        power-of-two bucket shapes, so it compiles once per bucket and
        never again — the compile-count guarantee behind
        ``repro.serve.EnsembleServer`` (continuous batching + hot
        reload). ``max_batch`` caps the ladder; ``use_pallas`` resolves
        per the kernel backend policy like every other eval entry."""
        from repro.serve.engine import BucketedScorer
        return BucketedScorer(self.cfg, self.members, max_batch=max_batch,
                              use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Single-model eval (the non-deprecated home of the old evaluate/kappa)
# ---------------------------------------------------------------------------

def evaluate_model(cfg, model: CNNELMModel, x, y, batch_size: int = 512,
                   use_pallas: Optional[bool] = None) -> float:
    """Accuracy of one model (a k=1 ensemble ride on the batched surface).
    Each call restacks the model's params into the member layout — in a hot
    scoring loop, build ``Ensemble.from_models(cfg, [model])`` once and
    reuse it instead."""
    ens = Ensemble.from_models(cfg, [model])
    return float(ens.evaluate(x, y, batch_size=batch_size,
                              use_pallas=use_pallas)[0])


def kappa_model(cfg, model: CNNELMModel, x, y, batch_size: int = 512,
                use_pallas: Optional[bool] = None) -> float:
    """Cohen's kappa of one model."""
    ens = Ensemble.from_models(cfg, [model])
    return float(ens.kappa(x, y, batch_size=batch_size,
                           use_pallas=use_pallas)[0])
