"""Round-granular run state — the checkpoint schema behind the
fault-tolerant ``AveragingRun`` (``repro.core.runner``).

One ``round-<r>.npz`` per averaging round (atomic via ``ckpt``'s
tmp-rename), holding everything the round produced:

* ``members``  — the round's pre-sync member snapshot (stacked CNN params
  + the solved β, padding already stripped on a multi-device mesh);
* ``stats``    — the final-epoch ``ELMStats`` of every member (the exact
  sufficient statistics β was solved from, so a checkpoint can re-solve
  or E²LM-merge without replaying data);
* ``averaged`` — the round's (weighted) averaged model through the
  executor's native Reduce;
* ``resume``   — on non-final rounds, the post-sync params every member
  was reset to. THE resume point: broadcasting this tree reproduces the
  uninterrupted run's device state bit-for-bit, because the inter-round
  sync itself broadcasts one identical row to every member slot;
* ``record``   — on the final round of a run that took SGD steps, its
  step record (``cnn_elm.StepRecord``), so that a finished run rebuilt
  from its checkpoint hands it back too.

Metadata carries the rng/round cursor (``round``, ``epochs_done`` = batch
permutations consumed per member stream — the runner fast-forwards each
``default_rng(seed + i)`` by exactly that many draws) plus the run
fingerprint (backend, seed, epochs/rounds/batch size, k, partition row
counts) that ``AveragingRun.resume`` validates before continuing.

Sequential runs additionally checkpoint per MEMBER (that backend's unit
of work): ``member-<i>.npz`` with the member's params, β and stats, so a
crash while training member j resumes by training only members j..k-1.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from repro.checkpoint.ckpt import (latest_step, latest_valid_step, list_steps,
                                   restore_checkpoint, save_checkpoint)
from repro.core import elastic, elm
from repro.core.cnn_elm import CNNELMModel, StackedMembers, StepRecord

ROUND = "round"
MEMBER = "member"
ELASTIC = "eround"


def run_fingerprint(backend: str, partitions, *, seed: int, epochs: int,
                    rounds: int, batch_size: int) -> dict:
    """The identity of a run, embedded in every checkpoint so resume can
    refuse a mismatched continuation instead of silently diverging. THE
    single definition of the fingerprint fields — the executors build the
    save-side dict and ``AveragingRun.resume`` the expected dict through
    this one function, so the two can never drift apart."""
    return {
        "backend": backend,
        "seed": seed,
        "epochs": epochs,
        "rounds": rounds,
        "batch_size": batch_size,
        "k": len(partitions),
        "sizes": [int(len(p.x)) for p in partitions],
    }


def check_fingerprint(meta: dict, expected: dict):
    """Raise with every differing field named (not just the first)."""
    bad = {k: (meta.get(k), v) for k, v in expected.items()
           if meta.get(k) != v}
    if bad:
        raise ValueError(
            "checkpoint does not match this run — refusing to resume: " +
            "; ".join(f"{k}: saved {s!r} vs run {e!r}"
                      for k, (s, e) in bad.items()))


def _stats_tree(stats: elm.ELMStats) -> dict:
    return {"u": stats.u, "v": stats.v, "n": stats.n}


def _tree_stats(tree: dict) -> elm.ELMStats:
    return elm.ELMStats(tree["u"], tree["v"], tree["n"])


@dataclass
class RoundState:
    """One restored ``round-<r>`` checkpoint."""
    round: int
    members: StackedMembers
    stats: elm.ELMStats
    averaged: CNNELMModel
    resume_params: Optional[dict]     # post-sync CNN params; None on final
    meta: dict
    step_record: Optional[StepRecord] = None    # final round, after SGD

    @property
    def final(self) -> bool:
        return bool(self.meta.get("final"))


def save_round(ckpt_dir: str, round_idx: int, *, members: StackedMembers,
               stats: elm.ELMStats, averaged: CNNELMModel,
               resume_params=None, step_record: Optional[StepRecord] = None,
               meta: dict) -> str:
    tree = {
        "members": {"cnn": members.cnn_params, "beta": members.beta},
        "stats": _stats_tree(stats),
        "averaged": {"cnn": averaged.cnn_params, "beta": averaged.beta},
    }
    if resume_params is not None:
        tree["resume"] = resume_params
    if step_record is not None:
        tree["record"] = {"params": step_record.params,
                          "mask": step_record.mask}
    return save_checkpoint(ckpt_dir, ROUND, round_idx, tree, meta)


def restore_round(ckpt_dir: str, round_idx: Optional[int] = None
                  ) -> RoundState:
    if round_idx is None:
        round_idx = latest_step(ckpt_dir, ROUND)
        if round_idx is None:
            raise FileNotFoundError(f"no '{ROUND}' checkpoint in {ckpt_dir}")
    tree, meta = restore_checkpoint(ckpt_dir, ROUND, round_idx)
    return RoundState(
        round=round_idx,
        members=StackedMembers(tree["members"]["cnn"],
                               tree["members"]["beta"]),
        stats=_tree_stats(tree["stats"]),
        averaged=CNNELMModel(tree["averaged"]["cnn"],
                             tree["averaged"]["beta"]),
        resume_params=tree.get("resume"),
        meta=meta["metadata"],
        step_record=(StepRecord(tree["record"]["params"],
                                tree["record"]["mask"])
                     if "record" in tree else None))


def latest_round(ckpt_dir: str) -> Optional[int]:
    return latest_step(ckpt_dir, ROUND)


def latest_ready_round(ckpt_dir: str) -> Optional[int]:
    """Newest FULLY-WRITTEN round — ``ckpt.latest_valid_step`` over the
    round files. The serving hot-reload watcher polls this while the
    training run is still writing: stray ``*.tmp`` files and torn
    ``round-<r>.npz`` are skipped (and retried next poll) instead of
    crashing the endpoint."""
    return latest_valid_step(ckpt_dir, ROUND)


# ---------------------------------------------------------------------------
# Elastic rounds — checkpointing a run under membership churn
# ---------------------------------------------------------------------------

@dataclass
class ElasticRoundState:
    """One restored ``eround-<r>`` checkpoint: the full ``ElasticGroup``
    (living members' params/steps/stats, retired weighted contributions)
    plus the membership bookkeeping the elastic runner needs to continue
    bit-identically — who is living (in join order), each member's id
    (which pins its ``seed + id`` rng stream), the round it joined at
    (which pins its ``start_epochs`` fast-forward), the next joiner id
    and the boundary average every member was reset to (``cur_init``)."""
    round: int
    group: elastic.ElasticGroup
    cur_init: object                     # post-boundary shared CNN init
    living: List[str]                    # join order
    joined_round: Dict[str, int]
    member_id: Dict[str, int]
    next_id: int
    meta: dict

    @property
    def final(self) -> bool:
        return bool(self.meta.get("final"))


def save_elastic_round(ckpt_dir: str, round_idx: int, *,
                       group: elastic.ElasticGroup, cur_init,
                       joined_round: Dict[str, int],
                       member_id: Dict[str, int], next_id: int,
                       meta: dict) -> str:
    """Snapshot the POST-boundary state of elastic round ``round_idx``:
    leavers already retired, the sync applied, joiners admitted. Member
    names become tree keys (they are ``m<id>``, so they never collide
    with the '/'-path or '#<i>'-tuple encodings of ``ckpt``)."""
    members_tree = {}
    for name, mm in group.members.items():
        sub = {"params": mm.params,
               "steps": np.asarray(mm.steps, np.float64)}
        if mm.stats is not None:
            sub["stats"] = _stats_tree(mm.stats)
        members_tree[name] = sub
    tree = {
        "members": members_tree,
        "retired_params": [(p, np.asarray(w, np.float64))
                           for p, w in group.retired_params],
        "retired_stats": [_stats_tree(s) for s in group.retired_stats],
        "cur_init": cur_init,
    }
    living = sorted(group.members, key=member_id.get)     # join order
    meta = {**meta,
            "living": living,
            "joined_round": {n: int(joined_round[n]) for n in living},
            "member_id": {n: int(member_id[n]) for n in living},
            "next_id": int(next_id)}
    return save_checkpoint(ckpt_dir, ELASTIC, round_idx, tree, meta)


def restore_elastic_round(ckpt_dir: str, round_idx: Optional[int] = None
                          ) -> ElasticRoundState:
    """Rebuild the ``ElasticGroup`` EXACTLY: members re-inserted in join
    order (``reduce_params`` sums in dict order, so insertion order is
    part of the bit-identity contract), retired entries in append order
    (``ckpt`` restores lists as tuples — normalised back to lists)."""
    if round_idx is None:
        round_idx = latest_step(ckpt_dir, ELASTIC)
        if round_idx is None:
            raise FileNotFoundError(
                f"no '{ELASTIC}' checkpoint in {ckpt_dir}")
    tree, meta = restore_checkpoint(ckpt_dir, ELASTIC, round_idx)
    md = meta["metadata"]
    member_id = {n: int(i) for n, i in md["member_id"].items()}
    group = elastic.ElasticGroup()
    for name in sorted(tree["members"], key=member_id.get):
        sub = tree["members"][name]
        group.members[name] = elastic.Member(
            params=sub["params"], steps=float(sub["steps"]),
            stats=_tree_stats(sub["stats"]) if "stats" in sub else None)
    # empty lists serialise to no keys at all — .get them back as empty
    group.retired_params = [(p, float(w))
                            for p, w in tree.get("retired_params", ())]
    group.retired_stats = [_tree_stats(s)
                           for s in tree.get("retired_stats", ())]
    return ElasticRoundState(
        round=round_idx, group=group, cur_init=tree["cur_init"],
        living=list(md["living"]),
        joined_round={n: int(r) for n, r in md["joined_round"].items()},
        member_id=member_id, next_id=int(md["next_id"]), meta=md)


def latest_elastic_round(ckpt_dir: str) -> Optional[int]:
    return latest_step(ckpt_dir, ELASTIC)


def latest_ready_elastic_round(ckpt_dir: str) -> Optional[int]:
    """Newest FULLY-WRITTEN elastic round (torn files skipped — the same
    validity probe as ``latest_ready_round``)."""
    return latest_valid_step(ckpt_dir, ELASTIC)


def save_member(ckpt_dir: str, i: int, model: CNNELMModel,
                stats: elm.ELMStats, meta: dict) -> str:
    tree = {"cnn": model.cnn_params, "beta": model.beta,
            "stats": _stats_tree(stats)}
    return save_checkpoint(ckpt_dir, MEMBER, i, tree, meta)


def restore_member(ckpt_dir: str, i: int):
    tree, meta = restore_checkpoint(ckpt_dir, MEMBER, i)
    return (CNNELMModel(tree["cnn"], tree["beta"]),
            _tree_stats(tree["stats"]), meta["metadata"])


def completed_members(ckpt_dir: str):
    """Member indices with a durable checkpoint (ascending)."""
    return list_steps(ckpt_dir, MEMBER)


def stack_stats(per_member) -> elm.ELMStats:
    """k host-level ``ELMStats`` -> one member-stacked ``ELMStats``."""
    return elm.ELMStats(
        np.stack([np.asarray(s.u) for s in per_member]),
        np.stack([np.asarray(s.v) for s in per_member]),
        np.stack([np.asarray(s.n) for s in per_member]))
