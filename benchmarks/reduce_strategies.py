"""Reduce-strategy sweep under non-IID Dirichlet partitions.

The paper's Reduce is a uniform weight average — exact for the ELM head
(E²LM stats just add) but indifferent to HOW the data landed on the
members. This benchmark skews the landing with ``partition_dirichlet``
(label proportions ~ Dir(α·1_k); α=100 ≈ IID, α=0.1 = most members see
a few classes) and sweeps every registered ``ReduceStrategy`` over the
skew ladder at k=8:

* ``uniform`` / ``shard_weighted`` — the existing weighted-average path,
  now resolved through the ``repro.core.reduce_strategies`` registry;
* ``boosted`` — AdaBoost member weights ``log((1-err)/err)`` from a
  held-out validation slice, floored + normalized, riding the SAME
  weighted-average collectives;
* ``gossip`` — decentralized ring mixing (``lax.ppermute`` neighbors
  only, ZERO global all-reduces) whose invariant-sum readout equals the
  one-psum average.

Persisted gates — the benchmark HARD-FAILS before writing anything:

* boosted ≥ uniform accuracy on the most-skewed α=0.1 split (the reason
  the strategy exists);
* the ``"uniform"`` string and a ``Uniform()`` registry instance produce
  bit-identical averaged models (the registry is a pure re-plumbing);
* the gossip→psum consensus gap shrinks monotonically in mixing rounds
  (geometric, tracked against ``gossip_mixing_lambda2``);
* the compiled mesh gossip sync carries exactly ``2·rounds``
  collective-permutes and ZERO all-reduces (``audit_executor`` +
  ``collective_stats`` on the HLO).

Run standalone: ``PYTHONPATH=src python -m benchmarks.reduce_strategies``
(``--smoke`` for the tiny CI config; or via ``benchmarks/run.py``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_result, time_call
from repro.configs.base import get_reduced_config, replace
from repro.core import reduce_strategies as rs
from repro.core.averaging import gossip_member_dim, gossip_mixing_lambda2
from repro.core.runner import (AveragingRun, MapConfig, ReduceConfig,
                               evaluate_model)
from repro.data.partition import Partition, partition_dirichlet
from repro.data.synthetic import make_extended_mnist
from repro.optim.schedules import dynamic_paper

KEY = jax.random.PRNGKey(0)
ROOT = os.path.join(os.path.dirname(__file__), "..")

ALPHAS = (100.0, 1.0, 0.1)
GOSSIP_ROUNDS_SWEEP = (1, 2, 4, 8)


def _leaves(model):
    return jax.tree.leaves((model.cnn_params, model.beta))


def _bit_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(_leaves(a), _leaves(b)))


def _label_skew(parts, num_classes: int) -> float:
    """Mean total-variation distance between each member's label
    marginal and the global marginal — the skew the α ladder dials."""
    ally = np.concatenate([p.y for p in parts])
    glob = np.bincount(ally, minlength=num_classes) / len(ally)
    tvs = []
    for p in parts:
        loc = np.bincount(p.y, minlength=num_classes) / max(len(p.y), 1)
        tvs.append(0.5 * np.abs(loc - glob).sum())
    return float(np.mean(tvs))


def _stack_members(members):
    trees = [(m.cnn_params, m.beta) for m in members]
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def run_reduce_strategies(k: int = 8, n_per_class: int = 80,
                          epochs: int = 1, batch_size: int = 32,
                          rounds: int = 1, gossip_rounds: int = 4,
                          alphas=ALPHAS, out_dir: str = None):
    """The strategy × skew sweep. Accuracy rows run on the stacked
    backend (the bit-reference); the gossip collective audit lowers the
    mesh ring program, so the process needs ``k`` devices — same re-exec
    discipline as ``benchmarks.hierarchical_reduce``."""
    if len(jax.devices()) < k:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"run_reduce_strategies needs {k} devices for the gossip "
                f"ring audit but the {jax.default_backend()} backend has "
                f"{len(jax.devices())} and simulated host devices only "
                f"exist on CPU")
        if os.environ.get("_REPRO_REDUCE_SWEEP_CHILD"):
            raise RuntimeError(
                f"reduce-sweep child still sees {len(jax.devices())} "
                f"devices (< {k}) despite the forced flag — refusing to "
                f"re-exec again")
        out_dir = out_dir or os.path.join(ROOT, "experiments")
        from repro.launch.mesh import host_device_flags
        env = dict(
            os.environ,
            _REPRO_REDUCE_SWEEP_CHILD="1",
            PYTHONPATH=os.pathsep.join(
                [os.path.join(ROOT, "src"), ROOT,
                 os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep),
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " " +
                       host_device_flags(k)).strip())
        subprocess.run(
            [sys.executable, "-m", "benchmarks.reduce_strategies",
             "--strategy-sweep", "--k", str(k),
             "--n-per-class", str(n_per_class), "--epochs", str(epochs),
             "--batch-size", str(batch_size), "--rounds", str(rounds),
             "--gossip-rounds", str(gossip_rounds),
             "--alphas", ",".join(map(str, alphas)),
             "--out-dir", out_dir],
            check=True, env=env, cwd=ROOT)
        with open(os.path.join(out_dir,
                               "BENCH_reduce_strategies.json")) as f:
            return json.load(f)

    from repro.analysis.hlo import audit_executor
    return _sweep(k, n_per_class, epochs, batch_size, rounds,
                  gossip_rounds, alphas, out_dir, audit_executor)


def _sweep(k, n_per_class, epochs, batch_size, rounds, gossip_rounds,
           alphas, out_dir, audit_executor):
    from repro.analysis.hlo import lower_stacked_programs
    from repro.core import executor
    from repro.core.averaging import broadcast_member_dim
    from repro.launch.hlo_analysis import collective_stats
    from repro.launch.mesh import make_member_mesh
    from repro.models import cnn

    cfg = get_reduced_config("cnn_elm_6c12c")
    if epochs:
        cfg = replace(cfg, elm_lambda=1.0)
    train = make_extended_mnist(n_per_class=n_per_class, seed=0)
    val = make_extended_mnist(n_per_class=max(n_per_class // 4, 4), seed=7)
    test = make_extended_mnist(n_per_class=n_per_class, seed=1)
    lr = dynamic_paper(0.05)
    map_cfg = MapConfig(epochs=epochs, lr_schedule=lr,
                        batch_size=batch_size, backend="stacked")

    def strategy_cfg(name):
        if name == "boosted":
            return ReduceConfig(rounds=rounds, strategy="boosted",
                                validation=Partition(val.x, val.y))
        if name == "gossip":
            return ReduceConfig(rounds=rounds,
                                strategy=rs.Gossip(rounds=gossip_rounds))
        return ReduceConfig(rounds=rounds, strategy=name)

    # ---- the accuracy sweep: strategy × α on fixed seeded partitions
    sweep_rows = []
    accs = {}                       # (strategy, alpha) -> acc
    partition_rows = []
    for alpha in alphas:
        parts = partition_dirichlet(train.x, train.y, k=k, alpha=alpha,
                                    seed=0, min_rows=2)
        partition_rows.append({
            "alpha": alpha,
            "rows_per_member": [len(p.x) for p in parts],
            "label_skew_tv": _label_skew(parts, cfg.num_classes),
        })
        for name in rs.registry_keys():
            res = AveragingRun(cfg, map_cfg, strategy_cfg(name)).run(
                parts, KEY)
            acc = float(evaluate_model(cfg, res.averaged, test.x, test.y))
            accs[(name, alpha)] = acc
            sweep_rows.append({"strategy": name, "alpha": alpha,
                               "acc": acc})
            emit(f"reduce_{name}_a{alpha:g}_k{k}", 0.0, f"acc={acc:.4f}")

    # ---- gate 1: boosted must not lose to uniform where skew bites
    worst = min(alphas)
    if accs[("boosted", worst)] < accs[("uniform", worst)]:
        raise AssertionError(
            f"boosted accuracy {accs[('boosted', worst)]:.4f} fell below "
            f"uniform {accs[('uniform', worst)]:.4f} on the α={worst} "
            f"split — the validation-weighted Reduce must not lose to "
            f"the uniform baseline on skewed data")

    # ---- gate 2: the registry re-plumbing is invisible — string vs
    # instance resolve to bit-identical averaged models
    parts_mid = partition_dirichlet(train.x, train.y, k=k, alpha=1.0,
                                    seed=0, min_rows=2)
    by_string = AveragingRun(
        cfg, map_cfg, ReduceConfig(rounds=rounds,
                                   strategy="uniform")).run(parts_mid, KEY)
    by_instance = AveragingRun(
        cfg, map_cfg, ReduceConfig(rounds=rounds,
                                   strategy=rs.Uniform())).run(parts_mid,
                                                               KEY)
    registry_bit_identical = _bit_equal(by_string.averaged,
                                        by_instance.averaged)
    if not registry_bit_identical:
        raise AssertionError(
            "strategy='uniform' and strategy=Uniform() produced different "
            "averaged models — the registry must be a pure re-plumbing")

    # ---- gate 3: gossip consensus converges geometrically onto the
    # one-psum average (the member-dim emulation of the mesh ring, on
    # the real post-Map members of the α=1 run)
    stacked = _stack_members(by_string.members)
    psum_avg = jax.tree.map(lambda a: jnp.mean(
        a.astype(jnp.float32), axis=0), stacked)
    gaps = []
    for T in GOSSIP_ROUNDS_SWEEP:
        iterates, _ = gossip_member_dim(stacked, None, T)
        gap = max(float(jnp.max(jnp.abs(
            it.astype(jnp.float32) - av[None]))) for it, av in zip(
                jax.tree.leaves(iterates), jax.tree.leaves(psum_avg)))
        gaps.append(gap)
    if not all(a > b for a, b in zip(gaps, gaps[1:])):
        raise AssertionError(
            f"gossip consensus gap must shrink monotonically in mixing "
            f"rounds, got {gaps} over rounds {GOSSIP_ROUNDS_SWEEP}")

    # ---- gate 4: the compiled mesh gossip sync is psum-free — exactly
    # 2·rounds neighbor permutes, zero global all-reduces
    mesh = make_member_mesh(num_pods=k)
    for rep in audit_executor(cfg, "mesh", mesh=mesh, k=k,
                              gossip_rounds=gossip_rounds):
        rep.raise_if_failed()
    ex, programs = lower_stacked_programs(cfg, "mesh", mesh=mesh, k=k,
                                          gossip_rounds=gossip_rounds)
    g_cs = collective_stats(programs["_gossip"].compile().as_text())
    s_cs = collective_stats(programs["_sync"].compile().as_text())

    # ---- wall-clock: one timed round-sync each way (structure on a
    # shared CPU, not fabric latency)
    params_k = ex._put(broadcast_member_dim(cnn.init_params(cfg, KEY),
                                            ex._k_pad))
    w = ex._weights(None)
    gossip_us = time_call(
        lambda: ex._call(executor._gossip, params_k, w,
                         rounds=gossip_rounds, publish=False),
        warmup=1, iters=3)
    psum_us = time_call(
        lambda: ex._call(executor._sync, params_k, w), warmup=1, iters=3)

    payload = {
        "k": k,
        "alphas": list(alphas),
        "epochs": epochs,
        "rounds": rounds,
        "batch_size": batch_size,
        "strategies": list(rs.registry_keys()),
        "sweep": sweep_rows,
        "partitions": partition_rows,
        "boosted_gate": {
            "alpha": worst,
            "boosted_acc": accs[("boosted", worst)],
            "uniform_acc": accs[("uniform", worst)],
        },
        "registry_bit_identical": registry_bit_identical,
        "gossip": {
            "rounds": gossip_rounds,
            "rounds_sweep": list(GOSSIP_ROUNDS_SWEEP),
            "consensus_gaps": gaps,
            "mixing_lambda2": gossip_mixing_lambda2(k),
            "ppermute_per_sync":
                g_cs.count_by_kind.get("collective-permute", 0),
            "allreduce_per_sync": g_cs.count_by_kind.get("all-reduce", 0),
            "gossip_per_chip_bytes": g_cs.per_chip_bytes,
            "psum_per_chip_bytes": s_cs.per_chip_bytes,
            "gossip_sync_us": gossip_us,
            "psum_sync_us": psum_us,
        },
        "cost_model": "one-psum sync: 1 global all-reduce over all k "
                      "pods; gossip sync: 2 collective-permutes per "
                      "mixing round (right + left ring neighbor), "
                      "2·rounds total, neighbor-scoped — zero global "
                      "collectives, consensus gap ~ lambda2^rounds",
        "backend": jax.default_backend(),
    }
    save_result("BENCH_reduce_strategies", payload, out_dir=out_dir)
    emit(f"gossip_sync_k{k}_T{gossip_rounds}", gossip_us,
         f"{payload['gossip']['ppermute_per_sync']} permutes "
         f"0 all-reduce")
    emit(f"psum_sync_k{k}", psum_us, "1 all-reduce")
    return payload


def main(smoke: bool = False, out_dir: str = None):
    if smoke:
        import tempfile
        out_dir = out_dir or tempfile.mkdtemp(
            prefix="bench_reduce_strategies_smoke_")
        print(f"# smoke JSONs -> {out_dir}", flush=True)
        return run_reduce_strategies(
            k=4, n_per_class=16, epochs=1, batch_size=16, rounds=1,
            gossip_rounds=2, alphas=(100.0, 0.1), out_dir=out_dir)
    return run_reduce_strategies(out_dir=out_dir)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (4 devices, k=4, 1 epoch)")
    ap.add_argument("--strategy-sweep", action="store_true",
                    help="run the sweep inline (the re-exec child entry — "
                         "expects the forced host device count already in "
                         "XLA_FLAGS)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--n-per-class", type=int, default=80)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--gossip-rounds", type=int, default=4)
    ap.add_argument("--alphas", default="100,1,0.1")
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args()
    if args.strategy_sweep:
        run_reduce_strategies(
            k=args.k, n_per_class=args.n_per_class, epochs=args.epochs,
            batch_size=args.batch_size, rounds=args.rounds,
            gossip_rounds=args.gossip_rounds,
            alphas=tuple(float(a) for a in args.alphas.split(",")),
            out_dir=args.out_dir)
    else:
        main(smoke=args.smoke, out_dir=args.out_dir)
