"""Fault-tolerance wall-clock: what surviving a preemptible cluster costs.

Two sweeps, one JSON (``experiments/BENCH_elastic_resume.json``):

* ``run_crash_resume`` — the checkpoint/resume round-trip on BOTH
  fault-tolerant backends. Uninterrupted run vs checkpointed run
  (``ckpt_overhead`` = the per-round atomic snapshot price: the forced
  intermediate β solves + averaged builds + the .npz writes) vs the full
  preemption path (``repro.core.faults`` crashes the run right after a
  round/member checkpoint is durable, then ``AveragingRun.resume``
  finishes it). The resumed members and averaged model must be
  BIT-IDENTICAL to the uninterrupted run — asserted here before anything
  is persisted, the same gate style as the mesh benchmark's
  one-collective contract.
* ``run_elastic`` — membership churn under the rounds contract: a static
  k-member baseline vs a run where a straggler (oversized shard, the
  work proxy on a CPU-simulated cluster) is dropped at the first boundary
  while a fresh member joins from the boundary average. Reports
  wall-clock, the membership timeline, and the averaged-model accuracy of
  both regimes on the training pool (elastic keeps the retired
  contribution, so accuracy should stay in the same band — recorded, not
  asserted).

Run standalone: ``PYTHONPATH=src python -m benchmarks.elastic_resume``
(``--smoke`` for the tiny CI config; or via ``benchmarks/run.py``).
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

import jax

from benchmarks.common import emit, save_result, time_call
from repro.configs.base import get_reduced_config, replace
from repro.core import faults
from repro.core.runner import (AveragingRun, ElasticEvent, ElasticSchedule,
                               MapConfig, ReduceConfig, evaluate_model)
from repro.data.partition import partition_iid, partition_unequal
from repro.data.synthetic import make_extended_mnist
from repro.optim.schedules import dynamic_paper

KEY = jax.random.PRNGKey(0)


def _workload(n_per_class: int):
    cfg = replace(get_reduced_config("cnn_elm_6c12c"), elm_lambda=1.0)
    ds = make_extended_mnist(n_per_class=n_per_class, seed=0)
    return cfg, ds, dynamic_paper(0.05)


def _assert_bit_identical(a, b, what: str):
    ok = True
    for ma, mb in zip([a.averaged] + a.members, [b.averaged] + b.members):
        ok &= np.array_equal(np.asarray(ma.beta), np.asarray(mb.beta))
        for la, lb in zip(jax.tree.leaves(ma.cnn_params),
                          jax.tree.leaves(mb.cnn_params)):
            ok &= np.array_equal(np.asarray(la), np.asarray(lb))
    if not ok:
        raise AssertionError(
            f"{what}: resumed run diverged from the uninterrupted run — "
            f"the checkpoint/resume contract is bit-identity")
    return True


def run_crash_resume(k: int = 4, n_per_class: int = 40, epochs: int = 4,
                     rounds: int = 4, batch_size: int = 32, iters: int = 2):
    """Returns the crash/resume payload for both backends (no file I/O of
    its own — ``main`` persists the combined JSON)."""
    cfg, ds, lr = _workload(n_per_class)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    out = {}

    setups = {
        "stacked": dict(
            run=lambda: AveragingRun(
                cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                               batch_size=batch_size),
                ReduceConfig(rounds=rounds)),
            unit="round", index=rounds // 2 - 1 if rounds > 1 else 0),
        "sequential": dict(
            run=lambda: AveragingRun(
                cfg, MapConfig(epochs=max(1, epochs // rounds),
                               lr_schedule=lr, batch_size=batch_size,
                               backend="sequential")),
            unit="member", index=k // 2),
    }
    for name, s in setups.items():
        plain_us = time_call(lambda: s["run"]().run(parts, KEY).averaged,
                             warmup=1, iters=iters)
        ref = s["run"]().run(parts, KEY)

        def ckpt_once():
            with tempfile.TemporaryDirectory() as d:
                from repro.core.runner import CheckpointConfig
                return s["run"]().run(parts, KEY,
                                      checkpoint=CheckpointConfig(dir=d))
        ckpt_us = time_call(lambda: ckpt_once().averaged,
                            warmup=1, iters=iters)

        d = tempfile.mkdtemp(prefix=f"bench_resume_{name}_")
        try:
            t0 = time.perf_counter()
            crashed = faults.run_to_crash(s["run"](), parts, KEY, d,
                                          unit=s["unit"], index=s["index"])
            crash_us = (time.perf_counter() - t0) * 1e6
            t0 = time.perf_counter()
            res = s["run"]().resume(parts, KEY, d)
            resume_us = (time.perf_counter() - t0) * 1e6
            files = [(f, os.path.getsize(os.path.join(d, f)))
                     for f in os.listdir(d) if f.endswith(".npz")]
        finally:
            shutil.rmtree(d, ignore_errors=True)
        out[name] = {
            "uninterrupted_us": plain_us,
            "checkpointed_us": ckpt_us,
            "ckpt_overhead": ckpt_us / plain_us,
            "to_crash_us": crash_us,
            "resume_us": resume_us,
            "crashed": crashed,
            "crash_unit": s["unit"],
            "crash_index": s["index"],
            "bit_identical": _assert_bit_identical(
                ref, res, f"crash/resume [{name}]"),
            "ckpt_files": len(files),
            "ckpt_bytes": sum(size for _, size in files),
        }
        emit(f"resume_{name}_k{k}", resume_us,
             f"crash@{s['unit']}{s['index']} ckpt_overhead="
             f"{out[name]['ckpt_overhead']:.2f}x bit_identical=True")
    return out


def run_elastic(k: int = 4, n_per_class: int = 40, epochs: int = 4,
                rounds: int = 4, batch_size: int = 32, iters: int = 2):
    """Static membership vs straggler-drop + boundary join."""
    cfg, ds, lr = _workload(n_per_class)
    # one deliberately oversized shard = the straggler (CPU-simulated
    # members share a clock, so data volume is the work/straggle proxy)
    base = len(ds.x) // (2 * k)
    sizes = [base] * (k - 1) + [min(3 * base, len(ds.x) - base * (k - 1))]
    parts = partition_unequal(ds.x, ds.y, sizes, seed=0)
    # 1.4: low enough that the smoke config's 3x shard still trips it, so
    # the leave path is exercised even on the tiny CI workload
    drop = faults.straggler_drop_schedule(parts, factor=1.4, after_round=0)
    join_part = partition_iid(ds.x, ds.y, k=k, seed=7)[0]
    sched = ElasticSchedule(drop.events + (
        ElasticEvent(after_round=rounds // 2 - 1 if rounds > 2 else 0,
                     join=(join_part,)),))

    static_run = AveragingRun(
        cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                       batch_size=batch_size),
        ReduceConfig(strategy="shard_weighted", rounds=rounds))
    elastic_run = AveragingRun(
        cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                       batch_size=batch_size),
        ReduceConfig(strategy="shard_weighted", rounds=rounds,
                     elastic=sched))

    last = {}

    def go(tag, run):
        def fn():
            last[tag] = run.run(parts, KEY)
            return last[tag].averaged.beta
        return fn

    static_us = time_call(go("static", static_run), warmup=1, iters=iters)
    elastic_us = time_call(go("elastic", elastic_run), warmup=1, iters=iters)
    res = last["elastic"]
    payload = {
        "static_us": static_us,
        "elastic_us": elastic_us,
        "churn_overhead": elastic_us / static_us,
        "shard_sizes": sizes,
        "straggler_dropped": [n for r in res.rounds for n in r.left],
        "joined": [n for r in res.rounds for n in r.joined],
        "members_per_round": [len(r.members) for r in res.rounds],
        "survivors": sorted(res.members),
        "retired_contributions": len(res.group.retired_params),
        "static_acc": evaluate_model(cfg, last["static"].averaged,
                                     ds.x, ds.y),
        "elastic_acc": evaluate_model(cfg, res.averaged, ds.x, ds.y),
    }
    emit(f"elastic_static_k{k}_r{rounds}", static_us,
         f"acc={payload['static_acc']:.3f}")
    emit(f"elastic_churn_k{k}_r{rounds}", elastic_us,
         f"drop={payload['straggler_dropped']} join={payload['joined']} "
         f"acc={payload['elastic_acc']:.3f}")
    return payload


def main(smoke: bool = False, out_dir: str = None):
    kw = dict(k=4, n_per_class=40, epochs=4, rounds=4, batch_size=32,
              iters=2)
    if smoke:
        kw = dict(k=2, n_per_class=8, epochs=2, rounds=2, batch_size=16,
                  iters=1)
        out_dir = out_dir or tempfile.mkdtemp(prefix="bench_elastic_smoke_")
        print(f"# smoke JSONs -> {out_dir}", flush=True)
    payload = {
        "crash_resume": run_crash_resume(**kw),
        "elastic": run_elastic(**kw),
        **{k_: v for k_, v in kw.items() if k_ != "iters"},
        "backend": jax.default_backend(),
    }
    save_result("BENCH_elastic_resume", payload, out_dir=out_dir)
    return payload


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (k=2, 2 epochs/rounds, 1 iter)")
    ap.add_argument("--out-dir", default=None,
                    help="where the JSON lands (default: experiments/, or "
                         "a throwaway dir under --smoke)")
    args = ap.parse_args()
    main(smoke=args.smoke, out_dir=args.out_dir)
