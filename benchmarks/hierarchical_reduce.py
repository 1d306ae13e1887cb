"""Hierarchical two-level Reduce vs the flat one-psum baseline.

The flat 1-D ``('pod',)`` member mesh lowers every Reduce/round-sync to
exactly ONE global all-reduce whose participant count — and therefore
whose latency on a real fabric — grows with the whole fleet. The 2-D
``('host', 'pod')`` mesh (``make_member_mesh(hosts=...)``) stages the
same weighted mean as an intra-host psum followed by an inter-host psum
(``averaging.hierarchical_psum_weighted_mean_members``): exactly TWO
all-reduces per sync regardless of fleet size, each scoped to one level
of the physical hierarchy.

This benchmark sweeps simulated host topologies and member counts
k=8–64 under ``--xla_force_host_platform_device_count`` (re-exec-ing
itself like ``benchmarks.map_phase.run_mesh`` when the process has too
few devices) and persists, per topology:

* the per-sync/per-reduce collective COUNTS read off the compiled HLO
  (the two-collective contract, also enforced by
  ``repro.analysis.hlo.audit_executor``);
* the per-chip collective BYTES for every k in the sweep — the cost
  model ``docs/perf.md`` §Mesh scaling quotes;
* wall-clock for one end-to-end rounds run vs the flat baseline
  (simulated pods share one CPU: structure, not compute scaling);
* the flat-vs-hierarchical parity gate: members bit-equal (the Map
  phase is topology-blind) and the averaged model within f32
  summation-order tolerance — the benchmark HARD-FAILS before
  persisting anything if the gate or the collective audit fails.

Run standalone: ``PYTHONPATH=src python -m benchmarks.hierarchical_reduce``
(``--smoke`` for the tiny CI config; or via ``benchmarks/run.py``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

import jax

from benchmarks.common import emit, save_result, time_call
from repro.configs.base import get_reduced_config, replace
from repro.core.runner import (AveragingRun, MapConfig, ReduceConfig,
                               evaluate_model)
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

KEY = jax.random.PRNGKey(0)
ROOT = os.path.join(os.path.dirname(__file__), "..")

# the flat-vs-hierarchical averaged-model tolerance: the two-stage psum
# re-orders the f32 partial sums, so agreement is summation-order
# tolerance (measured ~1e-7 relative), NOT bit-equality — the members
# themselves stay bit-equal because the Map phase never sees the
# topology
PARITY_RTOL, PARITY_ATOL = 1e-5, 1e-6

# multi-round runs are gated on accuracy, not parameters: the ~1-ulp
# sync difference feeds back into the next round's SGD and amplifies,
# but both fleets must still land on models of the same quality
ACC_TOL = 0.02


def _leaves(model):
    return jax.tree.leaves((model.cnn_params, model.beta))


def _members_bit_equal(a, b) -> bool:
    la = jax.tree.leaves([(m.cnn_params, m.beta) for m in a])
    lb = jax.tree.leaves([(m.cnn_params, m.beta) for m in b])
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def run_hierarchical(k: int = 8, n_per_class: int = 80, epochs: int = 2,
                     batch_size: int = 32, rounds: int = 2,
                     topologies=((1, 8), (2, 4), (4, 2)),
                     k_sweep=(8, 16, 32, 64), iters: int = 2,
                     out_dir: str = None):
    """The host-topology sweep. ``topologies`` are ``(hosts, pods)``
    pairs (hosts=1 → the flat 1-D mesh, the baseline and bit-reference);
    every pair must multiply to the same device count. ``k_sweep`` are
    the member counts the per-sync byte model is read at; ``k`` is the
    member count of the timed end-to-end runs and the parity gate."""
    shapes = {h * p for h, p in topologies}
    if len(shapes) != 1:
        raise ValueError(f"every (hosts, pods) pair must cover the same "
                         f"device count, got {sorted(shapes)}")
    if not any(h == 1 for h, _ in topologies):
        raise ValueError("topologies must include a flat hosts=1 baseline")
    # the flat baseline runs first so every hierarchical row can compare
    # against it as it completes
    topologies = tuple(sorted(topologies, key=lambda t: t[0] != 1))
    need = shapes.pop()
    if len(jax.devices()) < need:
        # same re-exec discipline as benchmarks.map_phase.run_mesh: the
        # forced-host-device flag is CPU-only and locks at first jax
        # init, and an already-forked child must never fork again
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"run_hierarchical needs {need} devices but the "
                f"{jax.default_backend()} backend has {len(jax.devices())} "
                f"and simulated host devices only exist on CPU")
        if os.environ.get("_REPRO_HIER_SWEEP_CHILD"):
            raise RuntimeError(
                f"hierarchical-sweep child still sees "
                f"{len(jax.devices())} devices (< {need}) despite the "
                f"forced flag — refusing to re-exec again")
        out_dir = out_dir or os.path.join(ROOT, "experiments")
        from repro.launch.mesh import host_device_flags
        env = dict(
            os.environ,
            _REPRO_HIER_SWEEP_CHILD="1",
            PYTHONPATH=os.pathsep.join(
                [os.path.join(ROOT, "src"), ROOT,
                 os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep),
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " " +
                       host_device_flags(need)).strip())
        subprocess.run(
            [sys.executable, "-m", "benchmarks.hierarchical_reduce",
             "--hier-sweep", "--k", str(k),
             "--n-per-class", str(n_per_class), "--epochs", str(epochs),
             "--batch-size", str(batch_size), "--rounds", str(rounds),
             "--topologies", ";".join(f"{h}x{p}" for h, p in topologies),
             "--k-sweep", ",".join(map(str, k_sweep)),
             "--iters", str(iters), "--out-dir", out_dir],
            check=True, env=env, cwd=ROOT)
        with open(os.path.join(out_dir,
                               "BENCH_hierarchical_reduce.json")) as f:
            return json.load(f)

    from repro.analysis.hlo import audit_executor, lower_stacked_programs
    from repro.launch.hlo_analysis import collective_stats
    from repro.launch.mesh import make_member_mesh

    cfg = get_reduced_config("cnn_elm_6c12c")
    if epochs:
        cfg = replace(cfg, elm_lambda=1.0)
    ds = make_extended_mnist(n_per_class=n_per_class, seed=0)
    lr = dynamic_paper(0.05)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    reduce_cfg = ReduceConfig(rounds=rounds if epochs else 1)
    F = cnn.feature_dim(cfg)

    def meshed(hosts, pods):
        return (make_member_mesh(num_pods=pods) if hosts == 1
                else make_member_mesh(hosts=hosts, pods=pods))

    def sync_reduce_stats(mesh, kk):
        """(sync CollectiveStats, reduce CollectiveStats, k_pad) at
        member count kk on ``mesh`` — read off the compiled HLO."""
        ex, programs = lower_stacked_programs(cfg, "mesh", mesh=mesh, k=kk)
        return tuple(collective_stats(programs[name].compile().as_text())
                     for name in ("_sync", "_reduce")) + (ex._k_pad,)

    # ---- the gate: parity + collective audit BEFORE anything persists.
    # Parity is gated on a rounds=1 run: with a SINGLE terminal Reduce
    # the Map phase never sees the topology (members bit-equal) and the
    # averaged models differ only by f32 summation order (tight
    # tolerance). With rounds>1 the ~1-ulp sync difference feeds back
    # into the next round's training and amplifies chaotically, so the
    # timed multi-round runs are gated on ACCURACY instead (below).
    parity_results = {}
    for hosts, pods in topologies:
        mesh = meshed(hosts, pods)
        for rep in audit_executor(cfg, "mesh", mesh=mesh, k=k):
            rep.raise_if_failed()
        parity_results[(hosts, pods)] = AveragingRun(
            cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                           batch_size=batch_size, backend="mesh",
                           mesh=mesh), ReduceConfig(rounds=1)).run(
                               parts, KEY)
    flat_key = next(t for t in topologies if t[0] == 1)
    flat_res = parity_results[flat_key]
    max_diff = 0.0
    members_ok = True
    for t, res in parity_results.items():
        if t == flat_key:
            continue
        members_ok &= _members_bit_equal(flat_res.members, res.members)
        for a, b in zip(_leaves(flat_res.averaged), _leaves(res.averaged)):
            a64 = np.asarray(a).astype(np.float64)
            b64 = np.asarray(b).astype(np.float64)
            max_diff = max(max_diff, float(np.abs(a64 - b64).max()))
            np.testing.assert_allclose(b64, a64, rtol=PARITY_RTOL,
                                       atol=PARITY_ATOL)
    if not members_ok:
        raise AssertionError(
            "hierarchical topology changed a MEMBER model — the Map "
            "phase must be topology-blind")

    # ---- timing + the per-k byte model, per topology
    topo_rows = []
    flat_us = flat_acc = None
    acc_max_abs_diff = 0.0
    for hosts, pods in topologies:
        mesh = meshed(hosts, pods)
        runner = AveragingRun(
            cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                           batch_size=batch_size, backend="mesh",
                           mesh=mesh), reduce_cfg)
        us = time_call(lambda: runner.run(parts, KEY).averaged.beta,
                       warmup=1, iters=iters)
        acc = evaluate_model(cfg, runner.run(parts, KEY).averaged,
                             ds.x, ds.y)
        if hosts == 1:
            flat_us, flat_acc = us, acc
        else:
            acc_max_abs_diff = max(acc_max_abs_diff,
                                   abs(acc - flat_acc))
        sync_cs, red_cs, _ = sync_reduce_stats(mesh, k)
        per_k = []
        for kk in k_sweep:
            s_cs, r_cs, k_pad = sync_reduce_stats(mesh, kk)
            per_k.append({
                "k": kk, "k_pad": k_pad,
                "sync_per_chip_bytes": s_cs.per_chip_bytes,
                "reduce_per_chip_bytes": r_cs.per_chip_bytes,
            })
        topo_rows.append({
            "hosts": hosts, "pods": pods,
            "axes": "host,pod" if hosts > 1 else "pod",
            "allreduce_per_sync":
                sync_cs.count_by_kind.get("all-reduce", 0),
            "allreduce_per_reduce":
                red_cs.count_by_kind.get("all-reduce", 0),
            "run_us": us,
            "acc": float(acc),
            "per_k": per_k,
        })
    if acc_max_abs_diff > ACC_TOL:
        raise AssertionError(
            f"hierarchical multi-round accuracy drifted "
            f"{acc_max_abs_diff:.4f} from the flat baseline "
            f"(tolerance {ACC_TOL})")
    for row in topo_rows:
        row["speedup_vs_flat"] = flat_us / row["run_us"]

    payload = {
        "k": k,
        "k_sweep": list(k_sweep),
        "devices": need,
        "epochs": epochs,
        "rounds": rounds if epochs else 1,
        "batch_size": batch_size,
        "feature_dim": F,
        "topologies": topo_rows,
        "parity": {
            "max_abs_diff": max_diff,
            "rtol": PARITY_RTOL,
            "atol": PARITY_ATOL,
            "members_bit_equal": bool(members_ok),
            "acc_max_abs_diff": float(acc_max_abs_diff),
            "acc_tol": ACC_TOL,
        },
        "cost_model": "flat ('pod',): 1 all-reduce over all hosts*pods "
                      "devices per sync; hierarchical ('host','pod'): "
                      "2 all-reduces per sync — one over the pods of "
                      "each host, one over the hosts — so the "
                      "per-collective participant count stops scaling "
                      "with the global fleet",
        "note": "simulated host devices share one physical CPU — counts "
                "and bytes are exact, wall-clock measures dispatch/"
                "collective structure, not fabric latency",
        "backend": jax.default_backend(),
    }
    save_result("BENCH_hierarchical_reduce", payload, out_dir=out_dir)
    for row in topo_rows:
        emit(f"hier_reduce_{row['hosts']}x{row['pods']}_k{k}",
             row["run_us"],
             f"{row['allreduce_per_sync']} ar/sync "
             f"{row['speedup_vs_flat']:.2f}x vs flat")
    return payload


def main(smoke: bool = False, out_dir: str = None):
    if smoke:
        import tempfile
        out_dir = out_dir or tempfile.mkdtemp(
            prefix="bench_hier_reduce_smoke_")
        print(f"# smoke JSONs -> {out_dir}", flush=True)
        return run_hierarchical(
            k=3, n_per_class=8, epochs=1, batch_size=16, rounds=1,
            topologies=((1, 4), (2, 2)), k_sweep=(3, 8), iters=1,
            out_dir=out_dir)
    return run_hierarchical(out_dir=out_dir)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (4 devices, k=3, 1 epoch)")
    ap.add_argument("--hier-sweep", action="store_true",
                    help="run the sweep inline (the re-exec child entry — "
                         "expects the forced host device count already in "
                         "XLA_FLAGS)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--n-per-class", type=int, default=80)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--topologies", default="1x8;2x4;4x2",
                    help="semicolon-separated hostsxpods pairs")
    ap.add_argument("--k-sweep", default="8,16,32,64")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args()
    if args.hier_sweep:
        run_hierarchical(
            k=args.k, n_per_class=args.n_per_class, epochs=args.epochs,
            batch_size=args.batch_size, rounds=args.rounds,
            topologies=tuple(tuple(int(v) for v in t.split("x"))
                             for t in args.topologies.split(";")),
            k_sweep=tuple(int(v) for v in args.k_sweep.split(",")),
            iters=args.iters, out_dir=args.out_dir)
    else:
        main(smoke=args.smoke, out_dir=args.out_dir)
