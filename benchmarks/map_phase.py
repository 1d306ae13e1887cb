"""Map-phase wall-clock: sequential ``train_member`` loop vs the stacked
vmap + lax.scan fast path (one device dispatch per epoch chunk).

The sequential reference dispatches 3 jit calls per batch per member from
the host (feature/stats, β solve, SGD step); the stacked path trains all k
members in one donated scan. The ratio is the host-dispatch overhead the
paper's "embarrassingly parallel Map" leaves on the table when driven batch
by batch from Python. Both sides now run through the composable runner
(``runner.AveragingRun``) — the benchmark times the API users actually
call, and reads the dispatch counts straight from ``RunResult`` telemetry.

Four configs, four JSONs under ``experiments/``:

* ``run``         → ``BENCH_map_phase.json`` — the equal-shard k=4 case
  (sequential vs stacked backend; the PR-1 headline number, kept as the
  regression floor).
* ``run_unequal`` → ``BENCH_map_phase_unequal.json`` — shards in a
  1:2:…:k size ratio; sequential + shard-weighted Reduce vs the
  padded/masked stacked path (the regime that used to hard-fail).
* ``run_chunked`` → ``BENCH_map_phase_chunked.json`` — the monolithic
  one-scan epoch vs the double-buffered chunked scan, plus the device-bytes
  bound the chunking buys and a bit-identical β check.
* ``run_rounds``  → ``BENCH_map_phase_rounds.json`` — single final average
  (``rounds=1``) vs multi-round parallel-SGD averaging (``rounds=r``): the
  wall-clock price of communicating every epochs/r epochs, with per-round
  dispatch telemetry.
* ``run_mesh``    → ``BENCH_map_phase_mesh.json`` — the member-mesh
  scaling sweep: k members shard_map-ed over {1, 2, 4, 8} simulated pods
  (the process re-execs itself under
  ``--xla_force_host_platform_device_count`` when it sees too few
  devices), with the one-collective-per-round cost model read straight
  off the compiled HLO (all-reduce count + per-chip bytes for the sync
  and the Reduce). Simulated pods share the physical CPU, so the sweep
  measures dispatch/collective STRUCTURE, not compute scaling.

Run standalone: ``PYTHONPATH=src python -m benchmarks.map_phase``
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
from repro.configs.base import get_reduced_config
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import partition_iid, partition_unequal
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

KEY = jax.random.PRNGKey(0)
ROOT = os.path.join(os.path.dirname(__file__), "..")


def _workload(n_per_class: int):
    cfg = get_reduced_config("cnn_elm_6c12c")
    ds = make_extended_mnist(n_per_class=n_per_class, seed=0)
    return cfg, ds, dynamic_paper(0.05)


def run(k: int = 4, n_per_class: int = 40, epochs: int = 2,
        batch_size: int = 32, iters: int = 3, out_dir: str = None):
    """Time both Map-phase backends on one equal-shard workload and persist
    the comparison. Returns the payload dict."""
    cfg, ds, lr = _workload(n_per_class)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    last = {}

    def backend_fn(backend):
        runner = AveragingRun(cfg, MapConfig(
            epochs=epochs, lr_schedule=lr, batch_size=batch_size,
            backend=backend))

        def go():
            res = runner.run(parts, KEY)
            last[backend] = res.dispatches
            return res.averaged.beta
        return go

    seq_us = time_call(backend_fn("sequential"), warmup=1, iters=iters)
    st_us = time_call(backend_fn("stacked"), warmup=1, iters=iters)

    num_batches = (len(parts[0].x) // batch_size)
    payload = {
        "sequential_us": seq_us,
        "stacked_us": st_us,
        "speedup": seq_us / st_us,
        "sequential_dispatches": last["sequential"],
        "stacked_dispatches": last["stacked"],
        "k": k,
        "epochs": epochs,
        "num_batches": num_batches,
        "batch_size": batch_size,
        "feature_dim": cnn.feature_dim(cfg),
        "backend": jax.default_backend(),
    }
    save_result("BENCH_map_phase", payload, out_dir=out_dir)
    emit(f"map_phase_sequential_k{k}_e{epochs}", seq_us,
         f"host loop {last['sequential']} dispatches")
    emit(f"map_phase_stacked_k{k}_e{epochs}", st_us,
         f"vmap+scan {payload['speedup']:.1f}x {last['stacked']} dispatches")
    return payload


def run_unequal(k: int = 4, n_per_class: int = 40, epochs: int = 2,
                batch_size: int = 32, iters: int = 3, out_dir: str = None):
    """Unequal shards (sizes 1:2:…:k): sequential members + shard-weighted
    Reduce vs the padded/masked stacked path. Before this path existed the
    stacked Map phase raised on these shards and everything fell back to the
    sequential loop — ``speedup`` is what the masked scan claws back."""
    cfg, ds, lr = _workload(n_per_class)
    base = len(ds.x) // (k * (k + 1) // 2)
    sizes = [base * (i + 1) for i in range(k)]
    parts = partition_unequal(ds.x, ds.y, sizes, seed=0)

    def backend_fn(backend):
        runner = AveragingRun(
            cfg,
            MapConfig(epochs=epochs, lr_schedule=lr, batch_size=batch_size,
                      backend=backend),
            ReduceConfig(strategy="shard_weighted"))
        return lambda: runner.run(parts, KEY).averaged.beta

    seq_us = time_call(backend_fn("sequential"), warmup=1, iters=iters)
    st_us = time_call(backend_fn("stacked"), warmup=1, iters=iters)

    batch_counts = [len(p.x) // batch_size for p in parts]
    payload = {
        "sequential_us": seq_us,
        "stacked_us": st_us,
        "speedup": seq_us / st_us,
        "k": k,
        "epochs": epochs,
        "shard_sizes": sizes,
        "batch_counts": batch_counts,
        "padded_batches": max(batch_counts),
        "pad_fraction": 1.0 - sum(batch_counts) / (k * max(batch_counts)),
        "batch_size": batch_size,
        "feature_dim": cnn.feature_dim(cfg),
        "backend": jax.default_backend(),
    }
    save_result("BENCH_map_phase_unequal", payload, out_dir=out_dir)
    emit(f"map_phase_unequal_seq_k{k}_e{epochs}", seq_us,
         f"shards {batch_counts}")
    emit(f"map_phase_unequal_stacked_k{k}_e{epochs}", st_us,
         f"masked scan {payload['speedup']:.1f}x")
    return payload


def run_chunked(k: int = 4, n_per_class: int = 40, epochs: int = 2,
                batch_size: int = 32, chunk_batches: int = 2,
                iters: int = 3, out_dir: str = None):
    """Monolithic whole-epoch scan vs the double-buffered chunked scan.
    The chunked path bounds peak device batch memory to TWO chunks — the
    one scanning plus the one in flight (``peak_bytes`` vs
    ``epoch_bytes``) — at the cost of one dispatch per chunk; the two must
    be bit-identical (asserted here, not just tested)."""
    cfg, ds, lr = _workload(n_per_class)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    nb = len(parts[0].x) // batch_size
    if not 0 < chunk_batches < nb:
        raise ValueError(
            f"chunk_batches={chunk_batches} would not chunk a {nb}-batch "
            f"epoch — the 'chunked' timing would silently measure the "
            f"monolithic path")
    last = {}  # beta from the most recent timed run (deterministic per path)

    def variant(name, chunk):
        runner = AveragingRun(cfg, MapConfig(
            epochs=epochs, lr_schedule=lr, batch_size=batch_size,
            backend="stacked", chunk_batches=chunk))

        def go():
            last[name] = runner.run(parts, KEY).stacked.beta
            return last[name]
        return go

    mono_us = time_call(variant("mono", None), warmup=1, iters=iters)
    chk_us = time_call(variant("chunked", chunk_batches), warmup=1,
                       iters=iters)
    identical = bool(np.array_equal(np.asarray(last["mono"]),
                                    np.asarray(last["chunked"])))

    row = int(np.prod(ds.x.shape[1:])) * 4 + cfg.num_classes * 4 + 4
    payload = {
        "monolithic_us": mono_us,
        "chunked_us": chk_us,
        "overhead": chk_us / mono_us,
        "bit_identical": identical,
        "k": k,
        "epochs": epochs,
        "num_batches": nb,
        "chunk_batches": chunk_batches,
        "epoch_bytes": nb * k * batch_size * row,
        "chunk_bytes": chunk_batches * k * batch_size * row,
        "peak_bytes": 2 * chunk_batches * k * batch_size * row,
        "batch_size": batch_size,
        "backend": jax.default_backend(),
    }
    save_result("BENCH_map_phase_chunked", payload, out_dir=out_dir)
    emit(f"map_phase_mono_k{k}_e{epochs}", mono_us, f"{nb} batches resident")
    emit(f"map_phase_chunked_k{k}_e{epochs}", chk_us,
         f"chunk={chunk_batches} {payload['overhead']:.2f}x "
         f"bit_identical={identical}")
    if not identical:
        raise AssertionError("chunked scan diverged from monolithic scan")
    return payload


def run_rounds(k: int = 4, n_per_class: int = 40, epochs: int = 4,
               batch_size: int = 32, rounds: int = 4, iters: int = 3,
               out_dir: str = None):
    """Single final average (``rounds=1``) vs multi-round parallel-SGD
    averaging (``rounds=r``, one sync every epochs/r epochs) on the stacked
    backend. ``sync_overhead`` is the wall-clock price of the extra
    averaging events; ``round_dispatches`` comes from ``RunResult``'s
    per-round telemetry."""
    if rounds < 2:
        raise ValueError(f"rounds={rounds} would benchmark the single-"
                         f"average config against itself; use rounds >= 2")
    if epochs % rounds:
        raise ValueError(f"epochs ({epochs}) must split into rounds "
                         f"({rounds})")
    cfg, ds, lr = _workload(n_per_class)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    last = {}

    def variant(r):
        runner = AveragingRun(
            cfg,
            MapConfig(epochs=epochs, lr_schedule=lr, batch_size=batch_size,
                      backend="stacked"),
            ReduceConfig(rounds=r))

        def go():
            last[r] = runner.run(parts, KEY)
            return last[r].averaged.beta
        return go

    single_us = time_call(variant(1), warmup=1, iters=iters)
    multi_us = time_call(variant(rounds), warmup=1, iters=iters)
    res = last[rounds]

    payload = {
        "single_round_us": single_us,
        "multi_round_us": multi_us,
        "sync_overhead": multi_us / single_us,
        "k": k,
        "epochs": epochs,
        "rounds": rounds,
        "epochs_per_round": epochs // rounds,
        "round_dispatches": [r.dispatches for r in res.rounds],
        "round_sync_dispatches": res.round_syncs,
        "total_dispatches": res.dispatches,
        "batch_size": batch_size,
        "backend": jax.default_backend(),
    }
    save_result("BENCH_map_phase_rounds", payload, out_dir=out_dir)
    emit(f"map_phase_rounds1_k{k}_e{epochs}", single_us,
         "single final average")
    emit(f"map_phase_rounds{rounds}_k{k}_e{epochs}", multi_us,
         f"sync every {epochs // rounds} epochs "
         f"{payload['sync_overhead']:.2f}x")
    return payload


def run_mesh(k: int = 8, n_per_class: int = 80, epochs: int = 2,
             batch_size: int = 32, rounds: int = 2,
             devices=(1, 2, 4, 8), iters: int = 2, out_dir: str = None):
    """Member-mesh scaling sweep: the SAME k-member workload over 1, 2, 4
    and 8 simulated pods, against the single-program stacked baseline.

    When the current process has fewer devices than ``max(devices)`` it
    re-execs itself with ``--xla_force_host_platform_device_count`` (jax
    locks the device count at first init, so the flag cannot be applied
    in-process) and returns the child's JSON payload.

    Besides wall-clock the payload records the one-collective-per-round
    cost model, measured off the compiled HLO (not asserted by hand):
    ``allreduce_per_sync`` / ``allreduce_per_reduce`` MUST be exactly 1 —
    a round costs epochs/rounds scan dispatches with ZERO collectives plus
    one all-reduce of the flat member-weighted tree; the final Reduce is
    one all-reduce of (params, β). The averaged β is also checked against
    the stacked baseline every timed config (rtol 1e-4)."""
    need = max(devices)
    if len(jax.devices()) < need:
        # the forced-host-device flag only works on the CPU backend, and a
        # child that inherited it yet still sees too few devices must not
        # fork again — both would loop this re-exec forever
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"run_mesh needs {need} devices but the {jax.default_backend()}"
                f" backend has {len(jax.devices())} and simulated host "
                f"devices only exist on CPU — run with JAX_PLATFORMS=cpu or "
                f"pass devices= within the real device count")
        if os.environ.get("_REPRO_MESH_SWEEP_CHILD"):
            raise RuntimeError(
                f"mesh-sweep child still sees {len(jax.devices())} devices "
                f"(< {need}) despite the forced flag — refusing to re-exec "
                f"again")
        out_dir = out_dir or os.path.join(ROOT, "experiments")
        from repro.launch.mesh import host_device_flags
        env = dict(
            os.environ,
            _REPRO_MESH_SWEEP_CHILD="1",
            PYTHONPATH=os.pathsep.join(
                [os.path.join(ROOT, "src"), ROOT,
                 os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep),
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " " +
                       host_device_flags(need)).strip())
        subprocess.run(
            [sys.executable, "-m", "benchmarks.map_phase", "--mesh-sweep",
             "--k", str(k), "--n-per-class", str(n_per_class),
             "--epochs", str(epochs), "--batch-size", str(batch_size),
             "--rounds", str(rounds),
             "--devices", ",".join(map(str, devices)),
             "--iters", str(iters), "--out-dir", out_dir],
            check=True, env=env, cwd=ROOT)
        with open(os.path.join(out_dir, "BENCH_map_phase_mesh.json")) as f:
            return json.load(f)

    from repro.analysis.hlo import (ContractViolation, check_one_all_reduce,
                                    lower_stacked_programs)
    from repro.launch.hlo_analysis import collective_stats
    from repro.launch.mesh import make_member_mesh

    cfg, ds, lr = _workload(n_per_class)
    if epochs:
        # λ=1 keeps the per-batch β solve well-conditioned, so the
        # cross-backend equivalence guard below measures implementation
        # equivalence instead of f32 amplification through a
        # nearly-singular normal matrix — the same choice the SGD
        # equivalence tests make
        from repro.configs.base import replace
        cfg = replace(cfg, elm_lambda=1.0)
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    reduce_cfg = ReduceConfig(rounds=rounds if epochs else 1)
    last = {}

    def variant(backend, mesh=None):
        runner = AveragingRun(
            cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                           batch_size=batch_size, backend=backend,
                           mesh=mesh), reduce_cfg)

        def go():
            last[backend] = runner.run(parts, KEY)
            return last[backend].averaged.beta
        return go

    st_us = time_call(variant("stacked"), warmup=1, iters=iters)
    st_beta = np.asarray(last["stacked"].averaged.beta)

    sweep = []
    for d in devices:
        mesh = make_member_mesh(num_pods=d)
        us = time_call(variant("mesh", mesh), warmup=1, iters=iters)
        res = last["mesh"]
        np.testing.assert_allclose(          # equivalence guard, every config
            np.asarray(res.averaged.beta), st_beta, rtol=1e-4, atol=1e-4)
        k_pad = -(-k // d) * d
        sweep.append({
            "devices": d,
            "mesh_us": us,
            "speedup_vs_stacked": st_us / us,
            "k_pad": k_pad,
            "members_per_pod": k_pad // d,
            "pad_members": k_pad - k,
            "dispatches": res.dispatches,
            "round_syncs": res.round_syncs,
        })

    # the cost model, read off the compiled HLO at the largest mesh
    _, programs = lower_stacked_programs(
        cfg, "mesh", mesh=make_member_mesh(num_pods=need), k=k)
    sync_hlo = programs["_sync"].compile().as_text()
    sync_cs = collective_stats(sync_hlo)
    red_hlo = programs["_reduce"].compile().as_text()
    red_cs = collective_stats(red_hlo)

    payload = {
        "stacked_us": st_us,
        "sweep": sweep,
        "k": k,
        "epochs": epochs,
        "rounds": rounds if epochs else 1,
        "batch_size": batch_size,
        "feature_dim": cnn.feature_dim(cfg),
        "allreduce_per_sync": sync_cs.count_by_kind.get("all-reduce", 0),
        "allreduce_per_reduce": red_cs.count_by_kind.get("all-reduce", 0),
        "sync_collective_per_chip_bytes": sync_cs.per_chip_bytes,
        "reduce_collective_per_chip_bytes": red_cs.per_chip_bytes,
        "cost_model": "per round: epochs/rounds scan dispatches with 0 "
                      "collectives + 1 all-reduce of the flat weighted "
                      "param tree; final Reduce: 1 all-reduce of "
                      "(params, beta)",
        "note": "simulated host pods share one physical CPU — the sweep "
                "measures dispatch/collective structure, not compute "
                "scaling",
        "backend": jax.default_backend(),
    }
    # the contract gate runs BEFORE anything is persisted — a violation
    # must not leave a fresh-but-invalid artifact for later readers;
    # collective_stats above stays for the per-chip-bytes cost model,
    # the pass/fail verdict is the auditor's
    for label, hlo in (("sync", sync_hlo), ("reduce", red_hlo)):
        check = check_one_all_reduce(hlo, name=f"one-all-reduce/{label}")
        if not check.ok:
            raise ContractViolation(
                f"one-collective contract violated: {check}")
    save_result("BENCH_map_phase_mesh", payload, out_dir=out_dir)
    emit(f"map_phase_stacked_k{k}_e{epochs}_baseline", st_us, "single device")
    for row in sweep:
        emit(f"map_phase_mesh_k{k}_d{row['devices']}", row["mesh_us"],
             f"{row['members_per_pod']}/pod pad={row['pad_members']} "
             f"{row['speedup_vs_stacked']:.2f}x")
    return payload


def main(smoke: bool = False, out_dir: str = None):
    kw = {"out_dir": out_dir} if out_dir else {}
    if smoke:
        # smoke results go to a throwaway dir (or the CALLER's --out-dir —
        # CI uploads that as an artifact) so the tracked full-config
        # artifacts under experiments/ are never overwritten by a CI tier
        import tempfile
        kw = dict(k=2, n_per_class=8, epochs=1, batch_size=16, iters=1,
                  out_dir=out_dir or
                  tempfile.mkdtemp(prefix="bench_map_phase_smoke_"))
        print(f"# smoke JSONs -> {kw['out_dir']}", flush=True)
    run(**kw)
    run_unequal(**kw)
    run_chunked(chunk_batches=2, **kw)
    # rounds needs epochs divisible by rounds; the smoke tier runs the
    # smallest multi-round config (2 epochs, sync after epoch 1)
    run_rounds(rounds=2, **{**kw, "epochs": 2}) if smoke else run_rounds(**kw)
    # the mesh sweep re-execs under forced host devices; smoke sweeps a
    # 2-pod mesh only (1 epoch, single final average)
    if smoke:
        run_mesh(k=2, n_per_class=8, epochs=1, batch_size=16, rounds=1,
                 devices=(1, 2), iters=1, out_dir=kw["out_dir"])
    else:
        run_mesh(**kw)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (k=2, 1 epoch, 1 iter)")
    ap.add_argument("--mesh-sweep", action="store_true",
                    help="run ONLY the mesh scaling sweep inline (the "
                         "re-exec child entry — expects the forced host "
                         "device count already in XLA_FLAGS)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--n-per-class", type=int, default=80)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args()
    if args.mesh_sweep:
        run_mesh(k=args.k, n_per_class=args.n_per_class, epochs=args.epochs,
                 batch_size=args.batch_size, rounds=args.rounds,
                 devices=tuple(int(d) for d in args.devices.split(",")),
                 iters=args.iters, out_dir=args.out_dir)
    else:
        main(smoke=args.smoke, out_dir=args.out_dir)
