"""Ensemble serving under open-loop load — tail latency vs offered rate.

The serving-side analogue of the Map-phase scaling benchmarks: a trained
k-member CNN-ELM ensemble behind ``repro.serve``'s continuous-batching
endpoint (``EnsembleServer`` over a ``BucketedScorer``), driven by the
synthetic open-loop load generator at ≥3 offered rates. One JSON
(``experiments/BENCH_serve_ensemble.json``):

* ``loads`` — per offered rate: p50/p95/p99/mean/max latency (ms),
  achieved images/s, completed/failed counts. Open loop means queueing
  delay lands IN the latency numbers, so saturation shows up as p99
  growth + achieved < offered, not as a throttled generator.
* ``compile_count`` / ``buckets`` — THE bucketed-shape contract,
  asserted (not just measured) before anything is persisted: after
  warmup + the whole sweep + a live weight hot-swap, the scorer holds
  EXACTLY one compiled program per ladder bucket. Any recompile fails
  the benchmark (and CI's serve-smoke step with it).
* ``hot_swap`` — mid-sweep the serving weights are swapped for a
  shape-identical re-stack (the checkpoint hot-reload path without the
  disk): asserted zero failed/dropped requests and zero new compiles.

Run standalone: ``PYTHONPATH=src python -m benchmarks.serve_ensemble``
(``--smoke`` for the tiny CI config; or via ``benchmarks/run.py``).
"""
from __future__ import annotations

import jax

from benchmarks.common import emit, save_result
from repro.configs.base import get_reduced_config
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.core.cnn_elm import stack_models
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.serve import EnsembleServer, ServeConfig, run_open_loop

KEY = jax.random.PRNGKey(0)


def run_serve(smoke: bool) -> dict:
    k = 4
    n_per_class = 40 if smoke else 120
    max_batch = 16 if smoke else 32
    n_requests = 120 if smoke else 600
    rates = (60.0, 120.0, 240.0) if smoke else (100.0, 200.0, 400.0, 800.0)

    cfg = get_reduced_config("cnn_elm_6c12c")
    ds = make_extended_mnist(n_per_class=n_per_class, seed=0)
    train, test = ds.split(n_test=10 * max(8, n_per_class // 4))
    result = AveragingRun(
        cfg, MapConfig(epochs=0, batch_size=200, backend="stacked"),
        ReduceConfig()).run(partition_iid(train.x, train.y, k), KEY)

    scorer = result.ensemble().bucketed_scorer(max_batch=max_batch)
    scorer.warmup()
    n_buckets = len(scorer.ladder.buckets)
    assert scorer.compile_count() == n_buckets, \
        f"warmup compiled {scorer.compile_count()} != {n_buckets} buckets"

    server = EnsembleServer(scorer, ServeConfig(
        max_batch=max_batch, max_wait_ms=4.0)).start(warmup=False)
    loads = []
    for i, rate in enumerate(rates):
        rep = run_open_loop(server, test.x, rate_per_s=rate,
                            n_requests=n_requests, seed=17 + i)
        assert rep.failed == 0, f"{rep.failed} failed requests at {rate}/s"
        loads.append(rep.to_json())
        emit(f"serve_rate{int(rate)}", rep.p50_ms * 1e3,
             f"p99={rep.p99_ms:.2f}ms imgs/s={rep.achieved_per_s:.0f}")
        if i == 0:
            # live hot-swap mid-sweep: a shape-identical re-stack (the
            # checkpoint watcher's payload, minus the disk) — must reuse
            # every compiled bucket and drop nothing
            server.swap_members(stack_models(list(reversed(result.members))))
    server.close()
    stats = server.stats()

    # THE regression guard: exactly one XLA compile per bucket shape,
    # across warmup + every load + the hot swap
    assert scorer.assert_compile_budget() == n_buckets, \
        f"{scorer.compile_count()} compiles for {n_buckets} buckets"
    assert stats.swaps == 1, f"hot swap not applied ({stats.swaps})"
    assert stats.failed == 0 and stats.dropped == 0, \
        f"failed={stats.failed} dropped={stats.dropped}"

    return {
        "k": k, "max_batch": max_batch, "max_wait_ms": 4.0,
        "n_requests_per_load": n_requests,
        "buckets": list(scorer.ladder.buckets),
        "compile_count": scorer.compile_count(),
        "batches": stats.batches,
        "mean_batch_occupancy": stats.mean_occupancy,
        "hot_swap": {"swaps": stats.swaps, "failed": stats.failed,
                     "dropped": stats.dropped,
                     "recompiles": scorer.compile_count() - n_buckets},
        "loads": loads,
    }


def main(smoke: bool = False, out_dir: str = None):
    payload = run_serve(smoke)
    path = save_result("BENCH_serve_ensemble", payload, out_dir)
    emit("serve_ensemble_json", 0.0, path)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config (same assertions)")
    ap.add_argument("--out-dir", default=None,
                    help="where the JSON lands (default: experiments/)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(smoke=args.smoke, out_dir=args.out_dir)
