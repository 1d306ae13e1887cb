"""Streaming Map phase under concept drift — sync policies compared.

The end-to-end scenario behind ``repro.stream`` (docs/streaming.md): k
class-skewed member streams (each member only ever sees a subset of the
label space), a label-permutation concept drift injected mid-stream, and
the SAME stream replayed under three sync policies:

* ``never``   — one initial publish, then no Reduce ever (the stale-
  endpoint baseline);
* ``cadence`` — ``ReduceConfig(sync="rounds")``: a fixed every-N-chunks
  publish;
* ``drift``   — ``ReduceConfig(sync="drift")``: publishes fire while any
  member's prequential ``DriftDetector`` signals drift.

One JSON (``experiments/BENCH_stream_map.json``), with the contracts
ASSERTED before anything is persisted (CI's streaming smoke step rides
on them):

* drift-triggered sync RECOVERS held-out accuracy on the post-drift
  concept and beats the never-sync endpoint;
* the sliding windows pass the downdate equivalence gate
  (``SlidingWindowStats.verify``) after real evictions;
* the glob-pattern ``FileSource`` yields chunk-for-chunk the same stream
  as the in-memory source it was staged from (ragged file sizes, so the
  carry-over chunking is exercised);
* the drift run's checkpoints land at IRREGULAR round numbers and a
  ``CheckpointWatcher`` stages the newest one in a single poll onto a
  live ``EnsembleServer`` with ZERO recompiles.

Run standalone: ``PYTHONPATH=src python -m benchmarks.stream_map``
(``--smoke`` for the tiny CI config; or via ``benchmarks/run.py``).
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit, save_result
from repro.checkpoint import run_state
from repro.configs.base import get_reduced_config
from repro.core.executor import CheckpointConfig
from repro.core.runner import MapConfig, ReduceConfig, evaluate_model
from repro.data.synthetic import make_extended_mnist
from repro.serve import (BucketedScorer, CheckpointWatcher, EnsembleServer,
                         ServeConfig)
from repro.stream import (ArraySource, FileSource, StreamConfig,
                          StreamingRun, SyntheticDriftSource, member_streams,
                          write_shard_files)

KEY = jax.random.PRNGKey(0)
LABEL_SHIFT = 5
CLASS_SETS = ((0, 1, 2, 3), (3, 4, 5, 6), (6, 7, 8, 9))


def _sources(n_chunks, chunk_rows, drift_at, n_per_class):
    """Fresh per-member drift sources (fresh so every policy replays the
    IDENTICAL stream: the sources are deterministic in their seeds)."""
    return [SyntheticDriftSource(
        n_chunks=n_chunks, chunk_rows=chunk_rows, drift_at=drift_at,
        seed=11 + i, label_shift=LABEL_SHIFT, class_filter=CLASS_SETS[i],
        n_per_class=n_per_class) for i in range(len(CLASS_SETS))]


def _check_file_source(src, tmp_dir: str) -> dict:
    """Stage one member's stream to ragged ``.npz`` shard files and
    assert the glob-pattern ``FileSource`` replays it chunk-for-chunk
    (the carry-over chunking contract)."""
    xs, ys = zip(*src.chunks())
    x, y = np.concatenate(xs), np.concatenate(ys)
    ragged = src.chunk_rows * 3 - 7            # never a chunk multiple
    paths = write_shard_files(x, y, tmp_dir, rows_per_file=ragged)
    fsrc = FileSource(os.path.join(tmp_dir, "shard-*.npz"),
                      chunk_rows=src.chunk_rows)
    asrc = ArraySource(x, y, chunk_rows=src.chunk_rows)
    match = all(np.array_equal(fx, ax) and np.array_equal(fy, ay)
                for (fx, fy), (ax, ay) in zip(fsrc.chunks(), asrc.chunks()))
    n_file_chunks = sum(1 for _ in fsrc.chunks())
    assert match, "FileSource diverged from the array stream it was " \
                  "staged from"
    assert n_file_chunks == len(xs), \
        f"FileSource yielded {n_file_chunks} chunks for {len(xs)} staged"
    return {"files": len(paths), "chunks": n_file_chunks,
            "ragged_rows_per_file": ragged, "matches_array_source": match}


def run_stream(smoke: bool) -> dict:
    k = len(CLASS_SETS)
    n_chunks = 24 if smoke else 48
    chunk_rows = 64 if smoke else 128
    drift_at = n_chunks // 2
    window = 6 if smoke else 8
    cadence = 8 if smoke else 12
    n_per_class = 24 if smoke else 48
    max_batch = 16

    cfg = get_reduced_config("cnn_elm_6c12c")
    # held-out eval glyphs (fresh seed), labelled with the POST-drift
    # concept: the permuted labels every stream switches to at drift_at
    ev = make_extended_mnist(n_per_class=20 if smoke else 40, seed=999)
    ey_post = ((ev.y + LABEL_SHIFT) % ev.num_classes).astype(ev.y.dtype)

    file_source = _check_file_source(
        _sources(n_chunks, chunk_rows, drift_at, n_per_class)[0],
        tempfile.mkdtemp(prefix="stream-shards-"))

    policies = []
    results = {}
    dirs = {}
    for policy in ("never", "cadence", "drift"):
        run = StreamingRun(
            cfg,
            MapConfig(epochs=0, batch_size=32, backend="stacked"),
            ReduceConfig(sync="drift" if policy == "drift" else "rounds"),
            StreamConfig(window_chunks=window, holdout_rows=16,
                         sync_every=0 if policy == "never" else cadence,
                         drift_threshold=0.25, drift_warmup=3,
                         verify_every=window))
        streams = member_streams(
            _sources(n_chunks, chunk_rows, drift_at, n_per_class), k,
            seed=1000, per_member=True)
        d = tempfile.mkdtemp(prefix=f"stream-{policy}-")
        t0 = time.perf_counter()
        res = run.run(streams, KEY, checkpoint=CheckpointConfig(dir=d))
        wall_us = (time.perf_counter() - t0) * 1e6
        assert res.last_published is not None
        pub_acc = evaluate_model(cfg, res.last_published, ev.x, ey_post)
        fresh_acc = evaluate_model(cfg, res.averaged, ev.x, ey_post)
        results[policy], dirs[policy] = res, d
        policies.append({
            "policy": policy, "syncs": len(res.syncs),
            "sync_chunks": res.sync_chunks,
            "published_acc": pub_acc, "fresh_acc": fresh_acc,
            "wall_us": wall_us, "dispatches": res.dispatches,
        })
        emit(f"stream_{policy}", wall_us / n_chunks,
             f"published_acc={pub_acc:.3f} syncs={len(res.syncs)}")

    by = {row["policy"]: row for row in policies}
    # THE headline: the drift-triggered endpoint recovers the post-drift
    # concept; the never-sync endpoint is stuck on the stale one
    assert by["drift"]["published_acc"] > by["never"]["published_acc"], \
        f"drift {by['drift']['published_acc']:.3f} did not beat " \
        f"never-sync {by['never']['published_acc']:.3f}"
    assert by["never"]["syncs"] == 1, "never-sync published more than once"
    assert any(c > drift_at for c in by["drift"]["sync_chunks"]), \
        "drift policy never fired after the injected shift"

    drift_res = results["drift"]
    # the window equivalence gate, after real evictions (verify raises —
    # and fails the benchmark — on downdate drift beyond f32 tolerance)
    gate_err = max(w.verify() for w in drift_res.windows)
    assert all(w.evicted > 0 for w in drift_res.windows), \
        "windows never slid — no downdate was exercised"
    window_gate = {
        "max_abs_error": float(gate_err),
        "pushed": int(drift_res.windows[0].pushed),
        "evicted": int(drift_res.windows[0].evicted),
        "capacity": window, "ok": True,
    }
    # prequential recovery: the held-out score collapses AT the shift and
    # is back up by stream end (the detector's own evidence)
    score_at_drift = float(np.mean(drift_res.records[drift_at].scores))
    score_end = float(np.mean(drift_res.records[-1].scores))
    assert score_end > score_at_drift, \
        f"no prequential recovery: {score_at_drift:.3f} -> {score_end:.3f}"

    serve = _check_serve(cfg, dirs["drift"], drift_res, ev, ey_post,
                         max_batch)

    return {
        "k": k, "n_chunks": n_chunks, "chunk_rows": chunk_rows,
        "drift_at": drift_at, "window_chunks": window, "cadence": cadence,
        "backend": "stacked",
        "policies": policies,
        "window_gate": window_gate,
        "recovery": {"score_at_drift": score_at_drift,
                     "score_end": score_end},
        "file_source": file_source,
        "serve": serve,
    }


def _check_serve(cfg, ckpt_dir, res, ev, ey_post, max_batch) -> dict:
    """A live endpoint starts on the drift run's FIRST published round
    and one watcher poll must jump it straight to the LAST — the rounds
    in between are irregular drift-triggered chunk indices, and the swap
    must reuse every compiled bucket (zero recompiles)."""
    first, last = res.syncs[0].chunk, res.syncs[-1].chunk
    scorer = BucketedScorer(cfg, run_state.restore_round(ckpt_dir, first)
                            .members, max_batch=max_batch)
    scorer.warmup()
    n_buckets = len(scorer.ladder.buckets)
    server = EnsembleServer(scorer, ServeConfig(
        max_batch=max_batch, max_wait_ms=2.0)).start(warmup=False)
    watcher = CheckpointWatcher(ckpt_dir, server, poll_ms=10,
                                start_round=first)
    staged = watcher.poll_once()
    assert staged == last, \
        f"watcher staged round {staged}, newest published is {last}"
    # score through the endpoint so the swap is APPLIED, then close
    labels = [f.result(timeout=30).label
              for f in server.submit_many(ev.x[:max_batch])]
    server.close()
    stats = server.stats()
    assert scorer.assert_compile_budget() == n_buckets, \
        f"{scorer.compile_count()} compiles for {n_buckets} buckets"
    assert stats.swaps == 1 and stats.failed == 0 and stats.dropped == 0
    post_acc = float(np.mean(np.asarray(labels) ==
                             np.asarray(ey_post[:max_batch])))
    emit("stream_serve_swap", 0.0,
         f"round {first}->{staged} recompiles=0 post_acc={post_acc:.3f}")
    return {"first_round": int(first), "staged_round": int(staged),
            "swaps": stats.swaps, "failed": stats.failed,
            "dropped": stats.dropped,
            "recompiles": scorer.compile_count() - n_buckets,
            "buckets": list(scorer.ladder.buckets),
            "compile_count": scorer.compile_count()}


def main(smoke: bool = False, out_dir: str = None):
    payload = run_stream(smoke)
    path = save_result("BENCH_stream_map", payload, out_dir)
    emit("stream_map_json", 0.0, path)


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
