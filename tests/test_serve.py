"""The serving subsystem (``repro.serve``): bucket ladder, compile-count
guarantee, pad-and-mask scoring, the vote tie rule through the padded
path, the continuous-batching scheduler's SLO contract, checkpoint
hot-reload with zero drops, and the torn-checkpoint robustness of
``ckpt.latest_valid_step`` (docs/serving.md documents every contract
asserted here)."""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

from repro.checkpoint import ckpt, run_state
from repro.configs.base import get_reduced_config, replace
from repro.core import faults
from repro.core.runner import AveragingRun, Ensemble, MapConfig, ReduceConfig
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.optim.schedules import dynamic_paper
from repro.serve import (BucketLadder, BucketedScorer, CheckpointWatcher,
                         EnsembleServer, QueueFull, ServeConfig, SwapRejected,
                         combine_block, run_open_loop)

CFG = get_reduced_config("cnn_elm_6c12c")


@pytest.fixture(scope="module")
def workload():
    ds = make_extended_mnist(n_per_class=30, seed=0)
    train, test = ds.split(n_test=60)
    result = AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=100, backend="stacked"),
        ReduceConfig()).run(partition_iid(train.x, train.y, 3),
                            jax.random.PRNGKey(0))
    return result, test


# ---------------------------------------------------------------------------
# Bucket ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder_shapes():
    assert BucketLadder(16).buckets == (1, 2, 4, 8, 16)
    assert BucketLadder(1).buckets == (1,)
    # max_batch is always the top rung, power of two or not
    assert BucketLadder(12).buckets == (1, 2, 4, 8, 12)
    assert BucketLadder(16, min_bucket=4).buckets == (4, 8, 16)


def test_bucket_for():
    lad = BucketLadder(16)
    assert [lad.bucket_for(n) for n in (1, 2, 3, 4, 5, 8, 9, 16)] == \
        [1, 2, 4, 4, 8, 8, 16, 16]
    with pytest.raises(ValueError):
        lad.bucket_for(0)
    with pytest.raises(ValueError):
        lad.bucket_for(17)          # the scheduler must never form one
    with pytest.raises(ValueError):
        BucketLadder(0)


def test_pad_block_rows_and_zeros():
    lad = BucketLadder(8)
    x = np.ones((3, 28, 28), np.float32)
    padded, n = lad.pad_block(x)
    assert padded.shape == (4, 28, 28) and n == 3
    assert np.array_equal(padded[:3], x) and not padded[3:].any()
    exact, n = lad.pad_block(np.ones((4, 28, 28)))
    assert exact.shape == (4, 28, 28) and n == 4


# ---------------------------------------------------------------------------
# The compile-count guarantee (the acceptance-criteria assertion)
# ---------------------------------------------------------------------------

def test_compile_once_per_bucket(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=8)
    scorer.warmup()
    n_buckets = len(scorer.ladder.buckets)
    assert scorer.compile_count() == n_buckets
    # every batch size from 1..max_batch dispatches at a ladder shape:
    # ZERO new compiles after warmup
    for n in range(1, 9):
        scorer.score_block(test.x[:n])
    assert scorer.compile_count() == n_buckets
    # a shape-identical weight swap reuses every compiled program
    from repro.core.cnn_elm import stack_models
    scorer.swap_members(stack_models(list(reversed(result.members))))
    for n in (1, 3, 5, 8):
        scorer.score_block(test.x[:n])
    assert scorer.assert_compile_budget() == n_buckets


def test_compile_count_without_warmup_lazy(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=8)
    scorer.score_block(test.x[:3])       # bucket 4
    scorer.score_block(test.x[:4])       # bucket 4 again — same program
    assert scorer.compile_count() == 1
    scorer.score_block(test.x[:5])       # bucket 8
    assert scorer.compile_count() == 2
    scorer.assert_compile_budget()


# ---------------------------------------------------------------------------
# Pad-and-mask scoring + the pinned vote tie rule
# ---------------------------------------------------------------------------

def test_padded_scores_match_ensemble_surface(workload):
    result, test = workload
    ens = result.ensemble()
    scorer = ens.bucketed_scorer(max_batch=8)
    for n in (1, 3, 5, 7, 8):
        got = scorer.score_block(test.x[:n])
        ref = ens.member_scores(test.x[:n])
        assert got.shape == ref.shape == (3, n, CFG.num_classes)
        # same math, different (padded) batch shape: numerically equal
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        assert np.array_equal(got.argmax(-1), ref.argmax(-1))


def test_padding_rows_never_vote(workload):
    """Batch composition must not change any row's answer: a single-image
    request scored inside a padded bucket equals the same image scored
    alone, for BOTH combine rules."""
    result, test = workload
    ens_vote = Ensemble(CFG, result.stacked, combine="vote")
    scorer = result.ensemble().bucketed_scorer(max_batch=8)
    n = 5                                     # pads to bucket 8: 3 pad rows
    for combine, ref in (
            ("mean", result.ensemble().predict(test.x[:n])),
            ("vote", ens_vote.predict(test.x[:n]))):
        got = scorer.predict_block(test.x[:n], combine=combine)
        assert np.array_equal(got, ref), combine
        # per-image: the padded-batch answer equals each image served solo
        solo = np.array([scorer.predict_block(test.x[i:i + 1],
                                              combine=combine)[0]
                         for i in range(n)])
        assert np.array_equal(got, solo), combine


def test_vote_tie_resolves_to_lowest_class_index():
    """The documented rule, pinned at the combine layer the server uses:
    ties → LOWEST class index (np.argmax convention)."""
    C = 10
    # 3 members, 2 rows. Row 0: three-way 1-1-1 tie among {7, 2, 5} → 2.
    # Row 1: members agree on 9 → 9 (no tie).
    scores = np.zeros((3, 2, C), np.float32)
    for m, cls in enumerate((7, 2, 5)):
        scores[m, 0, cls] = 1.0
    scores[:, 1, 9] = 1.0
    assert combine_block(scores, "vote", C).tolist() == [2, 9]
    # 2 members, 1-1 tie between {4, 1} → 1
    scores2 = np.zeros((2, 1, C), np.float32)
    scores2[0, 0, 4] = 1.0
    scores2[1, 0, 1] = 1.0
    assert combine_block(scores2, "vote", C).tolist() == [1]
    # mean combine: exact score tie between classes 3 and 6 → 3
    scores3 = np.zeros((2, 1, C), np.float32)
    scores3[:, 0, 3] = 0.5
    scores3[:, 0, 6] = 0.5
    assert combine_block(scores3, "mean", C).tolist() == [3]


def test_vote_tie_rule_survives_padded_path(workload):
    """End-to-end pin: vote predictions through the padded/bucketed
    serving path are identical to ``Ensemble(combine='vote')`` — same
    argmaxes, same vote counts, same tie resolution — for batch sizes
    that do and do not hit a bucket exactly."""
    result, test = workload
    ens_vote = Ensemble(CFG, result.stacked, combine="vote")
    scorer = result.ensemble().bucketed_scorer(max_batch=16)
    for n in (1, 2, 3, 6, 11, 16):
        got = scorer.predict_block(test.x[:n], combine="vote")
        assert np.array_equal(got, ens_vote.predict(test.x[:n])), n


# ---------------------------------------------------------------------------
# Hot swap validation
# ---------------------------------------------------------------------------

def test_swap_rejects_mismatched_tree(workload):
    result, _ = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=4)
    from repro.core.cnn_elm import StackedMembers, stack_models
    with pytest.raises(SwapRejected):
        scorer.swap_members(stack_models(result.members[:2]))   # wrong k
    bad_beta = StackedMembers(result.stacked.cnn_params,
                              result.stacked.beta[:, :, :5])
    with pytest.raises(SwapRejected):
        scorer.swap_members(bad_beta)                           # wrong shape


# ---------------------------------------------------------------------------
# Scheduler: the SLO contract
# ---------------------------------------------------------------------------

def test_flush_on_max_batch(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=4)
    # max_wait way beyond the test budget: only the max-batch trigger can
    # flush a FULL batch (the trailing partial flushes on close's drain)
    with EnsembleServer(scorer, ServeConfig(max_batch=4,
                                            max_wait_ms=60_000)) as srv:
        futs = srv.submit_many(test.x[:8])
        for f in futs:
            assert f.result(timeout=30).label >= 0
        t0 = time.monotonic()
    assert time.monotonic() - t0 < 30            # never waited out the SLO
    stats = srv.stats()
    assert stats.completed == 8 and stats.failed == 0 and stats.dropped == 0
    assert all(n == 4 for n, _ in srv._batches)


def test_flush_on_slo_deadline(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=8)
    with EnsembleServer(scorer, ServeConfig(max_batch=8,
                                            max_wait_ms=30.0)) as srv:
        futs = srv.submit_many(test.x[:3])       # never reaches max_batch
        res = [f.result(timeout=30) for f in futs]
    assert [r.label for r in res] == \
        result.ensemble().predict(test.x[:3]).tolist()
    stats = srv.stats()
    assert stats.completed == 3 and stats.failed == 0


def test_served_answers_match_direct_scoring(workload):
    """Whatever batches the scheduler forms, every single-image answer
    equals direct scoring — batch composition is invisible to callers."""
    result, test = workload
    ens = result.ensemble()
    expected = ens.predict(test.x)
    scorer = ens.bucketed_scorer(max_batch=8)
    with EnsembleServer(scorer, ServeConfig(max_batch=8,
                                            max_wait_ms=1.0)) as srv:
        futs = [srv.submit(img) for img in test.x]
        got = np.array([f.result(timeout=60).label for f in futs])
    assert np.array_equal(got, expected)
    stats = srv.stats()
    assert stats.completed == len(test.x)
    assert stats.failed == 0 and stats.dropped == 0
    scorer.assert_compile_budget()


def test_queue_depth_backpressure(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=4)
    srv = EnsembleServer(scorer, ServeConfig(max_batch=4, queue_depth=2))
    # worker not started: the queue fills at depth 2
    srv.submit(test.x[0])
    srv.submit(test.x[1])
    with pytest.raises(QueueFull):
        srv.submit(test.x[2])
    assert srv.stats().dropped == 1
    srv.start(warmup=False)
    srv.close()                                  # drains the 2 queued
    assert srv.stats().completed == 2


def test_close_drains_everything(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=4)
    srv = EnsembleServer(scorer, ServeConfig(max_batch=4,
                                             max_wait_ms=50.0)).start()
    futs = srv.submit_many(test.x[:11])          # 2 full + 1 partial batch
    srv.close()
    assert all(f.result(timeout=10).label >= 0 for f in futs)
    assert srv.stats().completed == 11


def test_serve_config_validation(workload):
    result, _ = workload
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(combine="product")
    with pytest.raises(ValueError):
        ServeConfig(max_wait_ms=-1)
    scorer = result.ensemble().bucketed_scorer(max_batch=4)
    with pytest.raises(ValueError):              # beyond the ladder
        EnsembleServer(scorer, ServeConfig(max_batch=8))


# ---------------------------------------------------------------------------
# Open-loop load generation
# ---------------------------------------------------------------------------

def test_open_loop_report(workload):
    result, test = workload
    scorer = result.ensemble().bucketed_scorer(max_batch=8)
    with EnsembleServer(scorer, ServeConfig(max_batch=8,
                                            max_wait_ms=2.0)) as srv:
        rep = run_open_loop(srv, test.x, rate_per_s=300, n_requests=60,
                            seed=3)
    assert rep.submitted == rep.completed == 60 and rep.failed == 0
    assert rep.p50_ms <= rep.p95_ms <= rep.p99_ms <= rep.max_ms
    assert rep.achieved_per_s > 0 and rep.duration_s > 0
    with pytest.raises(ValueError):
        run_open_loop(srv, test.x, rate_per_s=0, n_requests=1)


# ---------------------------------------------------------------------------
# latest_valid_step: tmp files + torn checkpoints (skip + retry)
# ---------------------------------------------------------------------------

def test_latest_valid_step_skips_torn_and_tmp():
    with tempfile.TemporaryDirectory() as d:
        assert ckpt.latest_valid_step(d, "round") is None
        ckpt.save_checkpoint(d, "round", 0, {"w": np.arange(3.0)})
        assert ckpt.latest_valid_step(d, "round") == 0
        # a writer dies MID-SAVE on round 1: torn final file + stray tmp
        with pytest.raises(faults.InjectedCrash):
            faults.inject_torn_save(d, "round", 1)
        # naive listing sees the torn step; the valid probe skips it
        assert ckpt.latest_step(d, "round") == 1
        assert ckpt.latest_valid_step(d, "round") == 0
        assert run_state.latest_ready_round(d) == 0
        with pytest.raises(Exception):           # the torn file is real
            np.load(os.path.join(d, "round-00000001.npz")).close()


def test_latest_valid_step_retry_sees_completed_save():
    """skip + RETRY: once a complete file replaces the wreckage, the
    very next poll returns the new step."""
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, "round", 0, {"w": np.arange(3.0)})
        faults.inject_torn_save(d, "round", 1, crash=False)
        assert ckpt.latest_valid_step(d, "round") == 0
        # the writer retries and completes (atomic replace over the torn
        # file, the same path ckpt.save_checkpoint takes)
        ckpt.save_checkpoint(d, "round", 1, {"w": np.arange(4.0)})
        assert ckpt.latest_valid_step(d, "round") == 1
        tree, _ = ckpt.restore_checkpoint(d, "round", 1)
        assert np.array_equal(tree["w"], np.arange(4.0))


def test_peek_step_reads_meta():
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, "round", 2, {"w": np.zeros(2)},
                             metadata={"round": 2})
        meta = ckpt.peek_step(d, "round", 2)
        assert meta["metadata"] == {"round": 2} and meta["step"] == 2
        assert ckpt.peek_step(d, "round", 3) is None


# ---------------------------------------------------------------------------
# Checkpoint hot-reload: zero drops, bit-equal post-swap
# ---------------------------------------------------------------------------

def _training_run():
    cfg = replace(CFG, elm_lambda=1.0)
    ds = make_extended_mnist(n_per_class=25, seed=0)
    train, test = ds.split(n_test=40)
    parts = partition_iid(train.x, train.y, 3)
    run = AveragingRun(
        cfg,
        MapConfig(epochs=2, lr_schedule=dynamic_paper(0.05), batch_size=50),
        ReduceConfig(rounds=2))
    return cfg, run, parts, test


def test_hot_reload_swaps_with_zero_drops():
    """The acceptance-criteria scenario: serve round 0 of a checkpointed
    run while the run resumes and writes round 1; the watcher swaps the
    weights mid-stream with zero failed/dropped requests, no recompile,
    and post-swap predictions BIT-EQUAL to scoring the new checkpoint
    directly."""
    cfg, run, parts, test = _training_run()
    key = jax.random.PRNGKey(0)
    with tempfile.TemporaryDirectory() as d:
        assert faults.run_to_crash(run, parts, key, d, unit="round",
                                   index=0)
        scorer = BucketedScorer(cfg, run_state.restore_round(d, 0).members,
                                max_batch=8)
        srv = EnsembleServer(scorer, ServeConfig(max_batch=8,
                                                 max_wait_ms=2.0)).start()
        watcher = CheckpointWatcher(d, srv, poll_ms=10, start_round=0).start()

        stop = threading.Event()
        futs = []

        def traffic():
            i = 0
            while not stop.is_set():
                futs.append(srv.submit(test.x[i % len(test.x)]))
                i += 1
                time.sleep(0.002)

        th = threading.Thread(target=traffic)
        th.start()
        run.resume(parts, key, d)                # writes round 1 (final)
        assert watcher.wait_for_round(1, timeout_s=30)
        time.sleep(0.05)
        stop.set()
        th.join()

        # drain the traffic, then one probe in flight at a time: each is a
        # batch of its own at the 1-row bucket. The comparison must score
        # at the served shape — XLA's CPU GEMM rounds a 1-row bucket
        # differently from an 8-row one.
        for f in futs:
            f.result(timeout=30)
        probe = test.x[:7]
        post = np.stack([srv.submit(img).result(timeout=30).member_scores
                         for img in probe], axis=1)
        srv.close()
        watcher.stop()
        fresh = BucketedScorer(cfg, run_state.restore_round(d, 1).members,
                               max_batch=8)
        direct = np.concatenate([fresh.score_block(img[None])
                                 for img in probe], axis=1)
        assert np.array_equal(post, direct)      # bit-equal, not allclose
        assert all(f.exception(timeout=10) is None for f in futs)
        stats = srv.stats()
        assert stats.failed == 0 and stats.dropped == 0
        assert stats.swaps == 1 and watcher.rejected == []
        scorer.assert_compile_budget()


def test_watcher_skips_torn_checkpoint_then_swaps():
    """A torn round-<r>.npz in the polled dir must not crash or swap the
    endpoint; the complete save that follows must."""
    cfg, run, parts, test = _training_run()
    key = jax.random.PRNGKey(0)
    with tempfile.TemporaryDirectory() as d:
        assert faults.run_to_crash(run, parts, key, d, unit="round",
                                   index=0)
        scorer = BucketedScorer(cfg, run_state.restore_round(d, 0).members,
                                max_batch=4)
        srv = EnsembleServer(scorer, ServeConfig(max_batch=4,
                                                 max_wait_ms=1.0)).start()
        watcher = CheckpointWatcher(d, srv, poll_ms=5, start_round=0)
        faults.inject_torn_save(d, "round", 1, crash=False)
        assert watcher.poll_once() is None       # torn: skipped, no swap
        assert watcher.current_round == 0
        assert srv.submit(test.x[0]).result(10).label >= 0
        run.resume(parts, key, d)                # overwrites the torn file
        assert watcher.poll_once() == 1
        assert watcher.current_round == 1
        srv.close()
        assert srv.stats().failed == 0


def test_ensemble_bucketed_scorer_entry(workload):
    """`runner.Ensemble.bucketed_scorer` is the serving entry: wired to
    the ensemble's cfg/members, pre-jittable, ladder-capped."""
    result, test = workload
    ens = result.ensemble()
    scorer = ens.bucketed_scorer(max_batch=16)
    assert scorer.k == ens.k and scorer.cfg is ens.cfg
    assert scorer.ladder.max_batch == 16
    s = scorer.score_block(test.x[:2])
    np.testing.assert_allclose(s, ens.member_scores(test.x[:2]),
                               rtol=1e-5, atol=1e-6)
