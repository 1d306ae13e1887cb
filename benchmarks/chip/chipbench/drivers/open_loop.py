"""Serving traffic: single-image requests from independent users, sent on
their own clock (an open loop) to the program's ``EnsembleServer``.

Parameters (``traffic/<mix>.json``): the ELM-only job that makes the
members (``members``, ``partition``, ``batch``, ``epochs`` 0, ``lr``,
``backend``), the endpoint (``max_batch``, ``max_wait_ms``, ``combine``),
the load (``rate_per_s``; ``held_out`` images the requests cycle through;
``warmup_requests``) and the check (``check_sample`` requests).

Arrivals: the window holds round(rate x seconds) requests. Their gaps are
the quantiles of the exponential distribution of that rate, scaled to
fill the window, in an order drawn from the seed: every seed offers the
same set of gaps, so a seed changes the order and not the load. Latency
is completion time minus due time, so a generator that runs late shows
in the latency; how late it ran is printed on its own line. A request
that fails or never completes counts as missing (an infinite latency).

This copies the open loop of ``repro.serve.loadgen.run_open_loop``, which
times a request from its submission instead of its due time.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from chipbench import compare, data, reference
from chipbench.drivers.common import Jobs, job_spec, partitions
from chipbench.harness import job_seeds

DRAIN_S = 60.0


class TimedScorer:
    """A thin proxy around the scorer the benchmark builds: host wall time
    of every ``score_block`` call (the traced run only)."""

    def __init__(self, scorer):
        self._scorer = scorer
        self.calls_s = []

    def __getattr__(self, name):
        return getattr(self._scorer, name)

    def score_block(self, x):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.score_block"):
            out = self._scorer.score_block(x)
        self.calls_s.append(time.perf_counter() - t0)
        return out


def schedule(rate: float, seconds: float, seed: int, n_images: int):
    """(offsets from the window's start, image index) of every request."""
    n = max(int(round(rate * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps[0], rng.integers(0, n_images, n)


def setup(env):
    from repro.serve import EnsembleServer, ServeConfig
    t = env.traffic
    if t["combine"] != "mean":
        raise ValueError("the check combines members by their mean score")
    t0 = time.monotonic()
    parts = partitions(env)
    d = env.config["data"]
    x_req, _ = data.held_out(d["generator"], t["held_out"], d["seed"] + 1,
                             env.model()["num_classes"], env.data_cache())
    t1 = time.monotonic()
    jobs = Jobs(env, parts)
    out = jobs.run(0)
    t2 = time.monotonic()
    from repro.core.cnn_elm import StackedMembers
    from repro.core.runner import Ensemble
    ens = Ensemble(jobs.cfg, StackedMembers(out["members"]["cnn"],
                                            out["members"]["beta"]),
                   combine=t["combine"])
    scorer = ens.bucketed_scorer(max_batch=t["max_batch"])
    if env.trace:
        scorer = TimedScorer(scorer)
    server = EnsembleServer(scorer, ServeConfig(
        max_batch=t["max_batch"], max_wait_ms=t["max_wait_ms"],
        combine=t["combine"])).start()
    state = {"parts": parts, "x": x_req, "server": server,
             "scorer": scorer}
    warm = t["warmup_requests"]
    offsets, idx = schedule(t["rate_per_s"], warm / t["rate_per_s"],
                            env.seed, len(x_req))
    _drive(server, x_req, offsets, idx)
    env.log(f"setup data_s={t1 - t0} members_job_s={t2 - t1} "
            f"server_and_burst_s={time.monotonic() - t2}")
    return state


def _drive(server, images, offsets, idx):
    """Submit request i at its due time; gather every answer."""
    t0 = time.monotonic() + 0.01
    due = t0 + offsets
    sent = np.zeros(len(due))
    futures = []
    for i in range(len(due)):
        wait = due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.monotonic()
        futures.append(server.submit(images[idx[i]]))
    close = time.monotonic()
    answers = []
    for f in futures:
        try:
            answers.append(f.result(timeout=max(
                DRAIN_S - (time.monotonic() - close), 0.001)))
        except Exception:
            answers.append(None)
    return t0, due, sent, answers, close


class GCPauses:
    """Python's garbage collections while active: count and longest, in
    ms (they stop the generator and the scheduler thread alike)."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                1e3 * (time.perf_counter() - self._t)))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def line(self) -> str:
        gen2 = [ms for g, ms in self.pauses if g == 2]
        return (f"gc collections={len(self.pauses)} gen2={len(gen2)} "
                f"max_ms={max((ms for _, ms in self.pauses), default=0.0)}")


def window(state, env):
    t = env.traffic
    server = state["server"]
    before = server.stats()
    if isinstance(state["scorer"], TimedScorer):
        state["scorer"].calls_s.clear()
    offsets, idx = schedule(t["rate_per_s"], env.seconds, env.seed,
                            len(state["x"]))
    with GCPauses() as pauses:
        t0, due, sent, answers, close = _drive(server, state["x"], offsets,
                                               idx)
    after = server.stats()
    lat = np.array([np.inf if a is None else s + a.latency_s - d
                    for a, s, d in zip(answers, sent, due)])
    late = (sent - due) * 1e3
    missing = int(np.sum(~np.isfinite(lat)))
    p95 = float(np.sort(lat)[int(np.ceil(0.95 * len(lat))) - 1] * 1e3)
    batches = after.batches - before.batches
    counters = {"occupancy": (after.completed - before.completed)
                / max(batches, 1)}
    if isinstance(state["scorer"], TimedScorer):
        calls = state["scorer"].calls_s
        counters["score_ms"] = 1e3 * float(np.mean(calls)) if calls \
            else None
    state.update(idx=idx, answers=answers)
    fin = lat[np.isfinite(lat)] * 1e3
    fifth = max(len(lat) // 5, 1)
    ends = [float(np.percentile(lat[sl], 95) * 1e3)
            for sl in (slice(0, fifth), slice(-fifth, None))]
    lines = [
        f"generator requests={len(due)} late_ms_p50="
        f"{float(np.percentile(late, 50))} late_ms_p99="
        f"{float(np.percentile(late, 99))} late_ms_max={float(late.max())}",
        f"served completed={len(fin)} missing={missing} batches={batches} "
        f"occupancy={counters['occupancy']} "
        f"p50_ms={float(np.percentile(fin, 50)) if len(fin) else None} "
        f"p95_ms={p95} p99_ms="
        f"{float(np.percentile(fin, 99)) if len(fin) else None} "
        f"achieved_per_s={len(fin) / (close - t0)} offered_per_s="
        f"{len(due) / env.seconds} compiles={after.compile_count} "
        f"p95_first_fifth_ms={ends[0]} p95_last_fifth_ms={ends[1]}",
        pauses.line()]
    return {"e2e": {"serve_p95_ms": p95}, "counters": counters,
            "attempted": len(due), "failed": missing, "lines": lines}


def check(state, env):
    """Replay the members on the reference (the same ELM-only job), score
    a sample of the answered requests, drawn from the seed, and hold the
    served member scores and combined labels to the reference's."""
    t, s = env.traffic, job_spec(env)
    state["server"].close()
    answers, idx = state["answers"], state["idx"]
    n = min(t["check_sample"], len(answers))
    # repro: allow(hardcoded-member-seed) the check's sample stream, no member's
    pick = np.random.default_rng(env.seed + 1).choice(len(answers), n,
                                                     replace=False)
    pick = [i for i in pick if answers[i] is not None]
    got = np.stack([answers[i].member_scores for i in pick], axis=1)
    labels = np.array([answers[i].label for i in pick])
    init_seed, shuffle_seed = job_seeds(env.seed, 0)
    t0 = time.monotonic()
    ref = reference.run_job(
        env.model(), state["parts"], init_seed=init_seed,
        shuffle_seed=shuffle_seed, epochs=0, rounds=1, lr=s["lr"],
        batch=s["batch"])
    m = ref["members"]
    want = np.asarray(reference.member_scores(
        m["cnn"], m["beta"], state["x"][idx[pick]],
        pool=env.model()["cnn_pool"], precision="highest"))
    agree = float(np.mean(labels == want.mean(0).argmax(-1)))
    numbers = {"score_gap": compare.score_gap(got, want),
               "label_gap": compare.label_gap(labels, want.mean(0))}
    env.log(f"check sample={len(pick)} reference_s="
            f"{time.monotonic() - t0} label_agreement={agree} "
            + " ".join(f"{k}={v}" for k, v in numbers.items()))
    return numbers


def close(state):
    server = state.get("server")
    if server is not None:
        server.close()
    state.clear()
