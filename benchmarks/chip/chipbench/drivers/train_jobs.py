"""Training traffic: whole Map+Reduce jobs back to back, one process, one
job at a time, as a user who trains the paper's k members and averages
them.

Parameters (``traffic/<mix>.json``): ``members`` k, ``partition``
(``iid`` or ``contiguous``), ``batch``, ``epochs`` (0: the ELM-only
pass), ``rounds``, ``lr`` (the paper's c of α = c/e), ``backend``
(``stacked`` or ``mesh``).

Set-up makes the data, the partitions and one warm-up job (job 0), which
compiles or loads every program the window runs. The window runs jobs
1, 2, ... while its time lasts; ``train_images_per_s`` is the images x
epochs of the completed jobs over the time from the window's start to
the end of the last one. The check replays one window job drawn from the
seed on the plain reference and compares the members and the averaged
model.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import compare, reference, work
from chipbench.drivers.common import Jobs, job_spec, partitions, to_host
from chipbench.harness import job_seeds


def setup(env):
    t0 = time.monotonic()
    parts = partitions(env)
    t1 = time.monotonic()
    jobs = Jobs(env, parts)
    jobs.run(0)
    env.log(f"setup data_s={t1 - t0} warmup_job_s={time.monotonic() - t1}")
    return {"parts": parts, "jobs": jobs, "done": []}


def job_work(env, parts) -> dict:
    """Operations, bytes and images of one job (``chipbench.work``)."""
    s = job_spec(env)
    nb = max(len(x) for x, _ in parts) // s["batch"]
    return work.job(env.model(), len(parts), nb, s["batch"], s["epochs"])


def window(state, env):
    done = state["done"]
    t0 = time.monotonic()
    j = 1
    while time.monotonic() - t0 < env.seconds:
        with jax.profiler.TraceAnnotation("bench.job"):
            out = state["jobs"].run(j)
        done.append((j, time.monotonic() - t0, out))
        j += 1
    span = done[-1][1]
    per = job_work(env, state["parts"])
    total = {k: v * len(done) for k, v in per.items()}
    walls = np.diff([0.0] + [t for _, t, _ in done])
    return {"e2e": {"train_images_per_s": total["images"] / span},
            "counters": {"jobs": len(done), "work": total, "span_s": span},
            "attempted": len(done), "failed": 0,
            "lines": [f"window jobs={len(done)} span_s={span} "
                      f"job_wall_s={[float(w) for w in walls]}"]}


def check(state, env):
    done = state["done"]
    pick = int(np.random.default_rng(env.seed).integers(len(done)))
    j, _, out = done[pick]
    got = to_host(out)
    state["done"] = []
    init_seed, shuffle_seed = job_seeds(env.seed, j)
    s = job_spec(env)
    t0 = time.monotonic()
    ref = reference.run_job(
        env.model(), state["parts"], init_seed=init_seed,
        shuffle_seed=shuffle_seed, epochs=s["epochs"], rounds=s["rounds"],
        lr=s["lr"], batch=s["batch"])
    numbers = {k: f(got, ref) for k, f in compare.NUMBERS.items()}
    env.log(f"check job={j} reference_s={time.monotonic() - t0} "
            + " ".join(f"{k}={v}" for k, v in numbers.items()))
    return numbers


def close(state):
    state.clear()
