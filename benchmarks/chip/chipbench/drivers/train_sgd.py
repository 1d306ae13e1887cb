"""SGD training traffic: the Map+Reduce jobs of ``train_jobs`` (its
set-up and window), with members that take the paper's SGD steps, checked
step by step.

Parameters (``traffic/<mix>.json``): those of ``train_jobs``, with
``epochs`` 1 and ``rounds`` 1: the check replays the job's one epoch.

Each job hands back, besides its members and averaged model, the
program's step record (``RunResult.step_record``): every member's params
at the start of every step. The check replays the picked window job on
the plain reference (``chipbench.reference_steps``) from those recorded
params, one step at a time, so that an error in one step cannot grow over
the next hundreds; the numbers are ``reference_steps.numbers``'. A
program that keeps no record cannot be checked: set-up stops before the
data and the warm-up job, with no result.

The window also counts the work of the gradient (``chipbench.sgd_work``)
for ``sgd_update_roofline.sgd``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import numpy as np
from repro.core import runner

from chipbench import compare, reference_steps, sgd_work
from chipbench.drivers import train_jobs
from chipbench.drivers.common import Jobs, job_spec, partitions, to_host
from chipbench.harness import SetupError, job_seeds

VARIANTS = ("control", "half_batch", "stale_beta", "unchanged",
            "no_reduce")
SHOWN = ("step_err_p50", "step_err_p99", "worst_step")


def keeps_record() -> bool:
    """Whether the program hands back a step record with a job."""
    return "step_record" in {f.name for f in
                             dataclasses.fields(runner.RunResult)}


class RecordedJobs(Jobs):
    """``Jobs`` whose answer also holds the step record."""

    def run(self, j: int):
        init_seed, shuffle_seed = job_seeds(self.env.seed, j)
        res = runner.AveragingRun(
            self.cfg, runner.MapConfig(seed=shuffle_seed, **self._map),
            self._reduce).run(self.parts, jax.random.PRNGKey(init_seed))
        if res.step_record is None:
            raise RuntimeError("the job took SGD steps and kept no record")
        out = {"members": {"cnn": res.stacked.cnn_params,
                           "beta": res.stacked.beta},
               "averaged": {"cnn": res.averaged.cnn_params,
                            "beta": res.averaged.beta},
               "record": res.step_record.params,
               "mask": res.step_record.mask}
        jax.block_until_ready(out)
        return out


def setup(env):
    s = job_spec(env)
    if s["epochs"] != 1 or s["rounds"] != 1:
        raise SetupError("bench: train_sgd replays one epoch: epochs 1, "
                         "rounds 1")
    if not keeps_record():
        raise SetupError("bench: the program keeps no step record "
                         "(RunResult.step_record); the SGD check replays "
                         "it")
    t0 = time.monotonic()
    parts = partitions(env)
    t1 = time.monotonic()
    jobs = RecordedJobs(env, parts)
    jobs.run(0)
    env.log(f"setup data_s={t1 - t0} warmup_job_s={time.monotonic() - t1}")
    return {"parts": parts, "jobs": jobs, "done": []}


def window(state, env):
    measured = train_jobs.window(state, env)
    s = job_spec(env)
    k, nb = len(state["parts"]), max(
        len(x) for x, _ in state["parts"]) // s["batch"]
    per = sgd_work.grad(env.model(), s["batch"])
    jobs = measured["counters"]["jobs"]
    measured["counters"]["work"].update(
        {f"sgd_update_{n}": v * k * nb * jobs for n, v in per.items()})
    return measured


def _numbers(env, parts, got, seed_job) -> Dict[str, float]:
    init_seed, shuffle_seed = seed_job
    s = job_spec(env)
    return reference_steps.numbers(
        env.model(), parts, got, init_seed=init_seed,
        shuffle_seed=shuffle_seed, lr=s["lr"], batch=s["batch"])


def check(state, env):
    done = state["done"]
    pick = int(np.random.default_rng(env.seed).integers(len(done)))
    j, _, out = done[pick]
    got = to_host(out)
    state["done"] = []
    t0 = time.monotonic()
    numbers = _numbers(env, state["parts"], got, job_seeds(env.seed, j))
    env.log(f"check job={j} reference_s={time.monotonic() - t0} "
            + " ".join(f"{k}={v}" for k, v in numbers.items()))
    return {k: v for k, v in numbers.items() if k not in SHOWN}


def close(state):
    state.clear()


def _variant(env, parts, seeds, name: str) -> dict:
    """A job's answer with the reference, or a fault, in the program's
    place: ``control`` (the reference at ``high``), ``half_batch``,
    ``stale_beta``; ``unchanged`` (the init handed back at every step and
    as the members, β zero); ``no_reduce`` (a sound job whose averaged
    model is its first member)."""
    s = job_spec(env)
    init_seed, shuffle_seed = seeds
    kw = dict(init_seed=init_seed, shuffle_seed=shuffle_seed, lr=s["lr"],
              batch=s["batch"])
    if name in ("control", "half_batch", "stale_beta"):
        return reference_steps.free_job(
            env.model(), parts, precision="high" if name == "control"
            else "highest", half_batch=name == "half_batch",
            stale_beta=name == "stale_beta", **kw)
    got = reference_steps.free_job(env.model(), parts, **kw)
    if name == "no_reduce":
        got["averaged"] = jax.tree.map(lambda a: a[0], got["members"])
    elif name == "unchanged":
        p0 = reference_steps.init(env.model(), init_seed)
        rec, k = got["record"], len(parts)
        got["record"] = jax.tree.map(
            lambda a, r: np.broadcast_to(a, r.shape), p0, rec)
        got["members"] = {"cnn": jax.tree.map(
            lambda a: np.broadcast_to(a, (k,) + a.shape), p0),
            "beta": np.zeros_like(got["members"]["beta"])}
        got["averaged"] = {"cnn": p0, "beta": got["members"]["beta"][0]}
    else:
        raise ValueError(f"unknown variant {name!r}")
    return got


def readings(env, seeds: List[int], variants=VARIANTS, log=print):
    """{seed: {variant: {number: value, "correct": verdict}}}: the control
    and the planted faults in the program's place, each checked as a run
    checks the program, with ``compare.judge``'s verdict under the cell's
    limits."""
    parts = partitions(env)
    out = {}
    for seed in seeds:
        t0 = time.monotonic()
        job = job_seeds(seed, 1)
        out[seed] = {}
        for v in variants:
            numbers = _numbers(env, parts, _variant(env, parts, job, v), job)
            numbers["correct"] = compare.judge(numbers, env.limits)[0]
            out[seed][v] = numbers
        log(f"seed={seed} s={time.monotonic() - t0} {out[seed]}")
    return out
