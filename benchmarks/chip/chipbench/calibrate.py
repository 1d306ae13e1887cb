"""The readings a cell's limits are set from, apart from the program's own
(every run prints those): the lower-precision control and the planted
faults, each put in the program's place and compared with the reference
exactly as a run compares the program.

* ``control``: the reference computed at ``high`` (three bf16 passes), the
  precision below the configuration's f32 at ``highest``;
* ``half_batch``: the reference with the second half of every batch left
  out and the mean taken over the rest;
* ``unchanged`` (training): the init handed back as the trained members,
  β zero; reads 1 on β;
* ``answer_swapped`` (serving): every sampled request answered with the
  scores, and the label, of another request.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from chipbench import compare, reference
from chipbench.drivers.common import job_spec, partitions
from chipbench.harness import job_seeds

TRAIN_VARIANTS = ("control", "half_batch", "unchanged")
SERVE_VARIANTS = ("control", "answer_swapped")


def _job(env, parts, seed: int, **kw):
    s = job_spec(env)
    init_seed, shuffle_seed = job_seeds(seed, 1)
    return reference.run_job(env.model(), parts, init_seed=init_seed,
                             shuffle_seed=shuffle_seed, epochs=s["epochs"],
                             rounds=s["rounds"], lr=s["lr"], batch=s["batch"],
                             **kw)


def train_readings(env, parts, seed: int, variants) -> Dict[str, dict]:
    ref = _job(env, parts, seed)
    out = {}
    for v in variants:
        if v == "unchanged":
            m = ref["members"]
            k = m["beta"].shape[0]
            got = {"members": {"cnn": {"stages": tuple(
                {n: np.broadcast_to(a, (k,) + a.shape) for n, a in st.items()}
                for st in ref["init"]["stages"])},
                "beta": np.zeros_like(m["beta"])},
                "averaged": {"cnn": ref["init"],
                             "beta": np.zeros_like(m["beta"][0])}}
        else:
            got = _job(env, parts, seed, precision="high"
                       if v == "control" else "highest",
                       half_batch=v == "half_batch")
        out[v] = {k: f(got, ref) for k, f in compare.NUMBERS.items()}
    return out


def serve_readings(env, parts, seed: int, variants) -> Dict[str, dict]:
    from chipbench import data
    t, d = env.traffic, env.config["data"]
    x, _ = data.held_out(d["generator"], t["held_out"], d["seed"] + 1,
                         env.model()["num_classes"], env.data_cache())
    rng = np.random.default_rng(seed)
    x = x[rng.integers(0, len(x), t["check_sample"])]
    pool = env.model()["cnn_pool"]

    def scores(precision):
        m = _job(env, parts, seed, precision=precision)["members"]
        return np.asarray(reference.member_scores(
            m["cnn"], m["beta"], x, pool=pool, precision=precision))

    want = scores("highest")
    out = {}
    for v in variants:
        got = (scores("high") if v == "control"
               else np.roll(want, 1, axis=1))
        out[v] = {"score_gap": compare.score_gap(got, want),
                  "label_gap": compare.label_gap(got.mean(0).argmax(-1),
                                                 want.mean(0))}
    return out


def readings(env, seeds: List[int], variants=None, log=print):
    """{seed: {variant: {number: value, "correct": verdict}}} for a
    training or serving cell; the verdict is ``compare.judge``'s under the
    cell's limits, as a run would give it."""
    parts = partitions(env)
    serving = env.traffic["driver"] == "open_loop"
    fn = serve_readings if serving else train_readings
    variants = variants or (SERVE_VARIANTS if serving else TRAIN_VARIANTS)
    out = {}
    for seed in seeds:
        t0 = time.monotonic()
        out[seed] = fn(env, parts, seed, variants)
        for numbers in out[seed].values():
            numbers["correct"] = compare.judge(numbers, env.limits)[0]
        log(f"seed={seed} s={time.monotonic() - t0} {out[seed]}")
    return out
