"""A copy of the benchmark at a size the CPU runs in seconds, for the
harness's own tests: the same files with the data, batches and load cut
down, written under a temporary checkout root."""
from __future__ import annotations

import json
import os
import shutil

from chipbench.harness import BENCH_REL, ROOT

TINY_DATA = {"cnn_elm_6c12c": 5, "cnn_elm_3c9c": 10}


def _edit(path: str, fn):
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f, indent=1)


def tiny_root(tmp: str) -> str:
    """A checkout root under ``tmp`` with ``BENCHMARK.json`` and a tiny
    copy of the benchmark's files; the CPU gets a row in the peaks table
    so that a traced run can read its metrics."""
    src, dst = os.path.join(ROOT, BENCH_REL), os.path.join(tmp, BENCH_REL)
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"),
                os.path.join(tmp, "BENCHMARK.json"))
    for name, n in TINY_DATA.items():
        _edit(os.path.join(dst, "configs", name + ".json"),
              lambda d: d["data"].update(n_per_class=n))

    def shrink(d):
        d["batch"] = 10
        if d["driver"] == "open_loop":
            d.update(rate_per_s=100.0, held_out=32, warmup_requests=8,
                     check_sample=8)

    for f in os.listdir(os.path.join(dst, "traffic")):
        _edit(os.path.join(dst, "traffic", f), shrink)
    _edit(os.path.join(dst, "peaks.json"), lambda d: d["devices"].update(
        cpu={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}))
    return tmp


def run_tiny(root: str, workload: str, *, seed: int = 2 ** 31 + 7,
             seconds: float = 0.2, trace: int = 0):
    """One run of a tiny cell on the CPU; returns (result, stdout
    lines, stderr lines)."""
    import io
    from chipbench.harness import run
    out, err = io.StringIO(), io.StringIO()
    result = run(["--workload", workload, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)], root=root,
                 chip=False, out=out, err=err)
    return result, out.getvalue().splitlines(), err.getvalue().splitlines()
