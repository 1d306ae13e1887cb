"""One run of one cell: load, warm up, measure for ``--seconds``, check
the answers against the plain reference, print the result.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json`` (the checkout's root) names the cell's configuration
  and traffic mix, and the metrics it reports;
* ``configs/<config>.json``: the model and its data;
* ``traffic/<traffic>.json``: the driver (``chipbench/drivers/<driver>.py``)
  and its parameters;
* ``limits/<cell>.json``: the limit of every number the check compares;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``
  returning a number or None.

A run prints, in order: the device, the compile cache, the window's
counts (compilations inside it, how late a generator ran), then each
compared number beside its limit on standard error, and as its last line
on standard output one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_REL = os.path.join("benchmarks", "chip")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
TRACE_WINDOW = "bench.window"


class SetupError(SystemExit):
    """The run cannot start: no chip, too few chips, an overridden kernel
    policy, or a cell that is not declared. Exits non-zero, prints no
    result."""


@dataclass
class Env:
    """What a driver gets: the cell's files, the run's arguments, and the
    program's configuration object."""
    root: str
    name: str
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    log: Callable[..., None] = print

    def model(self) -> dict:
        return self.config["model"]

    def data_cache(self) -> str:
        """Where ``chipbench.data`` keeps the sets it renders."""
        return os.path.join(self.root, BENCH_REL, ".cache", "data")


def job_seeds(seed: int, job: int):
    """(init seed, shuffle seed) of job ``job`` of a run: the init key and
    the members' batch-order streams, both from ``--seed``."""
    w = np.random.SeedSequence([int(seed), int(job)]).generate_state(2)
    return int(w[0] >> 1), int(w[1] >> 2)


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, *parts) -> dict:
    path = os.path.join(root, BENCH_REL, *parts)
    if not os.path.exists(path):
        raise SetupError(f"bench: no file {path}")
    with open(path) as f:
        return json.load(f)


def load_env(root: str, name: str, seed: int, seconds: float, trace: bool,
             log=print) -> Env:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    return Env(root, name, cell,
               _json(root, "configs", cell["config"] + ".json"),
               _json(root, "traffic", cell["traffic"] + ".json"),
               _json(root, "limits", name + ".json")["limits"], seed,
               seconds, trace, log)


def cell_metrics(spec: dict, name: str, trace: bool) -> List[dict]:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics
    with ``--trace 0``, the per-layer metrics that read it with ``1``."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def require_chip(chips: int):
    """No fallback: the TPU backend, enough chips, compiled kernels."""
    import jax
    from repro.kernels import resolve_interpret, resolve_use_pallas
    if jax.default_backend() != "tpu":
        raise SetupError(f"bench: no TPU (JAX backend is "
                         f"{jax.default_backend()!r})")
    if len(jax.devices()) < chips:
        raise SetupError(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(jax.devices())}")
    if not resolve_use_pallas(None) or resolve_interpret(None):
        raise SetupError("bench: the kernel policy is overridden "
                         "(REPRO_USE_PALLAS / REPRO_PALLAS_INTERPRET); the "
                         "benchmark runs the compiled kernels only")


def prepare(env: Env) -> str:
    """The persistent compile cache (every program, however quick to
    compile) and the configuration's matmul precision: float32 products
    in full in the program's XLA dots, as in its kernels. Returns the
    cache directory."""
    import jax
    from repro.launch.cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      env.config["matmul_precision"])
    return path


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.compiles = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if not self.active:
            return
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1


def settle():
    """Collect what set-up made and move it out of the collector's sight
    (``gc.freeze``): a collection in the window then scans only what the
    window made, instead of stopping every thread for tens of ms to walk
    the data, JAX's caches and the warm-up's objects."""
    gc.collect()
    gc.freeze()


def load_reader(root: str, name: str):
    path = os.path.join(root, BENCH_REL, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class ReadContext:
    """What a per-layer metric reader sees."""
    trace: object               # chipbench.trace.Summary or None
    counters: dict
    peak: dict
    chips: int


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def trace_dir(root: str) -> str:
    return os.path.join(root, BENCH_REL, ".cache", "trace")


def _trace_start(root: str):
    import jax
    shutil.rmtree(trace_dir(root), ignore_errors=True)
    os.makedirs(trace_dir(root), exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir(root), profiler_options=opts)


def _trace_read(root: str):
    from chipbench import trace as tr
    paths = []
    for d, _, files in os.walk(trace_dir(root)):
        paths += [os.path.join(d, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir(root)}")
    events = tr.load(max(paths, key=os.path.getmtime))
    return tr.summarize(events, tr.window_of(events, TRACE_WINDOW))


def run(argv=None, *, root: Optional[str] = None, chip: bool = True,
        t_start: Optional[float] = None, out=None, err=None) -> dict:
    """One run; returns the result object it printed. ``chip=False``
    skips the look for a chip (the harness's own tests, on the CPU)."""
    t_start = time.monotonic() if t_start is None else t_start
    out, err = out or sys.stdout, err or sys.stderr
    ap = argparse.ArgumentParser(prog="benchmarks/chip/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or ROOT
    log = lambda *a: print(*a, file=out, flush=True)
    env = load_env(root, args.workload, args.seed, args.seconds,
                   bool(args.trace), log)
    spec = load_spec(root)
    metrics = cell_metrics(spec, args.workload, env.trace)
    chips = int(env.cell["chips"])

    import jax
    if chip:
        require_chip(chips)
    cache = prepare(env)
    devices = jax.devices()[:chips]
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} compile_cache={cache}")

    driver = importlib.import_module(
        "chipbench.drivers." + env.traffic["driver"])
    counter = CompileCounter()
    state = driver.setup(env)
    settle()
    setup_s = time.monotonic() - t_start
    log(f"setup_s={setup_s}")

    if env.trace:
        _trace_start(root)
    counter.active = True
    with jax.profiler.TraceAnnotation(TRACE_WINDOW):
        measured = driver.window(state, env)
    counter.active = False
    summary = None
    if env.trace:
        jax.profiler.stop_trace()
        summary = _trace_read(root)
    log(f"window compiles={counter.compiles} traces={counter.traces}")
    for line in measured.get("lines", []):
        log(line)
    mem = memory_peak(devices)

    values = dict(measured["e2e"], setup_s=setup_s)
    result_metrics = {}
    if env.trace:
        from chipbench.work import peaks
        ctx = ReadContext(summary, measured["counters"], peaks(
            dev.device_kind, os.path.join(root, BENCH_REL, "peaks.json")),
            chips)
        log("trace busy_s=" + json.dumps(summary.busy_s)
            + f" window_s={summary.window_s}")
        for m in metrics:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
    else:
        for m in metrics:
            v = float(values[m["name"]])
            result_metrics[m["name"]] = {
                "value": v if np.isfinite(v) else None, "unit": m["unit"]}

    numbers = driver.check(state, env)
    driver.close(state)
    from chipbench.compare import judge
    ok, shown = judge(numbers, env.limits)
    ok = ok and measured["failed"] == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"]), "metrics": result_metrics,
              "device": device}
    if env.trace:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["compared"] = shown
    for k, v in shown.items():
        print(f"compared {k}={v['value']} limit={v['limit']}", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(t_start: float):
    try:
        run(t_start=t_start)
    except SetupError as e:
        print(e, file=sys.stderr)
        sys.exit(2)
