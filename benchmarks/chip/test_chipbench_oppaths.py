"""The op-name paths and host spans of a trace (``chipbench.oppaths``):
the wire-format decoder on traces recorded on a TPU v5e chip, the
interval arithmetic on hand-built events, and the four readers that use
them, which read nothing where the program names no layer or the trace
has no TPU plane."""
import gzip
import os
import shutil

import jax
import pytest

from chipbench import harness, oppaths
from chipbench import trace as tr
from chipbench.testing import tiny_root
from chipbench.work import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "testdata", "train_tiny.xplane.pb.gz")
NEW = ["conv2d_roofline.train", "epoch_build_idle_share.train",
       "serve_queue_wait_ms", "serve_score_host_ms"]
DEV0, HOST = "/device:TPU:0", "/host:CPU"


def op(start, dur, path, name="%fusion.1 = f32[] fusion()", plane=DEV0):
    return tr.Event(plane, "XLA Ops", name, path, float(start), float(dur))


def span(name, start, dur, **args):
    return oppaths.Span(tr.Event(HOST, "python3", name, "", float(start),
                                 float(dur)), args)


def test_decoder_on_recorded_chip_trace():
    """Every op of the older recording has its path but the copies the
    compiler inserted (and the one ``while`` that holds the scan)."""
    t = oppaths.load(OLD)
    ops = t.ops[0]
    assert len(ops) == 853 and t.ambiguous == {}
    bare = {tr.instruction(e) for e in ops if not e.path}
    assert bare == {"copy", "copy-start", "copy-done",
                    "copy_bitcast_fusion", "while"}
    assert sum(1 for e in ops if e.path) == 747
    stats = [e for e in ops if tr.instruction(e) == "_elm_stats"]
    assert stats and all("/jit(_elm_stats)/pallas_call" in e.path
                         for e in stats)
    # the same events as trace.load's device ops
    assert [(e.name, e.start_ns) for e in ops] == [
        (e.name, e.start_ns) for e in tr.device_ops(tr.load(OLD))[0]]


def test_under_a_scope():
    assert oppaths.under(op(0, 1, "jit(f)/while/body/conv2d/dot:"), "conv2d")
    assert oppaths.under(op(0, 1, "jit(f)/vmap(beta_solve)/cholesky:"),
                         "beta_solve")
    assert oppaths.under(op(0, 1, "jit(f)/vmap(transpose(jvp(sgd_update)))"
                                  "/conv2d/jit(_blocked_matmul)/pallas_call"),
                         "sgd_update")
    # a function's name is not its scope, nor is the op itself
    assert not oppaths.under(op(0, 1, "jit(f)/jit(_conv2d_valid)/dot:"),
                             "conv2d")
    assert not oppaths.under(op(0, 1, "jit(f)/reduce"), "reduce")
    assert not oppaths.under(op(0, 1, ""), "conv2d")


def test_interval_arithmetic():
    t = oppaths.Trace(
        {0: [op(100, 100, "jit(f)/conv2d/dot:"),
             op(150, 100, "jit(f)/elm_stats/pallas_call:"),
             op(600, 300, "jit(f)/conv2d/concatenate:"),
             op(0, 1000, "jit(f)/while:", name="%while.1 = () while()")],
         1: [op(0, 200, "jit(f)/conv2d/dot:", plane="/device:TPU:1")]},
        {}, [span("repro.map.epoch_build", 200, 500),
             span("repro.map.epoch_build", 300, 100),
             span("repro.serve.flush", -5, 1, n=1),
             span("repro.serve.flush", 10, 1, n=2, wait_us=3.0)])
    window = (0.0, 800.0)
    # the while is busy time, not conv2d time; 600-900 clips to 600-800
    assert t.scope_s("conv2d", window) == {0: 300 / 1e9, 1: 200 / 1e9}
    assert t.cover(window)[0] == [(0.0, 800.0)]
    assert [s.args for s in t.named("repro.serve.flush", window)] == [
        dict(n=2, wait_us=3.0)]
    builds = t.named("repro.map.epoch_build", window)
    no_while = oppaths.Trace({0: t.ops[0][:3]}, {}, t.spans)
    # chip 0 idles 250-600 inside the builds' union 200-700
    assert no_while.idle_inside(builds, window) == 350 / 1e9
    # two chips: chip 1 idles 200-700, the mean is taken
    two = oppaths.Trace({0: t.ops[0][:3], 1: t.ops[1]}, {}, t.spans)
    assert two.idle_inside(builds, window) == (350 + 500) / 2 / 1e9


def _root_with_trace(tmp_path, gz, counters):
    """A checkout with the benchmark's files and ``gz`` as the newest
    trace of a run, and the context the harness gives its readers."""
    root = tiny_root(str(tmp_path))
    d = os.path.join(harness.trace_dir(root), "plugins", "profile", "t")
    os.makedirs(d)
    path = os.path.join(d, "t.xplane.pb")
    with gzip.open(gz, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ev = tr.load(path)
    summary = tr.summarize(ev, tr.window_of(ev, harness.TRACE_WINDOW))
    return root, harness.ReadContext(summary, counters,
                                     peaks("TPU v5 lite"), 1)


def test_readers_silent_where_the_program_names_no_layer(tmp_path):
    """The older recording has a TPU plane but neither scopes nor spans:
    a program that names no layer, as a traced run sees it."""
    root, ctx = _root_with_trace(tmp_path, OLD, {"work": {
        "conv_flops": 1e9, "conv_bytes": 1e8}})
    for name in NEW:
        assert harness.load_reader(root, name)(ctx) is None, name


def test_readers_silent_without_a_tpu_plane(tmp_path):
    """A trace recorded here, on the CPU: scopes and spans, no TPU."""
    import jax.numpy as jnp
    from repro import scopes
    cpu = tmp_path / "cpu"
    with jax.profiler.trace(str(cpu)):
        with jax.profiler.TraceAnnotation(harness.TRACE_WINDOW):
            with jax.profiler.TraceAnnotation(scopes.SERVE_FLUSH, n=1,
                                              wait_us=5.0):
                jnp.ones(4).block_until_ready()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(cpu) for f in fs
             if f.endswith(".xplane.pb")]
    gz = str(tmp_path / "cpu.xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(gz, "wb") as dst:
        shutil.copyfileobj(src, dst)
    root, ctx = _root_with_trace(tmp_path / "root", gz, {"work": {
        "conv_flops": 1e9, "conv_bytes": 1e8}})
    for name in NEW:
        assert harness.load_reader(root, name)(ctx) is None, name


@pytest.mark.parametrize("name", NEW)
def test_readers_silent_without_a_traced_window(name):
    ctx = harness.ReadContext(None, {}, {}, 1)
    assert harness.load_reader(harness.ROOT, name)(ctx) is None


def test_readers_on_a_recorded_window(tmp_path):
    """``testdata/spans_tiny.xplane.pb.gz`` (``record_tiny.py``, one TPU
    v5e chip): a Map+Reduce job of 4 members x 5 batches of 10 images,
    two SGD epochs in two rounds, then 7 requests in 5 flushes. Each
    reader against a value worked out from the raw events."""
    import json
    from chipbench import work
    with open(os.path.join(HERE, "configs", "cnn_elm_3c9c.json")) as f:
        model = json.load(f)["model"]
    job = work.job(model, 4, 5, 10, 2)
    root, ctx = _root_with_trace(tmp_path, os.path.join(
        HERE, "testdata", "spans_tiny.xplane.pb.gz"), {"work": job})
    got = {n: harness.load_reader(root, n)(ctx) for n in NEW}
    # 3.459033 ms under conv2d; the job's least time is its 18,976,800
    # conv bytes at 819 GB/s
    assert got["conv2d_roofline.train"] == pytest.approx(
        100 * 18976800 / 819e9 / 0.003459033, rel=1e-9)
    assert got["epoch_build_idle_share.train"] == pytest.approx(
        2.7416412145861293, rel=1e-9)
    # wait_us of the 5 flushes over their 7 requests
    assert got["serve_queue_wait_ms"] == pytest.approx(
        (4815.092 + 4713.309 + 2614.44 + 2857.45 + 2074.06) / 7 / 1e3,
        rel=1e-6)
    assert got["serve_score_host_ms"] == pytest.approx(1.6851424, rel=1e-9)

    t = oppaths.load(os.path.join(HERE, "testdata",
                                  "spans_tiny.xplane.pb.gz"))
    window = ctx.trace.window_ns
    names = {s.event.name for s in t.spans if s.event.name.startswith(
        "repro.")}
    assert names == {"repro.map.epoch_build", "repro.map.put",
                     "repro.map.dispatch", "repro.map.gather",
                     "repro.reduce", "repro.serve.collect",
                     "repro.serve.flush", "repro.serve.score",
                     "repro.serve.dispatch", "repro.serve.fetch"}
    # every scope of the program ran on the chip, in jitted code
    for scope in oppaths.SCOPES:
        assert t.scope_s(scope, window)[0] > 0, scope
