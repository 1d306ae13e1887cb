"""The step-checked SGD cell (``train-6c12c-k4``, driver ``train_sgd``) on
the CPU at a tiny size: the sound program reads 0 or rounding on every
number; each fault planted in the program, and the lower-precision
control, reads above it, and the faults read ``correct`` false; a program
that keeps no step record stops in set-up; and the cell's two readers on
a trace recorded on a TPU v5e chip."""
import gzip
import io
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, oppaths, sgd_work, work
from chipbench import trace as tr
from chipbench.drivers import train_sgd
from chipbench.testing import run_tiny, tiny_root

CELL = "train-6c12c-k4"
HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ["beta_solve_share.sgd", "sgd_update_roofline.sgd"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("sgd")))


@pytest.fixture(scope="module")
def sound(root):
    return run_tiny(root, CELL)[0]


def test_sound_program_reads_rounding(sound):
    shown = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["correct"] is True
    assert set(shown) == {"init_max_err", "step_max_err", "replay_beta_err",
                          "average_max_err"}
    assert shown["init_max_err"] == 0.0
    assert shown["step_max_err"] < 1e-3       # f32 rounding of p − αg
    assert shown["replay_beta_err"] < 1e-6
    assert shown["average_max_err"] < 1e-6
    assert set(sound["metrics"]) == {"train_images_per_s", "setup_s"}


def _unchanged(cfg, p, s, xb, tb, mb, lr, **kw):
    """Every step hands back the params it was given, no sums."""
    return p, s, jax.tree.map(
        lambda a: jnp.broadcast_to(a, (mb.shape[0],) + a.shape), p)


def _half_batch(real):
    def epoch(cfg, p, s, xb, tb, mb, lr, **kw):
        half = xb.shape[2] // 2
        return real(cfg, p, s, xb[:, :, :half], tb[:, :, :half], mb, lr,
                    **kw)
    return epoch


def _stale_beta(monkeypatch):
    """β of step j solved from the sums before batch j is added: the
    scan body's ``elm`` hands ``solve_beta`` the sums ``add_stats`` was
    given, in a program compiled anew."""
    from repro.core import cnn_elm, elm
    before = []

    class Stale:
        def __getattr__(self, name):
            return getattr(elm, name)

        def add_stats(self, a, b):
            before.append(a)
            return elm.add_stats(a, b)

        def solve_beta(self, stats, lam):
            return elm.solve_beta(before.pop(), lam)

    def epoch(cfg, *a, **kw):   # a function of its own: traced anew
        return cnn_elm.stacked_epoch_scan(cfg, *a, **kw)

    monkeypatch.setattr(cnn_elm, "elm", Stale())
    return jax.jit(epoch, static_argnames=(
        "cfg", "solve_each_batch", "use_pallas", "masked"))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "stale_beta"])
def test_fault_is_not_correct(root, sound, monkeypatch, fault):
    from repro.core import executor
    planted = {"unchanged": lambda: _unchanged,
               "half_batch": lambda: _half_batch(executor._stacked_epoch),
               "stale_beta": lambda: _stale_beta(monkeypatch)}[fault]()
    monkeypatch.setattr(executor, "_stacked_epoch", planted)
    result, _, _ = run_tiny(root, CELL)
    assert result["correct"] is False
    over = [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]
    assert "step_max_err" in over
    assert result["compared"]["step_max_err"]["value"] > 100 * sound[
        "compared"]["step_max_err"]["value"]


def test_control_and_faults_read_above_the_sound_program(root, sound):
    """The reference at ``high`` and the faults in the program's place,
    through the calibration's own path; a Reduce left out reads on the
    averaged model alone."""
    env = harness.load_env(root, CELL, 0, 0.0, False)
    out = train_sgd.readings(env, [2 ** 31 + 3], log=lambda *a: None)
    got = list(out.values())[0]
    assert set(got) == set(train_sgd.VARIANTS)
    shown = {k: v["value"] for k, v in sound["compared"].items()}
    control = got["control"]
    assert control["step_max_err"] > 3 * shown["step_max_err"]
    assert control["replay_beta_err"] > shown["replay_beta_err"]
    for fault in ("half_batch", "stale_beta", "unchanged", "no_reduce"):
        assert got[fault]["correct"] is False, fault
    assert got["no_reduce"]["average_max_err"] > 0.1
    assert got["unchanged"]["step_max_err"] == 1.0


def test_program_without_record_stops_in_setup(root, monkeypatch):
    """A program whose ``RunResult`` has no step record: set-up refuses
    before the data or the warm-up job, and no result line is printed."""
    from dataclasses import dataclass
    from repro.core import runner

    @dataclass
    class RunResult:
        stacked: object = None

    def never(*a, **kw):
        raise AssertionError("set-up went on past the record check")

    monkeypatch.setattr(runner, "RunResult", RunResult)
    monkeypatch.setattr(train_sgd, "partitions", never)
    monkeypatch.setattr(train_sgd.RecordedJobs, "run", never)
    out, err = io.StringIO(), io.StringIO()
    with pytest.raises(harness.SetupError, match="step record"):
        harness.run(["--workload", CELL, "--seed", "5", "--seconds", "1"],
                    root=root, chip=False, out=out, err=err)
    assert not [ln for ln in out.getvalue().splitlines()
                if ln.startswith("{")]


def _root_with_trace(tmp_path, gz, counters):
    """A checkout with the benchmark's files, ``gz`` as the newest trace
    of a run, and the context the harness gives its readers."""
    root = tiny_root(str(tmp_path))
    d = os.path.join(harness.trace_dir(root), "plugins", "profile", "t")
    os.makedirs(d)
    path = os.path.join(d, "t.xplane.pb")
    with gzip.open(gz, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ev = tr.load(path)
    summary = tr.summarize(ev, tr.window_of(ev, harness.TRACE_WINDOW))
    return root, harness.ReadContext(summary, counters,
                                     work.peaks("TPU v5 lite"), 1)


def test_readers_on_a_recorded_window(tmp_path):
    """``testdata/spans_tiny.xplane.pb.gz`` (``testdata/record_tiny.py``,
    one TPU v5e chip): 3c-9c, 4 members x 5 batches of 10 images, two SGD
    epochs. The β solves' device time over the window; the gradient's
    least time over the ``sgd_update`` scope's device time."""
    gz = os.path.join(HERE, "testdata", "spans_tiny.xplane.pb.gz")
    with open(os.path.join(HERE, "configs", "cnn_elm_3c9c.json")) as f:
        model = json.load(f)["model"]
    per = sgd_work.grad(model, 10)
    steps = 4 * 5 * 2
    counters = {"work": {f"sgd_update_{k}": v * steps
                         for k, v in per.items()}}
    root, ctx = _root_with_trace(tmp_path, gz, counters)
    got = {n: harness.load_reader(root, n)(ctx) for n in READERS}
    t = oppaths.load(gz)
    window = ctx.trace.window_ns
    solve = t.scope_s("beta_solve", window)[0]
    grad = t.scope_s("sgd_update", window)[0]
    assert 0 < solve and 0 < grad
    assert got["beta_solve_share.sgd"] == pytest.approx(
        100 * solve / ctx.trace.window_s, rel=1e-12)
    least = max(per["flops"] * steps / 197e12, per["bytes"] * steps / 819e9)
    assert got["sgd_update_roofline.sgd"] == pytest.approx(
        100 * least / grad, rel=1e-12)
    assert 0 < got["sgd_update_roofline.sgd"] < 100


def test_gradient_work_by_hand():
    """6c-12c at B=200: conv1 (576 outputs x 25 x 6) and conv2 (64 x 150
    x 12) forward again, their kernel gradients and conv2's input
    gradient, and 4·B·L·C = 4·200·192·10 for the loss."""
    with open(os.path.join(HERE, "configs", "cnn_elm_6c12c.json")) as f:
        model = json.load(f)["model"]
    c1 = 2 * 200 * 576 * 25 * 6
    c2 = 2 * 200 * 64 * 150 * 12
    assert sgd_work.grad(model, 200)["flops"] == (
        c1 + c2 + c1 + 2 * c2 + 4 * 200 * 192 * 10)


@pytest.mark.parametrize("name", READERS)
def test_readers_silent_where_nothing_is_named(tmp_path, name):
    """No traced window; the older recording, whose program named no
    layer; counters without the gradient's work."""
    assert harness.load_reader(harness.ROOT, name)(
        harness.ReadContext(None, {}, {}, 1)) is None
    old = os.path.join(HERE, "testdata", "train_tiny.xplane.pb.gz")
    root, ctx = _root_with_trace(tmp_path, old, {"work": {
        "sgd_update_flops": 1e9, "sgd_update_bytes": 1e8}})
    assert harness.load_reader(root, name)(ctx) is None
