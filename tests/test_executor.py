"""The execution layer (`repro.core.executor`): backend registry and
selection, the ``"mesh"`` default ≡ ``"stacked"`` on whatever devices
exist (one device on plain CI; the REAL 8-device matrix re-run in a
subprocess under a forced host device count), the dispatch counts of the
training cells' plans, and the REPRO_HOST_DEVICES override."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import get_reduced_config, replace
from repro.core import cnn_elm, executor, faults
from repro.core.executor import (BACKENDS, ExecutionPlan,
                                 SequentialExecutor, StackedExecutor,
                                 make_executor)
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import partition_iid, partition_unequal
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_reduced_config("cnn_elm_6c12c")
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def parts():
    ds = make_extended_mnist(n_per_class=20, seed=0)
    return partition_iid(ds.x, ds.y, k=3, seed=0)


# ---------------------------------------------------------------------------
# Registry + config surface
# ---------------------------------------------------------------------------

def test_registry_and_backend_names():
    assert BACKENDS == ("sequential", "stacked", "mesh")
    assert isinstance(make_executor("sequential"), SequentialExecutor)
    # "stacked" and "mesh" build the one stacked executor; they differ
    # only in the default member mesh: the first device, or every device
    stacked, mesh = make_executor("stacked"), make_executor("mesh")
    assert type(stacked) is type(mesh) is StackedExecutor
    assert (stacked.name, mesh.name) == ("stacked", "mesh")
    assert list(stacked.mesh.devices.flat) == jax.devices()[:1]
    assert list(mesh.mesh.devices.flat) == jax.devices()
    given = make_executor("stacked", mesh=mesh.mesh)
    assert given.mesh is mesh.mesh
    with pytest.raises(ValueError, match="backend"):
        make_executor("gspmd")
    # MapConfig validates against the same registry
    assert MapConfig(backend="mesh").backend == "mesh"
    with pytest.raises(ValueError, match="mesh"):
        MapConfig(backend="vectorized")
    # only sequential lacks sync points
    assert not SequentialExecutor.supports_rounds
    assert StackedExecutor.supports_rounds


def test_rounds_rejected_on_sequential_only(parts):
    lr = dynamic_paper(0.05)
    with pytest.raises(ValueError, match="stacked"):
        AveragingRun(CFG, MapConfig(epochs=2, lr_schedule=lr,
                                    backend="sequential"),
                     ReduceConfig(rounds=2)).run(parts, KEY)
    # mesh accepts rounds (validated the other way in the mesh suite)
    res = AveragingRun(CFG, MapConfig(epochs=2, lr_schedule=lr,
                                      batch_size=32, backend="mesh"),
                       ReduceConfig(rounds=2)).run(parts, KEY)
    assert res.round_syncs == 1


# ---------------------------------------------------------------------------
# Mesh backend on whatever devices exist (1-pod degenerate on plain CI)
# ---------------------------------------------------------------------------

def test_mesh_backend_matches_stacked_elm_only(parts):
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32)).run(parts, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32,
                                     backend="mesh")).run(parts, KEY)
    assert me.backend == "mesh" and me.stacked is not None
    for a, b in zip(st.members, me.members):
        np.testing.assert_array_equal(np.asarray(a.beta), np.asarray(b.beta))
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-5, atol=1e-6)
    # epochs=0 Map telemetry: one scan chunk + one solve on either name
    assert st.dispatches == me.dispatches == 2


def test_mesh_backend_sgd_and_chunked_bit_identity(parts):
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    st = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr,
                                     batch_size=32)).run(parts, KEY)
    me = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr, batch_size=32,
                                     backend="mesh")).run(parts, KEY)
    for a, b in zip(st.members, me.members):
        np.testing.assert_allclose(np.asarray(a.beta), np.asarray(b.beta),
                                   rtol=1e-4, atol=2e-5)
    # chunking moves transfers, never values — on the mesh path too
    chk = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr,
                                      batch_size=32, backend="mesh",
                                      chunk_batches=2)).run(parts, KEY)
    np.testing.assert_array_equal(np.asarray(me.stacked.beta),
                                  np.asarray(chk.stacked.beta))


def test_mesh_backend_ensemble_and_records(parts):
    res = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32,
                                      backend="mesh")).run(parts, KEY)
    assert len(res.rounds) == 1 and res.rounds[0].dispatches > 0
    accs = res.ensemble().evaluate(
        np.concatenate([p.x for p in parts]),
        np.concatenate([p.y for p in parts]))
    assert accs.shape == (3,) and (accs > 0.2).all()


# ---------------------------------------------------------------------------
# The training cells' plans dispatch what they always did
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("epochs", [0, 1], ids=["elm_e0", "sgd_e1"])
def test_cell_plans_dispatch_counts(epochs):
    """The elm and SGD cells' plans (k=4, one device, the whole epoch in
    one chunk, one round): one epoch program and one β solve — the
    Reduce behind the averaged model is the run's output and not counted
    — with the rows gathered on the device."""
    ds = make_extended_mnist(n_per_class=8, seed=1)
    parts4 = partition_iid(ds.x, ds.y, k=4, seed=0)
    res = AveragingRun(replace(CFG, elm_lambda=1.0), MapConfig(
        epochs=epochs, batch_size=10,
        lr_schedule=dynamic_paper(0.05) if epochs else None)).run(parts4, KEY)
    assert res.dispatches == 2
    assert (res.device_epoch_builds, res.host_epoch_builds) == (1, 0)
    assert res.round_syncs == 0
    assert (res.step_record is not None) == bool(epochs)


def test_stacked_executor_direct(parts):
    """The stacked executor is drivable without the runner: on_round
    fires once per round with working closures, the last round's
    snapshot is what it hands back, and a plan whose epochs do not split
    into its rounds is refused."""
    cfg = replace(CFG, elm_lambda=1.0)
    seen = {}
    plan = ExecutionPlan(
        epochs=2, lr_schedule=dynamic_paper(0.05), batch_size=32, rounds=2,
        on_round=lambda r, snap, avg: seen.setdefault(r, snap().beta))
    out = StackedExecutor().execute(cfg, cnn.init_params(cfg, KEY), parts,
                                    plan)
    assert sorted(seen) == [0, 1] and out.stacked.k == 3
    np.testing.assert_array_equal(np.asarray(out.stacked.beta),
                                  np.asarray(seen[1]))
    with pytest.raises(ValueError, match="split evenly"):
        StackedExecutor().execute(
            cfg, cnn.init_params(cfg, KEY), parts,
            ExecutionPlan(epochs=3, lr_schedule=dynamic_paper(0.05),
                          batch_size=32, rounds=2))


def test_sequential_executor_direct(parts):
    """Executors are drivable without the runner: the sequential one hands
    back host members, fires on_round once with working closures, and
    rejects a rounds>1 plan instead of silently running rounds=1."""
    with pytest.raises(ValueError, match="stacked layout"):
        SequentialExecutor().execute(
            CFG, cnn.init_params(CFG, KEY), parts,
            ExecutionPlan(epochs=2, lr_schedule=dynamic_paper(0.05),
                          batch_size=32, rounds=2))
    fired = {}
    plan = ExecutionPlan(
        epochs=0, batch_size=32, seed=1000,
        on_round=lambda r, snap, avg: fired.update(r=r, sm=snap(),
                                                   avg=avg()))
    out = SequentialExecutor().execute(CFG, cnn.init_params(CFG, KEY),
                                       parts, plan)
    assert out.stacked is None and len(out.members) == 3
    assert fired["r"] == 0 and fired["sm"].k == 3
    ref = cnn_elm.average_models(out.members)
    np.testing.assert_array_equal(np.asarray(fired["avg"].beta),
                                  np.asarray(ref.beta))


# ---------------------------------------------------------------------------
# Epoch build: device gather (partitions fit) vs the host fallback
# ---------------------------------------------------------------------------

def _host_build(monkeypatch):
    """Force the host fallback: a device of 2 bytes fits no partition."""
    monkeypatch.setattr(executor, "_bytes_limit", lambda device: 2)


def _run_outputs(res):
    """Members, β and the averaged model of a run, on the host."""
    return jax.tree.map(np.asarray, (res.stacked.cnn_params,
                                     res.stacked.beta,
                                     res.averaged.cnn_params,
                                     res.averaged.beta))


def _run_case(case, parts, tmp_path):
    sgd = dict(lr_schedule=dynamic_paper(0.05), batch_size=32)
    cfg = replace(CFG, elm_lambda=1.0)
    if case == "stacked_e0":
        return AveragingRun(CFG, MapConfig(epochs=0, batch_size=32)).run(
            parts, KEY)
    if case == "sgd_e2_chunked":
        return AveragingRun(cfg, MapConfig(epochs=2, chunk_batches=1,
                                           **sgd)).run(parts, KEY)
    if case == "unequal_masked":
        x = np.concatenate([p.x for p in parts])
        y = np.concatenate([p.y for p in parts])
        uneq = partition_unequal(x, y, [40, 70, 90], seed=0)
        return AveragingRun(cfg, MapConfig(epochs=1, chunk_batches=2,
                                           **sgd)).run(uneq, KEY)
    # rounds=2, preempted after round 0 and resumed from start_round=1
    run = AveragingRun(cfg, MapConfig(epochs=2, **sgd), ReduceConfig(rounds=2))
    crashed, res = faults.run_crash_resume(run, parts, KEY, str(tmp_path),
                                           unit="round", index=0)
    assert crashed and res.resumed
    return res


@pytest.mark.parametrize("case", ["stacked_e0", "sgd_e2_chunked",
                                  "unequal_masked", "rounds2_resume"])
def test_device_epoch_build_matches_host_build(case, parts, tmp_path,
                                               monkeypatch):
    """The epoch gathered on the device feeds the scan the values the
    host build makes: members, β and the averaged model are bit-identical
    to the forced host fallback on every stacked path."""
    dev = _run_case(case, parts, tmp_path / "device")
    assert dev.device_epoch_builds >= 1 and dev.host_epoch_builds == 0
    _host_build(monkeypatch)
    host = _run_case(case, parts, tmp_path / "host")
    assert host.host_epoch_builds == dev.device_epoch_builds
    assert host.device_epoch_builds == 0
    for a, b in zip(jax.tree.leaves(_run_outputs(dev)),
                    jax.tree.leaves(_run_outputs(host))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("build", ["host", "device"])
def test_epoch_build_counters(build, parts, monkeypatch):
    """One epoch build per epoch, counted where it happened: the host when
    the partitions do not fit half a device's memory, else the device (a
    backend reporting no limit, as the CPU, counts as fitting)."""
    if build == "host":
        _host_build(monkeypatch)
    epochs, rounds = 4, 2
    res = AveragingRun(replace(CFG, elm_lambda=1.0),
                       MapConfig(epochs=epochs, batch_size=32,
                                 lr_schedule=dynamic_paper(0.05)),
                       ReduceConfig(rounds=rounds)).run(parts, KEY)
    per_round = epochs // rounds
    built = {"host": res.host_epoch_builds,
             "device": res.device_epoch_builds}
    assert built[build] == per_round * rounds
    assert built["device" if build == "host" else "host"] == 0
    # the gather rides the epoch's own dispatch: one per epoch, the
    # rounds - 1 syncs and the final solve
    assert res.dispatches == epochs + (rounds - 1) + 1


# ---------------------------------------------------------------------------
# The real multi-device matrix, via subprocess (tier-1 runs single-device)
# ---------------------------------------------------------------------------

def test_mesh_exec_suite_under_8_devices():
    """Re-run tests/test_mesh_exec.py (skipped above at 1 device) under 8
    forced host devices — the ISSUE-4 acceptance matrix: padded/unequal
    equivalence, rounds parity, ONE all-reduce per sync/Reduce (HLO),
    pod-sharded solve, real shardings, E²LM global readout."""
    if len(jax.devices()) >= 8:
        pytest.skip("already multi-device; the module runs directly")
    if os.environ.get("REPRO_SKIP_MESH_SUBPROCESS"):
        pytest.skip("REPRO_SKIP_MESH_SUBPROCESS set — the caller runs "
                    "tests/test_mesh_exec.py directly (the CI mesh step)")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=8"))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "tests/test_mesh_exec.py"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "passed" in out.stdout and "skipped" not in out.stdout


def test_repro_host_devices_env_override(tmp_path):
    """REPRO_HOST_DEVICES drives force_host_device_count (the dry-run's
    512 default) so tests/CI can request small simulated meshes cheaply."""
    script = (
        "from repro.launch.mesh import (force_host_device_count, "
        "make_host_mesh, make_member_mesh)\n"
        "n = force_host_device_count()\n"
        "import jax\n"
        "assert n == 6 and len(jax.devices()) == 6, (n, jax.devices())\n"
        "assert make_host_mesh().shape == {'data': 6, 'model': 1}\n"
        "assert make_member_mesh().shape == {'pod': 6}\n"
        "assert make_member_mesh(3).shape == {'pod': 3}\n"
        "print('OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               REPRO_HOST_DEVICES="6")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout