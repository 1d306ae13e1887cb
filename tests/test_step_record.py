"""The step record (``RunResult.step_record``): every member's params at
the start of every step of a run's last SGD epoch. On the CPU, at a
reduced size on seeded random weights, it equals the record of the plain
reference run free (``benchmarks/chip/chipbench/reference_steps.py``,
full f32) on every stacked layout; where no SGD step runs there is none,
and the ELM-only program is untouched."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs.base import get_reduced_config
from repro.core import cnn_elm, elm, executor
from repro.core.executor import CheckpointConfig
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import Partition
from repro.launch.mesh import make_member_mesh
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks", "chip"))
from chipbench import reference_steps  # noqa: E402

CFG = get_reduced_config("cnn_elm_6c12c")
MODEL = {k: getattr(CFG, k) for k in (
    "cnn_channels", "cnn_kernel", "cnn_pool", "image_size",
    "image_channels", "num_classes", "elm_lambda")}
B, LR, INIT, SHUFFLE = 8, 0.05, 7, 1234


def _parts(sizes):
    rng = np.random.default_rng(0)
    return [Partition(rng.random((n, 28, 28), np.float32),
                      rng.integers(0, CFG.num_classes, n)) for n in sizes]


def _run(parts, epochs=1, **map_kw):
    return AveragingRun(CFG, MapConfig(
        epochs=epochs, batch_size=B, seed=SHUFFLE,
        lr_schedule=dynamic_paper(LR), **map_kw), ReduceConfig()).run(
        parts, jax.random.PRNGKey(INIT))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


@pytest.mark.parametrize("layout,sizes,map_kw", [
    ("stacked", (32, 32, 32), {}),
    ("chunked", (32, 32, 32), {"chunk_batches": 3}),
    ("masked", (40, 24, 32), {}),
    ("mesh", (40, 24, 32), {"backend": "mesh", "mesh": "one-device"}),
])
def test_record_equals_the_free_running_reference(layout, sizes, map_kw):
    if map_kw.get("mesh") == "one-device":
        map_kw = dict(map_kw, mesh=make_member_mesh(1))
    parts = _parts(sizes)
    res = _run(parts, **map_kw)
    rec = res.step_record
    ref = reference_steps.free_job(
        MODEL, [(p.x, p.y) for p in parts], init_seed=INIT,
        shuffle_seed=SHUFFLE, lr=LR, batch=B)
    nb = max(sizes) // B
    # a chunked epoch rounds up to whole chunks: its tail is padding
    extra = -(-nb // 3) * 3 - nb if layout == "chunked" else 0
    want_mask = np.pad(ref["mask"], ((0, extra), (0, 0)))
    np.testing.assert_array_equal(rec.mask, want_mask)
    assert rec.mask.shape == (nb + extra, len(parts))
    got = _host(rec.params)
    ends = jax.tree.leaves(_host(res.stacked.cnn_params))
    for g, w, end in zip(jax.tree.leaves(got),
                         jax.tree.leaves(ref["record"]), ends):
        assert g.shape == (nb + extra,) + w.shape[1:]
        np.testing.assert_allclose(g[:nb], w, rtol=1e-4, atol=1e-6)
        # padding steps pass the params through: the tail holds the end
        np.testing.assert_array_equal(
            g[nb:], np.broadcast_to(end, g[nb:].shape))
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(got)[0][0, 0]),
        np.asarray(jax.tree.leaves(cnn.init_params(
            CFG, jax.random.PRNGKey(INIT)))[0]))
    for g, w in zip(ends, jax.tree.leaves(ref["members"]["cnn"])):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def test_record_replays_to_the_members_step_by_step():
    """Teacher forcing on the program's own record: every step's update
    and the final β agree with the reference at rounding."""
    parts = _parts((40, 24, 32))
    res = _run(parts)
    got = {"record": _host(res.step_record.params),
           "mask": res.step_record.mask,
           "members": {"cnn": _host(res.stacked.cnn_params),
                       "beta": np.asarray(res.stacked.beta)},
           "averaged": {"cnn": _host(res.averaged.cnn_params),
                        "beta": np.asarray(res.averaged.beta)}}
    n = reference_steps.numbers(MODEL, [(p.x, p.y) for p in parts], got,
                                init_seed=INIT, shuffle_seed=SHUFFLE, lr=LR,
                                batch=B)
    assert n["init_max_err"] == 0.0
    assert n["step_max_err"] < 1e-2
    assert n["replay_beta_err"] < 1e-4
    assert n["average_max_err"] < 1e-6


def test_record_is_the_last_epochs_and_survives_resume(tmp_path):
    """Two SGD epochs over two rounds: the record is the last epoch's,
    the same after a crash and resume, and a finished run rebuilt from
    its checkpoint hands it back too."""
    parts = _parts((32, 32, 32))
    rounds = ReduceConfig(rounds=2)
    run = AveragingRun(CFG, MapConfig(epochs=2, batch_size=B, seed=SHUFFLE,
                                      lr_schedule=dynamic_paper(LR)), rounds)
    whole = run.run(parts, jax.random.PRNGKey(INIT))
    leaves = jax.tree.leaves(_host(whole.step_record.params))
    assert leaves[0].shape[0] == 32 // B
    ck = str(tmp_path / "ck")
    run.run(parts, jax.random.PRNGKey(INIT),
            checkpoint=CheckpointConfig(dir=ck))
    rebuilt = run.resume(parts, jax.random.PRNGKey(INIT), ck)
    assert rebuilt.resumed
    os.remove(os.path.join(ck, sorted(os.listdir(ck))[-1]))
    resumed = run.resume(parts, jax.random.PRNGKey(INIT), ck)
    for res in (rebuilt, resumed):
        np.testing.assert_array_equal(res.step_record.mask,
                                      whole.step_record.mask)
        for a, b in zip(jax.tree.leaves(_host(res.step_record.params)),
                        leaves):
            np.testing.assert_array_equal(a, b)


def test_elm_only_scan_keeps_no_record():
    """Where the scan takes no SGD step it returns (params, stats) alone
    and writes nothing under ``step_record``; the members leave it bit for
    bit as the sequential reference's do, and the run has no record."""
    parts = _parts((40, 24, 32))
    stacked = _run(parts, epochs=0)
    seq = _run(parts, epochs=0, backend="sequential")
    assert stacked.step_record is None and seq.step_record is None
    for got, want in zip(stacked.members, seq.members):
        for a, b in zip(jax.tree.leaves((got.cnn_params, got.beta)),
                        jax.tree.leaves((want.cnn_params, want.beta))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    k, nb, F, C = 3, 2, cnn.feature_dim(CFG), CFG.num_classes
    params = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape),
                          cnn.init_params(CFG, jax.random.PRNGKey(0)))
    args = (CFG, params, elm.zero_stats_stacked(k, F, C),
            jnp.zeros((nb, k, B, 28, 28)), jnp.zeros((nb, k, B, C)),
            jnp.ones((nb, k)), jnp.float32(0.0))
    for sgd in (False, True):
        kw = dict(solve_each_batch=sgd, use_pallas=False, masked=False)
        out = jax.eval_shape(
            lambda *a: cnn_elm.stacked_epoch_scan(CFG, *a, **kw), *args[1:])
        assert len(out) == (3 if sgd else 2)
        text = executor._stacked_epoch.lower(*args, **kw).compile().as_text()
        written = re.findall(
            rf'dynamic-update-slice\(.*op_name="[^"]*/{scopes.STEP_RECORD}/',
            text)
        assert bool(written) == sgd
