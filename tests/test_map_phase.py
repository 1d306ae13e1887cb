"""Stacked (vmap + lax.scan) Map phase vs the sequential Algorithm 2
reference: numerical equivalence (equal AND unequal shards via the
padded/masked scan), the per-epoch-reshuffle rng contract, the chunked
double-buffered scan's bit-identity, the weighted Reduce, the pluggable
eval backend, and the map-phase benchmark smoke runs."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import get_reduced_config, replace
from repro.core import cnn_elm
from repro.core.averaging import weighted_average_trees
from repro.core.runner import (AveragingRun, MapConfig, ReduceConfig,
                               evaluate_model, kappa_model)
from repro.data.partition import (Partition, batches, chunk_scan_major,
                                  epoch_batch_arrays,
                                  padded_epoch_indices,
                                  padded_stacked_epoch_batches, partition_iid,
                                  partition_unequal, stacked_epoch_batches)
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

CFG = get_reduced_config("cnn_elm_6c12c")
KEY = jax.random.PRNGKey(0)


def _run(cfg, parts, *, epochs, lr_schedule=None, batch_size,
         stacked=False, weight_by_shard=False):
    """(members, averaged) through the runner — the surface the old
    ``distributed_cnn_elm`` shim used to wrap."""
    res = AveragingRun(
        cfg,
        MapConfig(epochs=epochs, lr_schedule=lr_schedule,
                  batch_size=batch_size,
                  backend="stacked" if stacked else "sequential"),
        ReduceConfig(
            strategy="shard_weighted" if weight_by_shard else "uniform"),
    ).run(parts, KEY)
    return res.members, res.averaged


def _stacked(cfg, parts, **map_kw):
    """The trained ``StackedMembers`` of a stacked run from KEY's init."""
    return AveragingRun(cfg, MapConfig(**map_kw)).run(parts, KEY).stacked


@pytest.fixture(scope="module")
def parts():
    ds = make_extended_mnist(n_per_class=20, seed=0)
    return partition_iid(ds.x, ds.y, k=3, seed=0)


@pytest.fixture(scope="module")
def uneq_parts():
    """Shards with 3/2/1 batches of 32 — the regime the stacked path used
    to reject."""
    ds = make_extended_mnist(n_per_class=20, seed=0)
    return partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)


def _assert_models_close(a, b, rtol, atol_beta, atol_params):
    np.testing.assert_allclose(np.asarray(a.beta), np.asarray(b.beta),
                               rtol=rtol, atol=atol_beta)
    for la, lb in zip(jax.tree.leaves(a.cnn_params),
                      jax.tree.leaves(b.cnn_params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol_params)


def test_epoch_batch_arrays_match_iterator(parts):
    """The fixed-shape epoch arrays must replay the streaming iterator's
    batch order bit-for-bit — the contract the scan path relies on."""
    part = parts[0]
    xs, ys = epoch_batch_arrays(part, 32, seed=7)
    for i, (x, y) in enumerate(batches(part, 32, seed=7)):
        np.testing.assert_array_equal(xs[i], x)
        np.testing.assert_array_equal(ys[i], y)
    assert xs.shape[0] == i + 1


def test_epoch_batch_arrays_reshuffles_per_epoch(parts):
    """Epoch e's arrays replay epoch e of the multi-epoch iterator — each
    epoch a FRESH permutation from one rng stream (regression: both paths
    used to replay epoch 0's permutation forever)."""
    part = parts[0]
    stream = list(batches(part, 32, seed=7, epochs=3))
    nb = epoch_batch_arrays(part, 32, seed=7, epoch=0)[0].shape[0]
    for e in range(3):
        xs, ys = epoch_batch_arrays(part, 32, seed=7, epoch=e)
        for i in range(nb):
            np.testing.assert_array_equal(xs[i], stream[e * nb + i][0])
            np.testing.assert_array_equal(ys[i], stream[e * nb + i][1])
    y0 = epoch_batch_arrays(part, 32, seed=7, epoch=0)[1]
    y1 = epoch_batch_arrays(part, 32, seed=7, epoch=1)[1]
    assert not np.array_equal(y0, y1), "epochs must reshuffle"


def test_batches_start_epoch_contract(parts):
    """batches(start_epoch=e) == epoch e of batches(epochs=e+1)."""
    part = parts[0]
    stream = list(batches(part, 32, seed=3, epochs=3))
    nb = len(stream) // 3
    tail = list(batches(part, 32, seed=3, start_epoch=2))
    assert len(tail) == nb
    for i, (x, y) in enumerate(tail):
        np.testing.assert_array_equal(x, stream[2 * nb + i][0])
        np.testing.assert_array_equal(y, stream[2 * nb + i][1])


def test_stacked_epoch_batches_rejects_unequal():
    x = np.zeros((100, 4, 4), np.float32)
    y = np.zeros((100,), np.int32)
    uneven = [Partition(x[:64], y[:64]), Partition(x[:32], y[:32])]
    with pytest.raises(ValueError, match="equal batch counts"):
        stacked_epoch_batches(uneven, 32, [0, 1])


def test_padded_stacked_epoch_batches(uneq_parts):
    """Padded builder: per-member prefix bit-identical to the member's own
    epoch arrays, zeros + mask 0 past it, all-ones mask when shards are
    equal."""
    xs, ys, mask = padded_stacked_epoch_batches(uneq_parts, 32,
                                                [1000, 1001, 1002])
    counts = [len(p.x) // 32 for p in uneq_parts]
    assert xs.shape[:2] == (3, max(counts)) and mask.shape == (3, max(counts))
    for i, p in enumerate(uneq_parts):
        ref_x, ref_y = epoch_batch_arrays(p, 32, seed=1000 + i)
        np.testing.assert_array_equal(xs[i, :counts[i]], ref_x)
        np.testing.assert_array_equal(ys[i, :counts[i]], ref_y)
        np.testing.assert_array_equal(mask[i],
                                      [1.0] * counts[i]
                                      + [0.0] * (max(counts) - counts[i]))
        assert not xs[i, counts[i]:].any()
    # num_batches rounds the common count further up (chunk alignment)
    xs4, _, mask4 = padded_stacked_epoch_batches(uneq_parts, 32,
                                                 [1000, 1001, 1002],
                                                 num_batches=4)
    assert xs4.shape[1] == 4 and not mask4[:, 3].any()
    with pytest.raises(ValueError, match="num_batches"):
        padded_stacked_epoch_batches(uneq_parts, 32, [0, 1, 2], num_batches=1)


def test_padded_epoch_indices_select_the_padded_batches(uneq_parts):
    """The index builder draws the padded builder's epoch: member i's
    batch b is its rows at idx[b, i], scan-major; padding points at row 0
    under mask 0, and both consume one draw per member stream."""
    seeds = [1000, 1001, 1002]
    xs, ys, mask = padded_stacked_epoch_batches(uneq_parts, 32, seeds,
                                                num_batches=4)
    rngs = [np.random.default_rng(s) for s in seeds]
    idx, mb = padded_epoch_indices(uneq_parts, 32, rngs, num_batches=4)
    assert idx.shape == (4, 3, 32) and idx.dtype == np.int32
    np.testing.assert_array_equal(mb, mask.T)
    for i, p in enumerate(uneq_parts):
        real = mask[i] > 0
        np.testing.assert_array_equal(p.x[idx[real, i]], xs[i, real])
        np.testing.assert_array_equal(p.y[idx[real, i]], ys[i, real])
        assert not idx[~real, i].any()
    # the live streams moved on by one permutation: epoch 1 follows
    nxt, _ = padded_epoch_indices(uneq_parts, 32, rngs)
    ref_x, _ = epoch_batch_arrays(uneq_parts[0], 32, seed=1000, epoch=1)
    np.testing.assert_array_equal(uneq_parts[0].x[nxt[:len(ref_x), 0]],
                                  ref_x)
    with pytest.raises(ValueError, match="num_batches"):
        padded_epoch_indices(uneq_parts, 32, seeds, num_batches=1)


def test_padded_equal_shards_all_ones(parts):
    _, _, mask = padded_stacked_epoch_batches(parts, 32, [0, 1, 2])
    np.testing.assert_array_equal(mask, np.ones_like(mask))


def test_chunk_scan_major():
    a = np.arange(24).reshape(6, 4)
    chunks = chunk_scan_major((a,), 2)
    assert len(chunks) == 3
    np.testing.assert_array_equal(np.concatenate([c[0] for c in chunks]), a)
    with pytest.raises(ValueError, match="chunks"):
        chunk_scan_major((a,), 4)


def test_stacked_equivalent_elm_only(parts):
    """epochs=0 (Tables 2/4): the stacked path must reproduce the sequential
    members and averaged model exactly (stats are pure sums; the β solve
    shares one lowering across both paths)."""
    m_seq, avg_seq = _run(CFG, parts, epochs=0, batch_size=32)
    m_st, avg_st = _run(CFG, parts, epochs=0, batch_size=32, stacked=True)
    for a, b in zip(m_seq, m_st):
        _assert_models_close(a, b, rtol=0, atol_beta=0, atol_params=0)
    _assert_models_close(avg_seq, avg_st, rtol=1e-6, atol_beta=1e-6,
                         atol_params=1e-6)


def test_stacked_equivalent_sgd_epochs(parts):
    """epochs=2: member params and β within rtol 1e-4 of the sequential
    reference. λ=1 keeps the solve well-conditioned so the comparison
    measures implementation equivalence, not f32 amplification through a
    nearly-singular normal matrix."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    m_seq, avg_seq = _run(cfg, parts, epochs=2, lr_schedule=lr,
                          batch_size=32)
    m_st, avg_st = _run(cfg, parts, epochs=2, lr_schedule=lr, batch_size=32,
                        stacked=True)
    for a, b in zip(m_seq + [avg_seq], m_st + [avg_st]):
        _assert_models_close(a, b, rtol=1e-4, atol_beta=2e-5,
                             atol_params=1e-6)


@pytest.mark.parametrize("backend", ["sequential", "stacked"])
def test_sgd_epoch_through_pallas_conv(parts, backend):
    """One SGD epoch with ``use_pallas=True`` — the kernel path a TPU takes
    (here interpreted): the conv kernel's custom VJP drives Alg. 2 line 13,
    and members track the XLA-conv run to the SGD tolerance."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    runs = [AveragingRun(cfg, MapConfig(epochs=1, lr_schedule=lr,
                                        batch_size=32, backend=backend,
                                        use_pallas=up)).run(parts, KEY)
            for up in (False, True)]
    ref, pallas = runs
    for a, b in zip(ref.members + [ref.averaged],
                    pallas.members + [pallas.averaged]):
        _assert_models_close(a, b, rtol=1e-4, atol_beta=2e-5,
                             atol_params=1e-6)


def test_stacked_members_api(parts):
    sm = _stacked(CFG, parts, epochs=0, batch_size=32)
    assert sm.k == len(parts)
    members = sm.unstack()
    assert len(members) == sm.k
    np.testing.assert_array_equal(np.asarray(members[1].beta),
                                  np.asarray(sm.beta[1]))
    avg = sm.averaged()
    np.testing.assert_allclose(
        np.asarray(avg.beta),
        np.mean([np.asarray(m.beta) for m in members], axis=0),
        rtol=1e-6, atol=1e-7)


# one SGD epoch over unequal shards (3/2/1 batches), k=3 members: on a
# mesh of four devices one slot is padding
_PARITY_RUN = """
import jax, numpy as np
from repro.configs.base import get_reduced_config, replace
from repro.core.runner import AveragingRun, MapConfig
from repro.data.partition import partition_unequal
from repro.data.synthetic import make_extended_mnist
from repro.launch.mesh import make_member_mesh
from repro.optim.schedules import dynamic_paper

def parity_run(backend, devices):
    ds = make_extended_mnist(n_per_class=20, seed=0)
    parts = partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)
    cfg = replace(get_reduced_config("cnn_elm_6c12c"), elm_lambda=1.0)
    res = AveragingRun(cfg, MapConfig(
        epochs=1, lr_schedule=dynamic_paper(0.05), batch_size=32,
        backend=backend,
        mesh=None if backend == "sequential" else make_member_mesh(devices),
    )).run(parts, jax.random.PRNGKey(0))
    return [np.asarray(a) for m in res.members
            for a in jax.tree.leaves((m.cnn_params, m.beta))]
"""


@pytest.mark.parametrize("case", ["one_device", "four_devices",
                                  "sequential"])
def test_stacked_parity_on_member_meshes(case, tmp_path):
    """The stacked executor on the one-device member mesh (the single
    chip's layout), on a forced four-device CPU mesh (its own process:
    the device count is fixed at start-up) and the sequential reference
    train the same members from unequal shards: equal β and params, to
    the repo's SGD tolerance."""
    scope: dict = {}
    exec(_PARITY_RUN, scope)
    ref = scope["parity_run"]("stacked", 1)
    if case == "four_devices":
        out = tmp_path / "four.npz"
        script = (_PARITY_RUN + "\nassert len(jax.devices()) == 4\n"
                  f"np.savez({str(out)!r}, *parity_run('mesh', 4))\n")
        root = os.path.join(os.path.dirname(__file__), "..")
        env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_"
                              "force_host_platform_device_count=4"))
        run = subprocess.run([sys.executable, "-c", script], env=env,
                             cwd=root, capture_output=True, text=True,
                             timeout=600)
        assert run.returncode == 0, run.stdout + run.stderr
        with np.load(out) as f:
            got = [f[f"arr_{i}"] for i in range(len(f.files))]
    else:
        got = scope["parity_run"](
            "stacked" if case == "one_device" else "sequential", 1)
    assert len(got) == len(ref)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=2e-5)


def test_average_models_weighted(parts):
    """Shard-size weights reduce unequal partitions to the exact weighted
    expectation (delegates to weighted_average_trees)."""
    init = cnn.init_params(CFG, KEY)
    models = [cnn_elm.train_member(CFG, init, p, epochs=0, lr_schedule=None,
                                   batch_size=32, seed=1000 + i)
              for i, p in enumerate(parts[:2])]
    w = [3.0, 1.0]
    avg = cnn_elm.average_models(models, weights=w)
    ref_cnn, ref_beta = weighted_average_trees(
        [(m.cnn_params, m.beta) for m in models], w)
    np.testing.assert_allclose(np.asarray(avg.beta), np.asarray(ref_beta),
                               rtol=1e-6)
    for la, lb in zip(jax.tree.leaves(avg.cnn_params),
                      jax.tree.leaves(ref_cnn)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-6)
    with pytest.raises(ValueError):
        cnn_elm.average_models(models, weights=[1.0])


def test_stacked_unequal_elm_only_bit_exact(uneq_parts):
    """epochs=0 over 3/2/1-batch shards: each masked-stacked member must be
    BIT-identical to its own sequential run (padding batches contribute
    exactly zero), and the shard-weighted Reduce must agree."""
    m_seq, avg_seq = _run(CFG, uneq_parts, epochs=0, batch_size=32,
                          weight_by_shard=True)
    m_st, avg_st = _run(CFG, uneq_parts, epochs=0, batch_size=32,
                        stacked=True, weight_by_shard=True)
    for a, b in zip(m_seq, m_st):
        _assert_models_close(a, b, rtol=0, atol_beta=0, atol_params=0)
    _assert_models_close(avg_seq, avg_st, rtol=1e-6, atol_beta=1e-6,
                         atol_params=1e-6)


def test_stacked_unequal_sgd_matches_sequential_weighted(uneq_parts):
    """epochs=2 SGD over unequal shards: masked-stacked members and the
    shard-weighted Reduce within rtol 1e-4 of the sequential reference —
    the acceptance bar for lifting the equal-batch-count restriction."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    m_seq, avg_seq = _run(cfg, uneq_parts, epochs=2, lr_schedule=lr,
                          batch_size=32, weight_by_shard=True)
    m_st, avg_st = _run(cfg, uneq_parts, epochs=2, lr_schedule=lr,
                        batch_size=32, stacked=True, weight_by_shard=True)
    for a, b in zip(m_seq + [avg_seq], m_st + [avg_st]):
        _assert_models_close(a, b, rtol=1e-4, atol_beta=2e-5,
                             atol_params=1e-6)


@pytest.mark.parametrize("chunk_batches", [1, 2])
def test_chunked_scan_bit_identical(uneq_parts, chunk_batches):
    """The double-buffered chunked epoch must be BIT-identical to the
    monolithic scan — chunking only changes where host→device transfers
    happen, never a single value. Unequal shards make the nastiest case:
    mask padding AND chunk-tail padding interact."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    mono = _stacked(cfg, uneq_parts, epochs=2, lr_schedule=lr,
                    batch_size=32)
    chk = _stacked(cfg, uneq_parts, epochs=2, lr_schedule=lr, batch_size=32,
                   chunk_batches=chunk_batches)
    np.testing.assert_array_equal(np.asarray(mono.beta), np.asarray(chk.beta))
    for la, lb in zip(jax.tree.leaves(mono.cnn_params),
                      jax.tree.leaves(chk.cnn_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_chunked_equal_shards_bit_identical(parts):
    """Equal shards + a chunk size that doesn't divide the epoch (4 batches
    into chunks of 3 → one padded tail chunk) still bit-identical."""
    mono = _stacked(CFG, parts, epochs=0, batch_size=32)
    chk = _stacked(CFG, parts, epochs=0, batch_size=32, chunk_batches=3)
    np.testing.assert_array_equal(np.asarray(mono.beta), np.asarray(chk.beta))


def test_weight_by_shard_on_stacked_path():
    """stacked=True must honour weight_by_shard (regression: it was silently
    ignored): shards of 40/33 rows both give 2 batches of 16, so the stacked
    path accepts them, and the Reduce must weight by shard size."""
    ds = make_extended_mnist(n_per_class=10, seed=4)
    parts = [Partition(ds.x[:40], ds.y[:40]), Partition(ds.x[40:73], ds.y[40:73])]
    members, avg = _run(CFG, parts, epochs=0, batch_size=16,
                        stacked=True, weight_by_shard=True)
    ref = cnn_elm.average_models(members, weights=[40.0, 33.0])
    np.testing.assert_allclose(np.asarray(avg.beta), np.asarray(ref.beta),
                               rtol=1e-6, atol=1e-7)


def test_backend_env_override_applies_per_call(monkeypatch):
    """REPRO_USE_PALLAS resolves outside the jit cache (regression: the
    unresolved None used to be the static key, so the first call's auto
    decision was replayed forever)."""
    from repro.kernels.conv2d import ops as conv_ops
    x = jax.numpy.zeros((1, 8, 8, 1))
    w = jax.numpy.zeros((3, 3, 1, 2))
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    auto = str(jax.make_jaxpr(lambda: conv_ops.conv2d_valid(x, w))())
    assert "conv_general_dilated" in auto  # CPU auto -> XLA reference
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    forced = str(jax.make_jaxpr(lambda: conv_ops.conv2d_valid(x, w))())
    assert "conv_general_dilated" not in forced  # the Pallas conv kernel


def test_eval_backend_is_pluggable():
    """The live scoring jit (runner._scores_stacked — every eval entry
    routes through it) takes use_pallas as a static arg (regression: the
    auto policy was baked into the first trace, so REPRO_USE_PALLAS flips
    and explicit backend requests were silently ignored for eval)."""
    from repro.core import runner
    ds = make_extended_mnist(n_per_class=4, seed=2)
    params_k = jax.tree.map(lambda a: a[None], cnn.init_params(CFG, KEY))
    beta_k = jax.numpy.zeros((1, cnn.feature_dim(CFG), CFG.num_classes))
    x = jax.numpy.asarray(ds.x[:8])
    ref = runner._scores_stacked.lower(CFG, params_k, beta_k, x,
                                       use_pallas=False).as_text()
    forced = runner._scores_stacked.lower(CFG, params_k, beta_k, x,
                                          use_pallas=True).as_text()
    assert "stablehlo.convolution" in ref        # XLA reference path
    assert "stablehlo.convolution" not in forced  # the Pallas conv kernel


def test_evaluate_kappa_accept_backend(parts):
    """evaluate/kappa honour an explicit backend and agree across them."""
    ds = make_extended_mnist(n_per_class=4, seed=3)
    model = cnn_elm.train_member(CFG, cnn.init_params(CFG, KEY), parts[0],
                                 epochs=0, lr_schedule=None, batch_size=32)
    a_ref = evaluate_model(CFG, model, ds.x, ds.y, use_pallas=False)
    a_pl = evaluate_model(CFG, model, ds.x, ds.y, use_pallas=True)
    assert a_ref == pytest.approx(a_pl)
    k_ref = kappa_model(CFG, model, ds.x, ds.y, use_pallas=False)
    k_pl = kappa_model(CFG, model, ds.x, ds.y, use_pallas=True)
    assert k_ref == pytest.approx(k_pl, abs=1e-6)


def test_map_phase_benchmark_smoke(tmp_path):
    """The benchmark must run end-to-end on a tiny config and emit a
    well-formed BENCH_map_phase.json."""
    from benchmarks import map_phase
    payload = map_phase.run(k=2, n_per_class=8, epochs=1, batch_size=16,
                            iters=1, out_dir=str(tmp_path))
    path = tmp_path / "BENCH_map_phase.json"
    assert path.exists()
    on_disk = json.loads(path.read_text())
    for key in ("sequential_us", "stacked_us", "speedup", "k", "epochs",
                "num_batches", "batch_size", "backend"):
        assert key in on_disk, key
    assert on_disk["sequential_us"] > 0 and on_disk["stacked_us"] > 0
    assert payload["speedup"] == pytest.approx(
        payload["sequential_us"] / payload["stacked_us"])


def test_map_phase_unequal_benchmark_smoke(tmp_path):
    """Unequal-shard config: well-formed BENCH_map_phase_unequal.json with
    genuinely unequal batch counts."""
    from benchmarks import map_phase
    payload = map_phase.run_unequal(k=2, n_per_class=8, epochs=1,
                                    batch_size=16, iters=1,
                                    out_dir=str(tmp_path))
    on_disk = json.loads((tmp_path / "BENCH_map_phase_unequal.json")
                         .read_text())
    for key in ("sequential_us", "stacked_us", "speedup", "shard_sizes",
                "batch_counts", "padded_batches", "pad_fraction"):
        assert key in on_disk, key
    assert len(set(payload["batch_counts"])) > 1
    assert payload["padded_batches"] == max(payload["batch_counts"])


def test_map_phase_chunked_benchmark_smoke(tmp_path):
    """Chunked config: well-formed BENCH_map_phase_chunked.json; the
    benchmark itself asserts bit-identity, so a divergence fails loudly."""
    from benchmarks import map_phase
    payload = map_phase.run_chunked(k=2, n_per_class=8, epochs=1,
                                    batch_size=16, chunk_batches=2, iters=1,
                                    out_dir=str(tmp_path))
    on_disk = json.loads((tmp_path / "BENCH_map_phase_chunked.json")
                         .read_text())
    for key in ("monolithic_us", "chunked_us", "overhead", "bit_identical",
                "chunk_batches", "epoch_bytes", "chunk_bytes", "peak_bytes"):
        assert key in on_disk, key
    assert payload["bit_identical"] is True
    assert payload["peak_bytes"] == 2 * payload["chunk_bytes"]
    assert payload["peak_bytes"] < payload["epoch_bytes"]


def test_map_phase_rounds_benchmark_smoke(tmp_path):
    """Multi-round config: well-formed BENCH_map_phase_rounds.json with one
    per-round dispatch entry per round and a positive sync overhead."""
    from benchmarks import map_phase
    payload = map_phase.run_rounds(k=2, n_per_class=8, epochs=2,
                                   batch_size=16, rounds=2, iters=1,
                                   out_dir=str(tmp_path))
    on_disk = json.loads((tmp_path / "BENCH_map_phase_rounds.json")
                         .read_text())
    for key in ("single_round_us", "multi_round_us", "sync_overhead",
                "rounds", "epochs_per_round", "round_dispatches",
                "total_dispatches"):
        assert key in on_disk, key
    assert len(payload["round_dispatches"]) == payload["rounds"] == 2
    assert payload["epochs_per_round"] == 1
    assert payload["single_round_us"] > 0 and payload["multi_round_us"] > 0
    with pytest.raises(ValueError, match="split into rounds"):
        map_phase.run_rounds(k=2, n_per_class=8, epochs=3, batch_size=16,
                             rounds=2, iters=1, out_dir=str(tmp_path))


def test_map_phase_mesh_benchmark_smoke(tmp_path):
    """Mesh-sweep config: re-execs itself under 2 forced host devices,
    emits a well-formed BENCH_map_phase_mesh.json, and hard-asserts the
    one-all-reduce contract for the sync and the Reduce."""
    from benchmarks import map_phase
    payload = map_phase.run_mesh(k=2, n_per_class=8, epochs=1,
                                 batch_size=16, rounds=1, devices=(1, 2),
                                 iters=1, out_dir=str(tmp_path))
    on_disk = json.loads((tmp_path / "BENCH_map_phase_mesh.json")
                         .read_text())
    for key in ("stacked_us", "sweep", "k", "allreduce_per_sync",
                "allreduce_per_reduce", "sync_collective_per_chip_bytes",
                "reduce_collective_per_chip_bytes", "cost_model"):
        assert key in on_disk, key
    assert payload["allreduce_per_sync"] == 1
    assert payload["allreduce_per_reduce"] == 1
    assert [row["devices"] for row in payload["sweep"]] == [1, 2]
    assert all(row["mesh_us"] > 0 for row in payload["sweep"])
