"""The stacked executor on a REAL multi-device member mesh (8 simulated
host CPUs).

Runs the acceptance matrix: the multi-device mesh ≡ the one-device mesh
(epochs=0 bit-exact, SGD rtol 1e-4) for equal, unequal AND
padded member counts (mesh larger than k; k not divisible by the pod
count — the pad-and-mask contract), rounds parity, shard-weighted Reduce
parity, the one-all-reduce HLO assertion for the Reduce and every sync,
the pod-sharded β solve, real ``member_dim_shardings`` placements, and
the E²LM one-collective global readout, and per-member inits.

Needs ≥8 devices: the whole module SKIPS on the plain tier-1 run (1 real
CPU device) and is executed two ways instead —
``tests/test_executor.py::test_mesh_exec_suite_under_8_devices`` re-runs
it in a subprocess under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
and the CI mesh step runs it directly under the same flag.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_reduced_config, replace
from repro.core import elm, executor
from repro.core.averaging import broadcast_member_dim
from repro.core.cnn_elm import StackedMembers
from repro.core.e2lm import reduce_stats
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
from repro.data.partition import (epoch_batch_arrays, partition_iid,
                                  partition_unequal)
from repro.data.synthetic import make_extended_mnist, one_hot
from repro.distributed import sharding
from repro.analysis.hlo import (audit_executor, check_donation,
                                check_no_collectives, check_one_all_reduce)
from repro.models import cnn
from repro.optim.schedules import dynamic_paper
from repro.launch.mesh import auto_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(run via tests/test_executor.py's subprocess wrapper or the CI "
           "mesh step)")

CFG = get_reduced_config("cnn_elm_6c12c")
CFG_IMG = (CFG.image_size, CFG.image_size)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def ds():
    return make_extended_mnist(n_per_class=20, seed=0)


def _mesh(pods):
    return auto_mesh((pods,), ("pod",))


def _members_bit_equal(a_members, b_members):
    for a, b in zip(a_members, b_members):
        np.testing.assert_array_equal(np.asarray(a.beta), np.asarray(b.beta))
        for la, lb in zip(jax.tree.leaves(a.cnn_params),
                          jax.tree.leaves(b.cnn_params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("k,pods", [(4, 4),   # even split, no padding
                                    (3, 8),   # mesh larger than k -> pad 5
                                    (6, 4)])  # k % pods != 0 -> pad 2
def test_mesh_equals_stacked_elm_only(ds, k, pods):
    """epochs=0 across every padding regime: members bit-exact, averaged
    within f32 summation-order tolerance — padded members must be
    arithmetically invisible."""
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32)).run(parts, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                     mesh=_mesh(pods))).run(parts, KEY)
    assert me.stacked.k == k          # snapshot strips the padded slots
    _members_bit_equal(st.members, me.members)
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-5, atol=1e-6)
    # members and the averaged model leave the mesh: one device each, for
    # the eval and serving surfaces (XLA cannot partition a Pallas kernel)
    for leaf in jax.tree.leaves((me.stacked.cnn_params, me.stacked.beta,
                                 me.averaged.cnn_params, me.averaged.beta)):
        assert len(leaf.sharding.device_set) == 1, leaf.sharding


def test_mesh_equals_stacked_sgd(ds):
    """epochs=2 SGD on a padded mesh (k=3 over 8 pods): rtol 1e-4 vs the
    stacked path — the ISSUE acceptance bar."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    parts = partition_iid(ds.x, ds.y, k=3, seed=0)
    st = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr,
                                     batch_size=32)).run(parts, KEY)
    me = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr, batch_size=32,
                                     backend="mesh", mesh=_mesh(8))
                      ).run(parts, KEY)
    for a, b in zip(st.members, me.members):
        np.testing.assert_allclose(np.asarray(a.beta), np.asarray(b.beta),
                                   rtol=1e-4, atol=2e-5)
        for la, lb in zip(jax.tree.leaves(a.cnn_params),
                          jax.tree.leaves(b.cnn_params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-4, atol=1e-6)


def test_mesh_rounds_parity(ds):
    """rounds=2 on the mesh: one sync, hook-visible averaged models and
    the final result match the stacked rounds run."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    parts = partition_iid(ds.x, ds.y, k=4, seed=0)
    caught = {"stacked": {}, "mesh": {}}

    def run(backend, mesh=None):
        return AveragingRun(
            cfg, MapConfig(epochs=2, lr_schedule=lr, batch_size=32,
                           backend=backend, mesh=mesh),
            ReduceConfig(rounds=2)).run(
            parts, KEY,
            round_hook=lambda r, m: caught[backend].setdefault(r, m))

    st, me = run("stacked"), run("mesh", _mesh(4))
    assert st.round_syncs == me.round_syncs == 1
    assert len(me.rounds) == 2
    for r in (0, 1):
        np.testing.assert_allclose(
            np.asarray(caught["stacked"][r].beta),
            np.asarray(caught["mesh"][r].beta), rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-4, atol=2e-5)


def test_mesh_weighted_reduce_parity_unequal(ds):
    """Unequal shards + shard-weighted Reduce on a padded mesh: members
    bit-exact at epochs=0, the weighted one-all-reduce Reduce matches the
    host weighted mean."""
    uneq = partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32),
                      ReduceConfig(strategy="shard_weighted")).run(uneq, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                     mesh=_mesh(8)),
                      ReduceConfig(strategy="shard_weighted")).run(uneq, KEY)
    _members_bit_equal(st.members, me.members)
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-4, atol=1e-6)
    for la, lb in zip(jax.tree.leaves(st.averaged.cnn_params),
                      jax.tree.leaves(me.averaged.cnn_params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=1e-6)


def test_mesh_2d_extra_axes(ds):
    """A mesh with extra axes (pod, data) shards members on 'pod' only and
    stays equivalent; a mesh WITHOUT a 'pod' axis raises."""
    parts = partition_iid(ds.x, ds.y, k=4, seed=0)
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32)).run(parts, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                     mesh=auto_mesh((4, 2),
                                                        ("pod", "data")))
                      ).run(parts, KEY)
    _members_bit_equal(st.members, me.members)
    with pytest.raises(ValueError, match="'pod' axis"):
        AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                    mesh=auto_mesh((8,), ("data",)))
                     ).run(parts, KEY)


# ---------------------------------------------------------------------------
# The one-collective contract (HLO telemetry) + sharded intermediates
# ---------------------------------------------------------------------------

def _placed(mesh, k):
    ex = executor.StackedExecutor(mesh=mesh, backend="mesh")
    ex._begin(CFG, k)
    params_k = ex._put(broadcast_member_dim(cnn.init_params(CFG, KEY),
                                            ex._k_pad))
    F, C = cnn.feature_dim(CFG), CFG.num_classes
    stats_k = ex._put(elm.zero_stats_stacked(ex._k_pad, F, C))
    return ex, params_k, stats_k


def _lower(mesh, program, *args, **kw):
    with jax.set_mesh(mesh):
        return program.lower(*args, **kw)


def test_sync_and_reduce_lower_to_one_allreduce():
    """The acceptance assertion: the compiled inter-round sync AND the
    final Reduce each contain EXACTLY ONE all-reduce (the flat-psum
    contract), and the epoch scan contains ZERO collectives — all read
    off the compiled artifacts by the ``repro.analysis.hlo`` auditor."""
    mesh = _mesh(8)
    ex, params_k, stats_k = _placed(mesh, 3)
    w = ex._weights(None)

    sync = _lower(mesh, executor._sync, params_k, w)
    check = check_one_all_reduce(sync)
    assert check.ok, check

    beta_k = jax.device_put(
        jnp.zeros((8, cnn.feature_dim(CFG), CFG.num_classes)),
        NamedSharding(mesh, P("pod")))
    red = _lower(mesh, executor._reduce, (params_k, beta_k), w)
    check = check_one_all_reduce(red)
    assert check.ok, check

    B, nb = 16, 2
    xb = np.zeros((nb, 8, B) + CFG_IMG, np.float32)
    tb = np.zeros((nb, 8, B, CFG.num_classes), np.float32)
    mb = np.zeros((nb, 8), np.float32)
    cur = ex._put((xb, tb, mb), member_dim=1)
    ep = _lower(mesh, executor._stacked_epoch, CFG, params_k, stats_k, *cur,
                jnp.float32(0.0), solve_each_batch=True, use_pallas=False,
                masked=True)
    for check in (check_no_collectives(ep), check_donation(ep)):
        assert check.ok, check


def test_full_mesh_audit_is_green():
    """``audit_executor(..., "mesh")`` — the one-call CI entry point —
    passes every check on the real stacked executor's programs."""
    mesh = _mesh(8)
    for report in audit_executor(CFG, "mesh", mesh=mesh, k=3):
        assert report.ok, str(report)


def test_solve_and_params_stay_pod_sharded():
    """β is solved pod-sharded (each device factorises only its local
    members) and the placed params shard k_pad/pods members per device;
    only the snapshot leaves the mesh (and strips padding)."""
    mesh = _mesh(4)
    ex, params_k, stats_k = _placed(mesh, 6)             # k_pad = 8
    assert ex._k_pad == 8
    for leaf in jax.tree.leaves(params_k):
        assert leaf.sharding.spec[0] == "pod"
        assert len(leaf.addressable_shards) == 4
        assert leaf.addressable_shards[0].data.shape[0] == 2   # 8 / 4 pods
    beta_k = ex._call(executor._solve, stats_k, CFG.elm_lambda)
    assert beta_k.sharding.spec[0] == "pod"
    assert beta_k.shape[0] == 8
    sm = StackedMembers(*ex._handed_back((params_k, beta_k), member_dim=0))
    assert sm.k == 6                                      # padding stripped
    assert len(jax.tree.leaves(sm.cnn_params)[0].devices()) == 1  # unsharded


def test_member_dim_shardings_real_placement():
    """sharding.member_dim_shardings places real shards on the 8-device
    mesh: member dim split over 'pod', everything else replicated;
    indivisible member counts replicate (fallback)."""
    mesh = _mesh(8)
    tree = {"w": jnp.zeros((8, 5, 3)), "b": jnp.zeros((8,))}
    sh = sharding.member_dim_shardings(tree, mesh)
    assert sh["w"].spec == P("pod", None, None) and sh["b"].spec == P("pod")
    placed = jax.device_put(tree, sh)
    assert placed["w"].addressable_shards[0].data.shape == (1, 5, 3)
    # k=6 does not divide 8 pods -> replicated fallback
    sh6 = sharding.member_dim_shardings({"w": jnp.zeros((6, 5))}, mesh)
    assert sh6["w"].spec == P(None, None)


def test_e2lm_global_beta_one_psum_of_stats(ds):
    """The E²LM cross-member readout: ONE psum_stats reduce of the final
    epoch's per-member stats equals the host-side reduce+solve, padded
    members contributing nothing."""
    k, pods = 3, 8
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    init = cnn.init_params(CFG, KEY)
    ex = executor.StackedExecutor(mesh=_mesh(pods), backend="mesh")
    plan = executor.ExecutionPlan(epochs=0, batch_size=32, seed=1000)
    ex.execute(CFG, init, parts, plan)
    gb = np.asarray(ex.e2lm_global_beta())

    # host reference: per-member per-batch stats in the same order
    member_stats = []
    for i, p in enumerate(parts):
        xs, ys = epoch_batch_arrays(p, 32, seed=1000 + i)
        stats = elm.zero_stats(cnn.feature_dim(CFG), CFG.num_classes)
        for x, y in zip(xs, ys):
            h = cnn.features(CFG, init, jnp.asarray(x), use_pallas=False)
            t = jnp.asarray(one_hot(y, CFG.num_classes))
            stats = elm.add_stats(stats, elm.batch_stats(h, t,
                                                         use_pallas=False))
        member_stats.append(stats)
    ref = np.asarray(elm.solve_beta(reduce_stats(member_stats),
                                    CFG.elm_lambda))
    np.testing.assert_allclose(gb, ref, rtol=1e-4, atol=1e-4)


def test_trainer_average_step_mesh_variant():
    """trainer.make_average_step(mesh=...) — the launcher/dry-run facing
    averaging event — lowers to the same ONE-all-reduce program as the
    executor sync and matches the GSPMD variant numerically."""
    from repro.core import trainer
    mesh = _mesh(4)
    k = 8
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(k, 4, 3)).astype(np.float32)),
              "b": jnp.asarray(rng.normal(size=(k,)).astype(np.float32))}
    placed = jax.device_put(params,
                            sharding.member_dim_shardings(params, mesh))
    step = jax.jit(trainer.make_average_step(mesh=mesh))
    out = step(placed)
    ref = trainer.make_average_step()(params)
    for la, lb in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)
    check = check_one_all_reduce(step.lower(placed))
    assert check.ok, check
    # weighted: shard-size weights flow into the same single collective
    w = [float(i + 1) for i in range(k)]
    outw = jax.jit(trainer.make_average_step(weights=w, mesh=mesh))(placed)
    refw = trainer.make_average_step(weights=w)(params)
    np.testing.assert_allclose(np.asarray(jax.tree.leaves(outw)[0]),
                               np.asarray(jax.tree.leaves(refw)[0]),
                               rtol=1e-5, atol=1e-6)
    # a member count that doesn't divide the pod axis fails loudly
    with pytest.raises(ValueError, match="do not divide"):
        jax.jit(trainer.make_average_step(mesh=mesh))(
            {"w": jnp.zeros((5, 3))})


def test_mesh_unequal_sgd_padded(ds):
    """The nastiest combination: SGD epochs over UNEQUAL shards (per-batch
    mask) on a mesh where k doesn't divide the pods (member padding) —
    both masks compose and members still track the stacked path at
    rtol 1e-4."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    uneq = partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)   # k=3
    st = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr,
                                     batch_size=32),
                      ReduceConfig(strategy="shard_weighted")
                      ).run(uneq, KEY)
    me = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr, batch_size=32,
                                     backend="mesh", mesh=_mesh(4)),
                      ReduceConfig(strategy="shard_weighted")
                      ).run(uneq, KEY)                            # k_pad=4
    for a, b in zip(st.members, me.members):
        np.testing.assert_allclose(np.asarray(a.beta), np.asarray(b.beta),
                                   rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("epochs", [0, 2], ids=["e0", "sgd_chunked"])
def test_mesh_device_epoch_build_matches_host_build(ds, epochs,
                                                    monkeypatch):
    """Each pod gathers its own members' batches on the device: with k=3
    unequal shards on the 8-pod mesh (5 padded member slots), members, β
    and the averaged model are bit-identical to the forced host build."""
    cfg = replace(CFG, elm_lambda=1.0)
    uneq = partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)   # k=3
    run = AveragingRun(cfg, MapConfig(
        epochs=epochs, batch_size=32, backend="mesh", mesh=_mesh(8),
        chunk_batches=2 if epochs else None,
        lr_schedule=dynamic_paper(0.05) if epochs else None))

    def outputs(res):
        return jax.tree.leaves(jax.tree.map(np.asarray, (
            res.stacked.cnn_params, res.stacked.beta,
            res.averaged.cnn_params, res.averaged.beta)))

    dev = run.run(uneq, KEY)
    monkeypatch.setattr(executor, "_bytes_limit", lambda device: 2)
    host = run.run(uneq, KEY)
    builds = max(epochs, 1)
    assert (dev.device_epoch_builds, dev.host_epoch_builds) == (builds, 0)
    assert (host.device_epoch_builds, host.host_epoch_builds) == (0, builds)
    for a, b in zip(outputs(dev), outputs(host)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Hierarchical two-level Reduce on the ('host','pod') mesh (ISSUE-9):
# members shard over BOTH axes, every Reduce/sync is an intra-host psum
# followed by an inter-host psum — exactly TWO all-reduces — and the
# result matches the flat one-psum mesh within f32 summation-order
# tolerance (NOT bit-equal: the two-stage sum re-orders the partials)
# ---------------------------------------------------------------------------

def _mesh2d(hosts, pods):
    from repro.launch.mesh import make_member_mesh
    return make_member_mesh(hosts=hosts, pods=pods)


def test_make_member_mesh_host_topologies():
    """The launch helper builds the 2-D topology and validates it: pods
    defaults to devices/hosts, non-divisible fleets and pods-without-
    hosts fail loudly."""
    m = _mesh2d(2, 4)
    assert dict(m.shape) == {"host": 2, "pod": 4}
    assert dict(_mesh2d(2, None).shape) == {"host": 2, "pod": 4}
    with pytest.raises(ValueError, match="split"):
        _mesh2d(3, None)
    with pytest.raises(ValueError, match="hosts"):
        _mesh2d(None, 4)


def test_member_spec_resolves_both_topologies():
    """DEFAULT_RULES['member'] picks the ('host','pod') tuple candidate
    on a 2-D mesh and falls back to plain 'pod' on the 1-D mesh."""
    tree = {"w": jnp.zeros((8, 5))}
    sh2 = sharding.member_dim_shardings(tree, _mesh2d(2, 4))
    assert sh2["w"].spec == P(("host", "pod"), None)
    sh1 = sharding.member_dim_shardings(tree, _mesh(8))
    assert sh1["w"].spec == P("pod", None)


@pytest.mark.parametrize("k,hosts,pods", [(8, 2, 4),  # even, no padding
                                          (3, 2, 2),  # slots=4 -> pad 1
                                          (6, 4, 2)])  # slots=8 -> pad 2
def test_mesh_2d_equals_stacked_elm_only(ds, k, hosts, pods):
    """epochs=0 on the hierarchical mesh across padding regimes: members
    bit-exact vs stacked, the two-collective weighted average within f32
    tolerance — the pad-and-mask ghosts stay invisible to BOTH levels."""
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32)).run(parts, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                     mesh=_mesh2d(hosts, pods))
                      ).run(parts, KEY)
    assert me.stacked.k == k
    _members_bit_equal(st.members, me.members)
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-5, atol=1e-6)


def test_mesh_2d_weighted_parity_unequal(ds):
    """Unequal shards + shard_weighted on a padded 2-D mesh (k=3 over
    2x2 slots): the hierarchical weighted mean — weight totals riding the
    same two collectives — matches the host ``weighted_average_trees``
    reference that the stacked backend computes."""
    uneq = partition_unequal(ds.x, ds.y, [96, 64, 33], seed=1)
    st = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32),
                      ReduceConfig(strategy="shard_weighted")).run(uneq, KEY)
    me = AveragingRun(CFG, MapConfig(epochs=0, batch_size=32, backend="mesh",
                                     mesh=_mesh2d(2, 2)),
                      ReduceConfig(strategy="shard_weighted")).run(uneq, KEY)
    _members_bit_equal(st.members, me.members)
    for la, lb in zip(jax.tree.leaves((st.averaged.cnn_params,
                                       st.averaged.beta)),
                      jax.tree.leaves((me.averaged.cnn_params,
                                       me.averaged.beta))):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=1e-6)


def test_mesh_2d_flat_vs_hier_full_run(ds):
    """The tentpole parity bar: the SAME run on the flat 1-D mesh and the
    2-D ('host','pod') mesh produces bit-equal MEMBERS (the Map phase is
    topology-blind) and averaged models within f32 summation-order
    tolerance — the hierarchical Reduce only re-orders the f32 partial
    sums, so bit-equality is deliberately NOT claimed."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    parts = partition_iid(ds.x, ds.y, k=8, seed=0)
    mk = lambda mesh: AveragingRun(
        cfg, MapConfig(epochs=1, lr_schedule=lr, batch_size=32,
                       backend="mesh", mesh=mesh), ReduceConfig(rounds=1))
    flat = mk(_mesh(8)).run(parts, KEY)
    hier = mk(_mesh2d(2, 4)).run(parts, KEY)
    _members_bit_equal(flat.members, hier.members)
    for la, lb in zip(jax.tree.leaves((flat.averaged.cnn_params,
                                       flat.averaged.beta)),
                      jax.tree.leaves((hier.averaged.cnn_params,
                                       hier.averaged.beta))):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)


def test_mesh_2d_rounds_parity(ds):
    """rounds=2 on the hierarchical mesh: the two-collective sync feeds
    round 1 and the final model still tracks the stacked rounds run."""
    cfg = replace(CFG, elm_lambda=1.0)
    lr = dynamic_paper(0.05)
    parts = partition_iid(ds.x, ds.y, k=4, seed=0)
    st = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr,
                                     batch_size=32),
                      ReduceConfig(rounds=2)).run(parts, KEY)
    me = AveragingRun(cfg, MapConfig(epochs=2, lr_schedule=lr, batch_size=32,
                                     backend="mesh", mesh=_mesh2d(2, 2)),
                      ReduceConfig(rounds=2)).run(parts, KEY)
    assert st.round_syncs == me.round_syncs == 1
    np.testing.assert_allclose(np.asarray(st.averaged.beta),
                               np.asarray(me.averaged.beta),
                               rtol=1e-4, atol=2e-5)


def test_hier_sync_and_reduce_lower_to_two_allreduces():
    """The acceptance assertion for the hierarchical topology: sync AND
    Reduce compile to EXACTLY TWO all-reduces (intra-host + inter-host,
    data-dependent so XLA cannot fuse them), the epoch scan stays
    collective-free, and the one-call auditor is green on BOTH
    topologies."""
    from repro.analysis.hlo import check_two_all_reduces
    mesh = _mesh2d(2, 4)
    ex, params_k, _ = _placed(mesh, 3)                    # k_pad = 8
    w = ex._weights(None)

    sync = _lower(mesh, executor._sync, params_k, w)
    check = check_two_all_reduces(sync)
    assert check.ok, check

    beta_k = jax.device_put(
        jnp.zeros((8, cnn.feature_dim(CFG), CFG.num_classes)),
        NamedSharding(mesh, P(("host", "pod"))))
    red = _lower(mesh, executor._reduce, (params_k, beta_k), w)
    check = check_two_all_reduces(red)
    assert check.ok, check

    for report in audit_executor(CFG, "mesh", mesh=mesh, k=3):
        assert report.ok, str(report)
    # the flat 1-D audit still enforces ONE collective
    for report in audit_executor(CFG, "mesh", mesh=_mesh(8), k=3):
        assert report.ok, str(report)


@pytest.mark.parametrize("pods", [4, 8])
def test_member_init_on_a_mesh_matches_one_device(ds, pods):
    """Per-member inits (the streaming runner's block continuation) on a
    multi-device mesh, padded slots included (k=3): members, β and the
    averaged model match the one-device run."""
    cfg = replace(CFG, elm_lambda=1.0)
    parts = partition_iid(ds.x, ds.y, k=3, seed=0)
    inits = [cnn.init_params(cfg, jax.random.PRNGKey(i)) for i in range(3)]
    plan = executor.ExecutionPlan(epochs=1, lr_schedule=dynamic_paper(0.05),
                                  batch_size=32, member_init=inits)

    def run(mesh):
        out = executor.StackedExecutor(mesh=mesh).execute(
            cfg, inits[0], parts, plan)
        return out.members

    one, many = run(_mesh(1)), run(_mesh(pods))
    for a, b in zip(one, many):
        for la, lb in zip(jax.tree.leaves((a.cnn_params, a.beta)),
                          jax.tree.leaves((b.cnn_params, b.beta))):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)
