"""The pluggable Reduce-strategy registry (ISSUE-10 acceptance): string/
instance/legacy-sequence resolution with the pinned error + deprecation
surface, AdaBoost ``boosted`` member weights (property-tested + backend
parity), the Dirichlet(α) non-IID partitioner (conservation, skew
monotonicity, determinism), gossip ring consensus (geometric convergence
onto the one-psum average, the psum-free compiled sync), elastic runs
under registry weights, the streaming rejections, and the
``unregistered-reduce-strategy`` lint rule."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_reduced_config, replace
from repro.core import reduce_strategies as rs
from repro.core.averaging import (gossip_member_dim, gossip_mixing_lambda2,
                                  weighted_average_trees)
from repro.core.runner import (AveragingRun, ElasticEvent, ElasticSchedule,
                               MapConfig, ReduceConfig)
from repro.data.partition import (Partition, partition_dirichlet,
                                  partition_iid)
from repro.data.synthetic import make_extended_mnist
from repro.optim.schedules import dynamic_paper

CFG = replace(get_reduced_config("cnn_elm_6c12c"), elm_lambda=1.0)
KEY = jax.random.PRNGKey(0)
LR = dynamic_paper(0.05)


@pytest.fixture(scope="module")
def ds():
    return make_extended_mnist(n_per_class=20, seed=0)


@pytest.fixture(scope="module")
def parts(ds):
    return partition_iid(ds.x, ds.y, k=3, seed=0)


@pytest.fixture(scope="module")
def val():
    v = make_extended_mnist(n_per_class=6, seed=7)
    return Partition(v.x, v.y)


def _leaves(model):
    return jax.tree.leaves((model.cnn_params, model.beta))


def _assert_models_close(a, b, rtol, atol):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# registry + resolution surface
# ---------------------------------------------------------------------------

def test_registry_keys_are_the_four_builtins():
    assert rs.registry_keys() == ("boosted", "gossip", "shard_weighted",
                                  "uniform")


def test_resolve_string_and_instance_passthrough():
    assert isinstance(rs.resolve("uniform"), rs.Uniform)
    g = rs.Gossip(rounds=7)
    assert rs.resolve(g) is g


def test_resolve_unknown_string_lists_registry_dynamically():
    with pytest.raises(ValueError, match="uniform"):
        rs.resolve("by_shard")

    @rs.register("test_only_strategy")
    class _TestOnly(rs.ReduceStrategy):
        def weights(self, ctx):
            return None

    try:
        # a newly registered strategy resolves AND shows up in the error
        assert isinstance(rs.resolve("test_only_strategy"), _TestOnly)
        with pytest.raises(ValueError, match="test_only_strategy"):
            rs.resolve("nope")
    finally:
        del rs.REGISTRY["test_only_strategy"]


def test_resolve_class_not_instance_raises():
    with pytest.raises(ValueError, match="INSTANCE"):
        rs.resolve(rs.Uniform)


def test_resolve_sequence_deprecation_to_explicit_weights():
    with pytest.deprecated_call():
        strat = rs.resolve([0.2, 0.8])
    assert isinstance(strat, rs.ExplicitWeights)
    ctx = rs.ReduceContext(num_members=2)
    np.testing.assert_allclose(strat.weights(ctx), [0.2, 0.8])
    with pytest.raises(ValueError, match="2 explicit weights for 3"):
        strat.weights(rs.ReduceContext(num_members=3))


def test_reduce_config_legacy_sequence_warns_and_still_runs(parts):
    """The pinned deprecation path: a bare weight sequence keeps working
    end to end, announced once at config construction."""
    with pytest.deprecated_call():
        rc = ReduceConfig(strategy=[3.0, 1.0, 1.0])
    assert rc.resolve_weights(parts) == [3.0, 1.0, 1.0]
    res = AveragingRun(CFG, MapConfig(epochs=0, batch_size=16), rc).run(
        parts, KEY)
    ref = weighted_average_trees([m.beta for m in res.members],
                                 [3.0, 1.0, 1.0])
    np.testing.assert_allclose(np.asarray(res.averaged.beta),
                               np.asarray(ref), rtol=1e-6, atol=1e-7)


def test_boosted_requires_validation_slice(val):
    with pytest.raises(ValueError, match="validation"):
        ReduceConfig(strategy="boosted")
    ReduceConfig(strategy="boosted", validation=val)     # ok


def test_validation_slice_rejected_for_non_scoring_strategy(val):
    with pytest.raises(ValueError, match="validation"):
        ReduceConfig(strategy="uniform", validation=val)


def test_elastic_rejects_explicit_and_gossip():
    sched = ElasticSchedule((ElasticEvent(after_round=0, leave=("m0",)),))
    with pytest.raises(ValueError, match="explicit weight"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            ReduceConfig(rounds=2, strategy=[1.0, 2.0], elastic=sched)
    with pytest.raises(ValueError, match="gossip"):
        ReduceConfig(rounds=2, strategy="gossip", elastic=sched)


def test_gossip_rejected_on_sequential_backend(parts):
    run = AveragingRun(CFG, MapConfig(epochs=0, batch_size=16,
                                      backend="sequential"),
                       ReduceConfig(strategy="gossip"))
    with pytest.raises(ValueError, match="sequential"):
        run.run(parts, KEY)


def test_streaming_rejects_gossip_and_boosted(val):
    from repro.stream.run import StreamConfig, StreamingRun
    with pytest.raises(ValueError, match="gossip"):
        StreamingRun(CFG, MapConfig(epochs=0, batch_size=16),
                     ReduceConfig(strategy="gossip"), StreamConfig())
    with pytest.raises(ValueError, match="boosted"):
        StreamingRun(CFG, MapConfig(epochs=0, batch_size=16),
                     ReduceConfig(strategy="boosted", validation=val),
                     StreamConfig())


# ---------------------------------------------------------------------------
# boosted weights — properties + parity
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(k=st.integers(2, 12))
def test_boosted_uniform_error_gives_uniform_weights(k):
    w = rs.boosted_weights(np.full(k, 0.3))
    np.testing.assert_allclose(w, np.full(k, 1.0 / k), rtol=1e-12)


@settings(max_examples=20, deadline=None)
@given(k=st.integers(2, 12), seed=st.integers(0, 999))
def test_boosted_weights_positive_normalized_monotone(k, seed):
    rng = np.random.default_rng(seed)
    errs = rng.uniform(0.0, 1.0, size=k)     # includes 0/1 edge regions
    w = np.asarray(rs.boosted_weights(errs))
    assert w.shape == (k,)
    assert np.all(w > 0)                     # the floor bites, never zero
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-12)
    order = np.argsort(errs)
    # lower validation error never gets less weight
    assert np.all(np.diff(w[order]) <= 1e-12)


def test_boosted_backend_parity_epochs0(parts, val):
    """epochs=0 removes SGD noise: the boosted weights (host f64 from
    device argmax) and the weighted average must agree tightly across
    sequential and stacked."""
    mk = lambda b: AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16, backend=b),
        ReduceConfig(strategy="boosted", validation=val))
    seq = mk("sequential").run(parts, KEY)
    stk = mk("stacked").run(parts, KEY)
    _assert_models_close(seq.averaged, stk.averaged, rtol=1e-5, atol=1e-6)


def test_boosted_upweights_the_better_member(ds, val):
    """A member trained on garbage labels must get LESS weight than its
    siblings: the boosted average sits closer to the good members'
    average than the uniform one does."""
    rng = np.random.default_rng(3)
    parts = partition_iid(ds.x, ds.y, k=3, seed=0)
    bad = Partition(parts[2].x,
                    rng.integers(0, CFG.num_classes, len(parts[2].y)))
    skew = [parts[0], parts[1], bad]
    mk = lambda strat, **kw: AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16),
        ReduceConfig(strategy=strat, **kw)).run(skew, KEY)
    uni = mk("uniform")
    boo = mk("boosted", validation=val)
    good = weighted_average_trees([m.beta for m in uni.members[:2]],
                                  [1.0, 1.0])
    d_uni = float(jnp.abs(uni.averaged.beta - good).max())
    d_boo = float(jnp.abs(boo.averaged.beta - good).max())
    assert d_boo < d_uni


# ---------------------------------------------------------------------------
# Dirichlet partitioner — properties
# ---------------------------------------------------------------------------

def _tv_skew(parts, num_classes):
    ally = np.concatenate([p.y for p in parts])
    glob = np.bincount(ally, minlength=num_classes) / len(ally)
    return float(np.mean([
        0.5 * np.abs(np.bincount(p.y, minlength=num_classes) /
                     max(len(p.y), 1) - glob).sum() for p in parts]))


@settings(max_examples=10, deadline=None)
@given(k=st.integers(2, 8), seed=st.integers(0, 99))
def test_dirichlet_rows_conserved_and_deterministic(k, seed):
    ds = make_extended_mnist(n_per_class=15, seed=1)
    a = partition_dirichlet(ds.x, ds.y, k=k, alpha=0.5, seed=seed)
    b = partition_dirichlet(ds.x, ds.y, k=k, alpha=0.5, seed=seed)
    assert sum(len(p.x) for p in a) == len(ds.x)
    rows = np.sort(np.concatenate([p.x.reshape(len(p.x), -1).sum(1)
                                   for p in a]))
    np.testing.assert_allclose(
        rows, np.sort(ds.x.reshape(len(ds.x), -1).sum(1)), rtol=1e-6)
    for pa, pb in zip(a, b):                 # seeded determinism
        np.testing.assert_array_equal(pa.x, pb.x)
        np.testing.assert_array_equal(pa.y, pb.y)


def test_dirichlet_skew_monotone_in_alpha(ds):
    tvs = [_tv_skew(partition_dirichlet(ds.x, ds.y, k=6, alpha=a, seed=0),
                    CFG.num_classes) for a in (100.0, 1.0, 0.1)]
    assert tvs[0] < tvs[1] < tvs[2]
    assert tvs[0] < 0.2                      # α=100 ≈ IID


def test_dirichlet_min_rows_and_validation(ds):
    parts = partition_dirichlet(ds.x, ds.y, k=4, alpha=0.1, seed=0,
                                min_rows=5)
    assert all(len(p.x) >= 5 for p in parts)
    with pytest.raises(ValueError, match="alpha"):
        partition_dirichlet(ds.x, ds.y, k=4, alpha=0.0)
    with pytest.raises(ValueError, match="k"):
        partition_dirichlet(ds.x, ds.y, k=0, alpha=1.0)


# ---------------------------------------------------------------------------
# gossip — consensus properties on the member-dim emulation
# ---------------------------------------------------------------------------

def test_gossip_published_equals_weighted_mean():
    """The invariant-sum readout is the EXACT weighted mean at any round
    count — mixing only redistributes, never loses, mass."""
    k = 5
    keys = jax.random.split(jax.random.PRNGKey(1), k)
    tree = {"a": jnp.stack([jax.random.normal(c, (4, 3)) for c in keys]),
            "b": jnp.stack([jax.random.normal(c, (7,)) * 3 for c in keys])}
    w = jnp.asarray([1.0, 2.0, 0.5, 4.0, 1.5])
    ref = jax.tree.map(
        lambda a: jnp.tensordot(w / w.sum(), a, axes=1), tree)
    for rounds in (1, 2, 5):
        _, pub = gossip_member_dim(tree, w, rounds)
        for lp, lr in zip(jax.tree.leaves(pub), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                                       rtol=1e-5, atol=1e-6)


def test_gossip_iterates_converge_geometrically():
    """Per-member consensus gap shrinks like λ₂^T (3-point ring
    stencil): monotone decreasing and within a small factor of the
    spectral prediction."""
    k = 8
    keys = jax.random.split(jax.random.PRNGKey(2), k)
    tree = {"p": jnp.stack([jax.random.normal(c, (6,)) for c in keys])}
    mean = jax.tree.map(lambda a: jnp.mean(a, axis=0), tree)
    lam = gossip_mixing_lambda2(k)
    assert 0 < lam < 1

    def gap(T):
        it, _ = gossip_member_dim(tree, None, T)
        return max(float(jnp.max(jnp.abs(l - m[None]))) for l, m in
                   zip(jax.tree.leaves(it), jax.tree.leaves(mean)))

    gaps = [gap(T) for T in (1, 2, 4, 8, 16)]
    assert all(a > b for a, b in zip(gaps, gaps[1:]))
    # geometric envelope: gap(16)/gap(8) tracks λ₂^8 within a factor 5
    ratio = gaps[4] / gaps[3]
    assert ratio < min(5 * lam ** 8, 1.0)


def test_gossip_stacked_run_matches_uniform_average(parts):
    """End to end on the stacked backend: the gossip Reduce's published
    model is the uniform average up to f32 mixing noise."""
    mk = lambda rc: AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16), rc).run(parts, KEY)
    uni = mk(ReduceConfig(strategy="uniform"))
    gos = mk(ReduceConfig(strategy=rs.Gossip(rounds=6)))
    for a, b in zip(uni.members, gos.members):   # Map is strategy-blind
        for la, lb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    _assert_models_close(uni.averaged, gos.averaged, rtol=1e-5, atol=1e-6)


def test_gossip_rejects_checkpoint(parts, tmp_path):
    from repro.core.runner import CheckpointConfig
    run = AveragingRun(CFG, MapConfig(epochs=0, batch_size=16),
                       ReduceConfig(strategy="gossip"))
    with pytest.raises(ValueError, match="checkpoint"):
        run.run(parts, KEY,
                checkpoint=CheckpointConfig(dir=str(tmp_path)))


def test_uniform_string_vs_instance_bit_identical(parts):
    mk = lambda strat: AveragingRun(
        CFG, MapConfig(epochs=1, lr_schedule=LR, batch_size=16),
        ReduceConfig(strategy=strat)).run(parts, KEY)
    a, b = mk("uniform"), mk(rs.Uniform())
    for la, lb in zip(_leaves(a.averaged), _leaves(b.averaged)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# elastic runs under registry weights
# ---------------------------------------------------------------------------

def test_elastic_shard_weighted_seq_matches_stacked(ds, val):
    """The ISSUE-10 elastic regression: registry strategies drive the
    per-block cumulative weights, leavers' retained contributions
    included, identically on both host backends."""
    parts = partition_iid(ds.x, ds.y, k=3, seed=0)
    sched = ElasticSchedule((ElasticEvent(after_round=0, leave=("m2",),
                                          join=(parts[2],)),))
    for strat, kw in (("shard_weighted", {}),
                      ("boosted", {"validation": val})):
        mk = lambda b: AveragingRun(
            CFG, MapConfig(epochs=2, lr_schedule=LR, batch_size=16,
                           backend=b),
            ReduceConfig(rounds=2, elastic=sched, strategy=strat, **kw))
        seq = mk("sequential").run(parts, KEY)
        stk = mk("stacked").run(parts, KEY)
        assert sorted(seq.members) == sorted(stk.members)
        for n in seq.members:
            np.testing.assert_allclose(
                np.asarray(seq.members[n].beta),
                np.asarray(stk.members[n].beta), rtol=1e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(seq.averaged.beta),
                                   np.asarray(stk.averaged.beta),
                                   rtol=1e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# mesh: ring collectives + parity (needs >= 8 simulated devices)
# ---------------------------------------------------------------------------

mesh_only = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI mesh step)")


@mesh_only
def test_mesh_gossip_matches_stacked_and_audits_psum_free(ds):
    from repro.analysis.hlo import (audit_executor, lower_stacked_programs,
                                    ppermute_count)
    from repro.launch.mesh import make_member_mesh

    k, rounds = 8, 3
    parts = partition_iid(ds.x, ds.y, k=k, seed=0)
    mesh = make_member_mesh(num_pods=k)
    mk = lambda b, **kw: AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16, backend=b, **kw),
        ReduceConfig(strategy=rs.Gossip(rounds=rounds))).run(parts, KEY)
    stk = mk("stacked")
    msh = mk("mesh", mesh=mesh)
    _assert_models_close(stk.averaged, msh.averaged, rtol=1e-5, atol=1e-6)

    # the compiled ring program: 2 permutes per round, zero all-reduces
    reports = audit_executor(CFG, "mesh", mesh=mesh, k=k,
                             gossip_rounds=rounds)
    by_name = {r.program: r for r in reports}
    assert by_name["mesh/_gossip"].ok
    _, programs = lower_stacked_programs(CFG, "mesh", mesh=mesh, k=k,
                                         gossip_rounds=rounds)
    hlo = programs["_gossip"].compile().as_text()
    assert ppermute_count(hlo) == 2 * rounds
    assert "all-reduce" not in hlo


@mesh_only
def test_mesh_gossip_rejects_hierarchical_mesh(ds):
    from repro.launch.mesh import make_member_mesh
    parts = partition_iid(ds.x, ds.y, k=4, seed=0)
    mesh2d = make_member_mesh(hosts=2, pods=4)
    run = AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16, backend="mesh",
                       mesh=mesh2d),
        ReduceConfig(strategy="gossip"))
    with pytest.raises(ValueError, match="pod"):
        run.run(parts, KEY)


@mesh_only
def test_mesh_boosted_matches_stacked_bitwise_weights(ds, val):
    parts = partition_iid(ds.x, ds.y, k=4, seed=0)
    mk = lambda b, **kw: AveragingRun(
        CFG, MapConfig(epochs=0, batch_size=16, backend=b, **kw),
        ReduceConfig(strategy="boosted", validation=val)).run(parts, KEY)
    stk = mk("stacked")
    msh = mk("mesh")
    _assert_models_close(stk.averaged, msh.averaged, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the unregistered-reduce-strategy lint rule
# ---------------------------------------------------------------------------

def test_lint_flags_unregistered_strategy_literal(tmp_path):
    from repro.analysis import lint
    snippet = tmp_path / "snippet.py"
    snippet.write_text(
        "from repro.core.runner import ReduceConfig\n"
        "ok = ReduceConfig(strategy='boosted')\n"
        "bad = ReduceConfig(strategy='by_shard')\n"
        "hushed = ReduceConfig(strategy='by_shard')"
        "  # repro: allow(unregistered-reduce-strategy)\n")
    rep = lint.lint_paths([snippet])
    found = [f for f in rep.findings
             if f.rule == "unregistered-reduce-strategy"]
    assert len(found) == 1 and found[0].line == 3
    assert "by_shard" in found[0].message
    assert "uniform" in found[0].message      # registry keys in the hint
    assert rep.suppressed >= 1
