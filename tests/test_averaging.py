"""Weight-averaging (the paper's Reduce) properties."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.averaging import (average_member_dim, average_trees,
                                  broadcast_member_dim, weighted_average_trees)
from repro.launch.mesh import auto_mesh

RNG = np.random.default_rng(7)


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "b": {"inner": jnp.asarray(rng.normal(size=(3,)).astype(np.float32))}}


def test_average_trees_is_mean():
    ms = [_tree(i) for i in range(5)]
    avg = average_trees(ms)
    ref = np.mean([np.asarray(m["w"]) for m in ms], axis=0)
    np.testing.assert_allclose(np.asarray(avg["w"]), ref, rtol=1e-6)


def test_average_idempotent():
    m = _tree(0)
    avg = average_trees([m, m, m])
    np.testing.assert_allclose(np.asarray(avg["w"]), np.asarray(m["w"]),
                               rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 6))
def test_member_dim_equals_host_average(k):
    """The multi-pod Reduce (mean over leading dim) == the host-level
    list reduce (Alg. 2 lines 18-20)."""
    ms = [_tree(100 + i) for i in range(k)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ms)
    a1 = average_member_dim(stacked)
    a2 = average_trees(ms)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=1e-6), a1, a2)


def test_broadcast_roundtrip():
    m = _tree(3)
    stacked = broadcast_member_dim(m, 4)
    assert jax.tree.leaves(stacked)[0].shape[0] == 4
    back = average_member_dim(stacked)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=1e-6), back, m)


def test_weighted_average_unequal_shards():
    a, b = _tree(1), _tree(2)
    w = weighted_average_trees([a, b], [3.0, 1.0])
    ref = 0.75 * np.asarray(a["w"]) + 0.25 * np.asarray(b["w"])
    np.testing.assert_allclose(np.asarray(w["w"]), ref, rtol=1e-6)


def test_averaging_linear_models_equals_averaging_predictions():
    """For linear models, weight averaging == prediction averaging — the
    law-of-large-numbers argument in the paper's §2.1 holds exactly."""
    x = jnp.asarray(RNG.normal(size=(32, 4)).astype(np.float32))
    ws = [jnp.asarray(RNG.normal(size=(4, 2)).astype(np.float32))
          for _ in range(5)]
    avg_w = average_trees(ws)
    pred_of_avg = x @ avg_w
    avg_of_pred = sum(x @ w for w in ws) / 5.0
    np.testing.assert_allclose(np.asarray(pred_of_avg),
                               np.asarray(avg_of_pred), rtol=1e-5, atol=1e-6)


def test_average_preserves_dtype():
    ms = [jax.tree.map(lambda a: a.astype(jnp.bfloat16), _tree(i))
          for i in range(3)]
    avg = average_trees(ms)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(avg))


def test_average_bf16_accumulates_in_f32():
    """Regression: the member sum used to accumulate in leaf dtype, so a
    bf16 backbone lost ~k·2⁻⁸ relative precision before the divide. The f32
    accumulator must land on the f32-exact mean (to one final bf16 round)
    and agree with the weighted path under uniform weights."""
    rng = np.random.default_rng(11)
    k = 16
    ms = [{"w": jnp.asarray(
        rng.normal(loc=1.0, scale=0.05, size=(16, 16)).astype(np.float32)
    ).astype(jnp.bfloat16)} for _ in range(k)]
    avg = average_trees(ms)
    ref = np.mean([np.asarray(m["w"], np.float32) for m in ms], axis=0)
    # within one bf16 ulp of the f32-exact mean (values are ~1.0, ulp 2⁻⁸)
    np.testing.assert_allclose(np.asarray(avg["w"], np.float32), ref,
                               atol=2 ** -8, rtol=0)
    # uniform weights ≡ the weighted path (both scale/accumulate in f32)
    wavg = weighted_average_trees(ms, [1.0] * k)
    np.testing.assert_array_equal(np.asarray(avg["w"], np.float32),
                                  np.asarray(wavg["w"], np.float32))


def test_psum_weighted_mean_members_single_collective_semantics():
    """The flat-psum weighted mean (the mesh executor's Reduce/sync
    primitive) inside shard_map over the member dim == the host weighted
    member-dim mean; zero weights drop members (the padded-member
    contract)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.averaging import psum_weighted_mean_members
    from jax import shard_map

    n_dev = len(jax.devices())
    mesh = auto_mesh((n_dev,), ("pod",))
    k = 2 * n_dev
    ms = [_tree(200 + i) for i in range(k)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ms)
    w = np.zeros((k,), np.float32)
    w[:k - 1] = np.arange(1, k, dtype=np.float32)   # last member dropped

    fn = shard_map(
        lambda t, wl: psum_weighted_mean_members(t, wl, "pod"),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda a: P("pod", *([None] * (a.ndim - 1))),
                               stacked), P("pod")),
        out_specs=jax.tree.map(lambda a: P(*([None] * (a.ndim - 1))),
                               stacked))
    out = fn(jax.device_put(stacked,
                            jax.tree.map(lambda s: NamedSharding(mesh, s),
                                         jax.tree.map(
                                             lambda a: P("pod", *([None] * (
                                                 a.ndim - 1))), stacked),
                                         is_leaf=lambda x: isinstance(x, P))),
             jax.device_put(jnp.asarray(w), NamedSharding(mesh, P("pod"))))
    ref = average_member_dim(stacked, weights=w)
    for la, lb in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)


def test_make_average_step_mesh_validates_contract():
    """trainer.make_average_step(mesh=): a mesh without a 'pod' axis and a
    member count that doesn't divide the pod axis both fail with clear
    errors (the mesh executor's contract), not deep shard_map KeyErrors."""
    import pytest
    from repro.core import trainer

    with pytest.raises(ValueError, match="'pod' axis"):
        trainer.make_average_step(mesh=auto_mesh((1,), ("data",)))
    n = len(jax.devices())
    step = trainer.make_average_step(mesh=auto_mesh((n,), ("pod",)))
    if n > 1:   # with 1 pod every member count divides
        with pytest.raises(ValueError, match="do not divide"):
            step({"w": jnp.zeros((n + 1, 3))})
    else:       # degenerate mesh still averages correctly
        out = step({"w": jnp.asarray([[1.0, 3.0], [3.0, 5.0]])})
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   [[2.0, 4.0], [2.0, 4.0]])
