"""ELM core + E²LM MapReduce properties (paper §2.2, Eq. 1-5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import e2lm, elm
from repro.layers.norms import optimal_tanh
from repro.launch.mesh import auto_mesh

RNG = np.random.default_rng(42)


def _data(n, L, C):
    h = jnp.asarray(RNG.normal(size=(n, L)).astype(np.float32))
    w_true = RNG.normal(size=(L, C)).astype(np.float32)
    t = jnp.asarray(np.asarray(optimal_tanh(h)) @ w_true
                    + 0.01 * RNG.normal(size=(n, C)).astype(np.float32))
    return h, t


def test_solve_beta_recovers_linear_map():
    h, t = _data(2000, 30, 4)
    stats = elm.batch_stats(h, t)
    beta = elm.solve_beta(stats, lam=1e4)
    pred = elm.predict(h, beta)
    resid = float(jnp.mean(jnp.square(pred - t)))
    assert resid < 1e-2, resid


def test_solve_beta_equals_normal_equations():
    h, t = _data(500, 20, 3)
    ha = optimal_tanh(h)
    stats = elm.batch_stats(h, t)
    beta = elm.solve_beta(stats, lam=10.0)
    ref = np.linalg.solve(np.asarray(ha.T @ ha) + np.eye(20) / 10.0,
                          np.asarray(ha.T @ t))
    np.testing.assert_allclose(np.asarray(beta), ref, rtol=1e-3, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(k=st.integers(2, 7), n=st.integers(40, 200))
def test_e2lm_partition_invariance(k, n):
    """Eq. 3/4: U,V sums decompose EXACTLY over arbitrary partitions —
    the property that makes classifier-level MapReduce lossless for ELM."""
    rng = np.random.default_rng(k * 1000 + n)
    h = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    whole = elm.batch_stats(h, t)
    cuts = sorted(rng.choice(np.arange(1, n), size=k - 1, replace=False))
    bounds = [0, *cuts, n]
    shards = [elm.batch_stats(h[a:b], t[a:b])
              for a, b in zip(bounds[:-1], bounds[1:])]
    merged = e2lm.reduce_stats(shards)
    np.testing.assert_allclose(np.asarray(merged.u), np.asarray(whole.u),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(merged.v), np.asarray(whole.v),
                               rtol=1e-4, atol=1e-3)
    assert int(merged.n) == n
    b1 = elm.solve_beta(whole, 100.0)
    b2 = elm.solve_beta(merged, 100.0)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2),
                               rtol=1e-3, atol=1e-4)


def test_oselm_matches_batch_solution():
    """OS-ELM streaming updates converge to the batch ridge solution."""
    h, t = _data(400, 12, 2)
    lam = 50.0
    state = e2lm.oselm_init(12, 2, lam)
    for i in range(0, 400, 50):
        state = e2lm.oselm_update(state, h[i:i + 50], t[i:i + 50])
    batch_beta = elm.solve_beta(elm.batch_stats(h, t), lam)
    np.testing.assert_allclose(np.asarray(state.beta), np.asarray(batch_beta),
                               rtol=5e-2, atol=5e-3)


def test_elm_loss_matches_paper_eq16():
    h, t = _data(64, 8, 2)
    beta = jnp.asarray(RNG.normal(size=(8, 2)).astype(np.float32))
    loss = elm.elm_loss(h, beta, t)
    ref = 0.5 * np.mean(np.sum((np.asarray(optimal_tanh(h) @ beta) -
                                np.asarray(t)) ** 2, axis=-1))
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)


def test_optimal_tanh_constants():
    """1.7159 * tanh(2/3 x) — LeCun's efficient-backprop activation."""
    x = jnp.asarray([0.0, 1.0, -1.0, 10.0])
    y = np.asarray(optimal_tanh(x))
    np.testing.assert_allclose(y[0], 0.0, atol=1e-7)
    np.testing.assert_allclose(y[3], 1.7159, rtol=1e-3)  # saturation
    np.testing.assert_allclose(y[1], -y[2], rtol=1e-6)   # odd function
    np.testing.assert_allclose(y[1], 1.7159 * np.tanh(2 / 3), rtol=1e-5)


def test_psum_stats_inside_shard_map():
    """E²LM map inside SPMD: per-device partial stats + one psum == global."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    n_dev = len(jax.devices())
    mesh = auto_mesh((n_dev,), ("data",))
    n = 8 * n_dev
    h = jnp.asarray(RNG.normal(size=(n, 6)).astype(np.float32))
    t = jnp.asarray(RNG.normal(size=(n, 2)).astype(np.float32))

    def local(h_loc, t_loc):
        return e2lm.psum_stats(elm.batch_stats(h_loc, t_loc), "data")

    fn = shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=P())
    out = fn(h, t)
    whole = elm.batch_stats(h, t)
    np.testing.assert_allclose(np.asarray(out.u), np.asarray(whole.u),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(out.v), np.asarray(whole.v),
                               rtol=1e-4, atol=1e-3)
