"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.conv2d import ops as conv_ops, ref as conv_ref
from repro.kernels.conv2d.kernel import blocked_matmul
from repro.kernels.elm_stats import ops as elm_ops, ref as elm_ref
from repro.kernels.swa_attention import ops as swa_ops, ref as swa_ref

RNG = np.random.default_rng(0)


def _rand(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,w,cin,k,cout", [
    (1, 8, 8, 1, 3, 4),
    (2, 28, 28, 1, 5, 6),     # the paper's input geometry
    (3, 12, 12, 6, 5, 12),    # the paper's second stage
    (2, 9, 9, 3, 5, 9),
])
def test_conv2d_matches_ref(b, h, w, cin, k, cout):
    x = _rand(b, h, w, cin)
    wgt = _rand(k, k, cin, cout)
    out = conv_ops.conv2d_valid(x, wgt, use_pallas=True)
    ref = conv_ref.conv2d_valid_ref(x, wgt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocked_matmul_dtypes(dtype):
    x = _rand(200, 70).astype(dtype)
    w = _rand(70, 130).astype(dtype)
    out = blocked_matmul(x, w, interpret=True)
    ref = (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 150), k=st.integers(1, 80), n=st.integers(1, 90))
def test_blocked_matmul_property(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    out = blocked_matmul(x, w, bm=32, bn=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)


def test_features_grad_through_pallas_matches_xla():
    """jax.grad of the ELM loss through the Pallas conv (custom VJP, two
    more blocked GEMMs) equals the XLA-conv gradient at the 6c-12c width."""
    from repro.configs.base import get_config
    from repro.core import elm
    from repro.models import cnn
    cfg = get_config("cnn_elm_6c12c")
    rng = np.random.default_rng(0)
    params = cnn.init_params(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.random((4, 28, 28)).astype(np.float32))
    beta = jnp.asarray(rng.normal(
        size=(cnn.feature_dim(cfg), cfg.num_classes)).astype(np.float32))
    t = jax.nn.one_hot(jnp.arange(4) % cfg.num_classes, cfg.num_classes)

    def loss(p, use_pallas):
        h = cnn.features(cfg, p, x, use_pallas=use_pallas)
        return elm.elm_loss(h, beta, t)

    g_pl = jax.grad(loss)(params, True)
    g_ref = jax.grad(loss)(params, False)
    for a, b in zip(jax.tree.leaves(g_pl), jax.tree.leaves(g_ref)):
        # f32 sums in another order: 1e-5 of each leaf's own scale, so
        # near-zero entries are judged against the gradient they sit in
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())


def test_im2col_decomposition():
    """conv == im2col + matmul (the kernel's structural claim)."""
    x = _rand(2, 10, 10, 3)
    w = _rand(3, 3, 3, 5)
    patches = conv_ref.im2col(x, 3, 3)
    out = (patches @ w.reshape(27, 5)).reshape(2, 8, 8, 5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(conv_ref.conv2d_valid_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# elm_stats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,L,C", [
    (64, 10, 3), (300, 50, 10), (1000, 192, 20), (17, 7, 2), (256, 128, 20),
])
def test_elm_stats_matches_ref(n, L, C):
    h = _rand(n, L)
    t = _rand(n, C)
    u1, v1 = elm_ops.elm_stats(h, t, use_pallas=True)
    u2, v2 = elm_ref.elm_stats_ref(h, t)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 200), L=st.integers(2, 60), C=st.integers(1, 12))
def test_elm_stats_property(n, L, C):
    rng = np.random.default_rng(n * 977 + L * 31 + C)
    h = jnp.asarray(rng.normal(size=(n, L)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(n, C)).astype(np.float32))
    u, v = elm_ops.elm_stats(h, t, use_pallas=True)
    np.testing.assert_allclose(np.asarray(u), np.asarray(h.T @ h),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(v), np.asarray(h.T @ t),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,L,C", [(64, 10, 3), (300, 50, 10), (17, 7, 2)])
def test_elm_stats_masked_matches_ref(n, L, C):
    """Mask-aware kernel vs oracle: binary masks drop rows from U/V."""
    h = _rand(n, L)
    t = _rand(n, C)
    m = jnp.asarray((RNG.random(n) > 0.4).astype(np.float32))
    u1, v1 = elm_ops.elm_stats(h, t, mask=m, use_pallas=True)
    u2, v2 = elm_ref.elm_stats_ref(h, t, m)
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u2), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-3)
    hs = np.asarray(h)[np.asarray(m) > 0]
    ts = np.asarray(t)[np.asarray(m) > 0]
    np.testing.assert_allclose(np.asarray(u1), hs.T @ hs, rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(v1), hs.T @ ts, rtol=1e-4,
                               atol=1e-3)


def test_elm_stats_fractional_mask_weights_once():
    """Row weights must enter U and V exactly ONCE (Hᵀdiag(m)H), never
    squared — the masked kernel scales only the transposed operand."""
    h = _rand(50, 12)
    t = _rand(50, 4)
    m = jnp.asarray(RNG.random(50).astype(np.float32))
    u, v = elm_ops.elm_stats(h, t, mask=m, use_pallas=True)
    hm = np.asarray(h) * np.asarray(m)[:, None]
    np.testing.assert_allclose(np.asarray(u), hm.T @ np.asarray(h),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(v), hm.T @ np.asarray(t),
                               rtol=1e-4, atol=1e-3)


def test_elm_stats_ones_mask_bit_identical():
    """An all-ones mask must not perturb a single bit vs the unmasked op —
    the equal-shard fast path's guarantee."""
    h = _rand(128, 33)
    t = _rand(128, 5)
    u0, v0 = elm_ops.elm_stats(h, t, use_pallas=True)
    u1, v1 = elm_ops.elm_stats(h, t, mask=jnp.ones(128), use_pallas=True)
    np.testing.assert_array_equal(np.asarray(u0), np.asarray(u1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_elm_stats_u_symmetric_psd():
    h = _rand(100, 40)
    t = _rand(100, 5)
    u, _ = elm_ops.elm_stats(h, t, use_pallas=True)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u.T), atol=1e-4)
    eig = np.linalg.eigvalsh(np.asarray(u))
    assert eig.min() > -1e-3


# ---------------------------------------------------------------------------
# sliding-window attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,w,d", [
    (128, 128, 32), (256, 64, 32), (256, 100, 64), (512, 200, 16),
])
def test_swa_matches_ref(S, w, d):
    q, k, v = _rand(2, S, d), _rand(2, S, d), _rand(2, S, d)
    out = swa_ops.swa_attention(q, k, v, window=w, use_pallas=True)
    ref = swa_ref.swa_attention_ref(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_swa_bf16():
    q = _rand(1, 256, 32).astype(jnp.bfloat16)
    k = _rand(1, 256, 32).astype(jnp.bfloat16)
    v = _rand(1, 256, 32).astype(jnp.bfloat16)
    out = swa_ops.swa_attention(q, k, v, window=64, use_pallas=True)
    ref = swa_ref.swa_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_swa_window_actually_limits():
    """Tokens beyond the window must NOT influence the output."""
    q, k, v = _rand(1, 256, 16), _rand(1, 256, 16), _rand(1, 256, 16)
    w = 32
    out1 = swa_ops.swa_attention(q, k, v, window=w, use_pallas=True)
    # perturb keys/values far outside the window of the last query
    k2 = k.at[:, :128].set(9.99)
    v2 = v.at[:, :128].set(-9.99)
    out2 = swa_ops.swa_attention(q, k2, v2, window=w, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                               rtol=1e-5, atol=1e-5)
