"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.conv2d import ops as conv_ops, ref as conv_ref
from repro.kernels.elm_stats import ops as elm_ops, ref as elm_ref
from repro.kernels.swa_attention import ops as swa_ops, ref as swa_ref

RNG = np.random.default_rng(0)


def _rand(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


# ---------------------------------------------------------------------------
# conv2d (layout (C, H, W, B): the images on the lanes)
# ---------------------------------------------------------------------------

# every conv of both configs: (H=W, Cin, Cout) — 3c-9c's 1->3 at 28 px and
# 3->9 at 12 px, 6c-12c's 1->6 and 6->12
STAGES = [(28, 1, 3), (12, 3, 9), (28, 1, 6), (12, 6, 12)]


@pytest.mark.parametrize("b", [1, 32, 200])
@pytest.mark.parametrize("hw,cin,cout", STAGES)
def test_conv2d_matches_ref(hw, cin, cout, b):
    x = _rand(cin, hw, hw, b)
    wgt = _rand(5, 5, cin, cout)
    out = conv_ops.conv2d_valid(x, wgt, use_pallas=True)
    ref = conv_ref.conv2d_valid_ref(x, wgt)
    assert out.shape == (cout, hw - 4, hw - 4, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_other_window_matches_ref():
    """A 3x3 window on an 8 px map: nothing in the kernel assumes 5x5."""
    x, wgt = _rand(2, 8, 8, 3), _rand(3, 3, 2, 4)
    np.testing.assert_allclose(
        np.asarray(conv_ops.conv2d_valid(x, wgt, use_pallas=True)),
        np.asarray(conv_ref.conv2d_valid_ref(x, wgt)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hw,cin,cout", STAGES)
def test_conv2d_vjp_matches_ref(hw, cin, cout):
    """dX (the transposed stencil) and dW (reduced over images and
    positions) of the kernel's custom VJP against XLA's conv gradient."""
    x, wgt = _rand(cin, hw, hw, 37), _rand(5, 5, cin, cout)
    g = _rand(cout, hw - 4, hw - 4, 37)

    def grads(use_pallas):
        return jax.grad(lambda a, w: jnp.vdot(conv_ops.conv2d_valid(
            a, w, use_pallas=use_pallas), g), argnums=(0, 1))(x, wgt)

    for a, b in zip(grads(True), grads(False)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())


@pytest.mark.parametrize("config", ["cnn_elm_3c9c", "cnn_elm_6c12c"])
def test_features_grad_through_pallas_matches_xla(config):
    """jax.grad of the ELM loss through the Pallas conv (custom VJP: dX by
    the same kernel, dW by the weight-gradient kernel) equals the XLA-conv
    gradient at the config's widths."""
    from repro.configs.base import get_config
    from repro.core import elm
    from repro.models import cnn
    cfg = get_config(config)
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


@pytest.mark.parametrize("b", [32, 200])
@pytest.mark.parametrize("config", ["cnn_elm_3c9c", "cnn_elm_6c12c"])
def test_features_row_bit_equal_across_batch_sizes(config, b):
    """An image's features through the kernel are the same bits scored
    alone or inside a batch (serving pads batches to bucket sizes)."""
    from repro.configs.base import get_config
    from repro.models import cnn
    cfg = get_config(config)
    params = cnn.init_params(cfg, jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(b).random((b, 28, 28))
                    .astype(np.float32))
    whole = cnn.features(cfg, params, x, use_pallas=True)
    for i in (0, b - 1):
        alone = cnn.features(cfg, params, x[i:i + 1], use_pallas=True)
        np.testing.assert_array_equal(np.asarray(alone[0]),
                                      np.asarray(whole[i]))


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
