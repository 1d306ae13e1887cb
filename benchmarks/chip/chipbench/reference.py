"""The plain reference of the paper's CNN-ELM Map+Reduce (arXiv 1610.02373,
Algorithm 2), written from the paper in ``jax.numpy`` and ``lax``, imports
nothing of the program.

* features: valid k x k convolutions (``lax.conv_general_dilated``), ReLU,
  s x s mean pooling, flattened; the ELM activation 1.7159 tanh(2/3 h).
* one member's epoch: for each batch, U += HᵀH, V += HᵀT; with SGD,
  β = (I/λ + U)⁻¹V from the running sums, then one step of
  W ← W − α ∇W ½ mean‖Hβ − T‖².
* a job: k members from one shared init, ``epochs`` epochs split into
  ``rounds`` blocks with the members reset to their mean between blocks,
  β of each member solved from its last epoch's sums, and the Reduce: the
  mean of every member's weights and β.
* batch order: member i draws one permutation per epoch from
  ``numpy.random.default_rng(shuffle_seed + i)`` and takes ⌊n/B⌋ batches.
* init: per stage, a normal draw from the next split of the key, scaled
  by sqrt(2 / fan_in); zero biases.

Every product runs at the precision it is given: ``highest`` (full f32)
for the reference; ``high``, three bf16 passes (a_hi·b_hi + a_hi·b_lo +
a_lo·b_hi, f32 sums), for the lower-precision control. ``high`` is
written out here rather than left to the backend, so that it computes
the same on the CPU as on the TPU.
``half_batch`` leaves out the second half of every batch and takes the
mean over the rest: a planted fault, for the comparison's own readings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _at(op, a, b, prec: str):
    """``op(a, b)`` at full f32, or as three bf16 passes."""
    if prec == "highest":
        return op(a, b)
    if prec != "high":
        raise ValueError(f"unknown precision {prec!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def mm(a, b, prec: str):
    return _at(lambda x, y: jnp.matmul(x, y, precision=HIGHEST), a, b, prec)


def conv(x, w, prec: str):
    return _at(lambda a, b: lax.conv_general_dilated(
        a, b, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST), x, w, prec)


def init_params(channels, kernel: int, in_ch: int, key):
    stages = []
    for ch_out in channels:
        key, sub = jax.random.split(key)
        fan_in = kernel * kernel * in_ch
        w = jax.random.normal(sub, (kernel, kernel, in_ch, ch_out),
                              jnp.float32) * (2.0 / fan_in) ** 0.5
        stages.append({"w": w, "b": jnp.zeros((ch_out,), jnp.float32)})
        in_ch = ch_out
    return {"stages": tuple(stages)}


def features(params, images, pool: int, prec):
    x = images[..., None].astype(jnp.float32)
    for st in params["stages"]:
        x = jax.nn.relu(conv(x, st["w"], prec) + st["b"])
        b, h, w, c = x.shape
        x = x.reshape(b, h // pool, pool, w // pool, pool, c).mean((2, 4))
    return x.reshape(x.shape[0], -1)


def act(h):
    return 1.7159 * jnp.tanh(h * (2.0 / 3.0))


def solve(u, v, lam: float):
    a = u + jnp.eye(u.shape[-1], dtype=jnp.float32) / lam
    f = lax.linalg.cholesky(a)
    y = lax.linalg.triangular_solve(f, v, left_side=True, lower=True)
    return lax.linalg.triangular_solve(f, y, left_side=True, lower=True,
                                       transpose_a=True)


@functools.partial(jax.jit, static_argnames=("pool", "lam", "sgd",
                                             "precision", "half_batch"))
# repro: allow(missing-donate) the reference runs once a check; its buffers stay plain
def member_epochs(params_k, xb, tb, lr, *, pool: int, lam: float, sgd: bool,
                  precision: str, half_batch: bool = False):
    """One epoch of every member: xb (k, nb, B, H, W), tb (k, nb, B, C).
    Returns the members' params and their (U, V) sums of this epoch."""
    prec = precision
    if half_batch:
        half = xb.shape[2] // 2
        xb, tb = xb[:, :, :half], tb[:, :, :half]

    n = xb.shape[-1]
    for st in params_k["stages"]:
        n = (n - st["w"].shape[1] + 1) // pool
    L = n * n * params_k["stages"][-1]["w"].shape[-1]
    C = tb.shape[-1]

    def member(params, x_m, t_m):

        def step(carry, batch):
            p, u, v = carry
            x, t = batch
            h = act(features(p, x, pool, prec))
            u = u + mm(h.T, h, prec)
            v = v + mm(h.T, t, prec)
            if sgd:
                beta = solve(u, v, lam)

                def loss(q):
                    r = mm(act(features(q, x, pool, prec)), beta, prec) - t
                    return 0.5 * jnp.mean(jnp.sum(r * r, axis=-1))

                g = jax.grad(loss)(p)
                p = jax.tree.map(lambda a, b: a - lr * b, p, g)
            return (p, u, v), None

        zero = (params, jnp.zeros((L, L), jnp.float32),
                jnp.zeros((L, C), jnp.float32))
        (p, u, v), _ = lax.scan(step, zero, (x_m, t_m))
        return p, u, v

    return jax.vmap(member)(params_k, xb, tb)


@functools.partial(jax.jit, static_argnames=("lam",))
def solve_members(u, v, *, lam: float):
    return jax.vmap(lambda a, b: solve(a, b, lam))(u, v)


def epoch_batches(parts, batch: int, rngs, num_classes: int):
    """One epoch's batches of every member, drawn from the members' live
    streams: xb (k, nb, B, H, W) and one-hot tb (k, nb, B, C)."""
    xs, ts = [], []
    for (x, y), rng in zip(parts, rngs):
        nb = len(x) // batch
        idx = rng.permutation(len(x))[:nb * batch]
        xs.append(x[idx].reshape(nb, batch, *x.shape[1:]))
        ts.append(np.eye(num_classes, dtype=np.float32)[y[idx]].reshape(
            nb, batch, num_classes))
    return np.stack(xs), np.stack(ts)


def run_job(model: dict, parts, *, init_seed: int, shuffle_seed: int,
            epochs: int, rounds: int, lr: float, batch: int,
            precision: str = "highest", half_batch: bool = False):
    """The reference's answer for one job: the init, the members (params
    and β) and the averaged model, all as host arrays."""
    key = jax.random.PRNGKey(init_seed)
    init = init_params(model["cnn_channels"], model["cnn_kernel"],
                       model["image_channels"], key)
    k = len(parts)
    params = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape),
                          init)
    rngs = [np.random.default_rng(shuffle_seed + i) for i in range(k)]
    kw = dict(pool=model["cnn_pool"], lam=float(model["elm_lambda"]),
              precision=precision, half_batch=half_batch)
    passes = [(False, 0.0)] if epochs == 0 else \
        [(True, lr / (e + 1)) for e in range(epochs)]
    per_round = max(epochs // rounds, 1)
    u = v = None
    for e, (sgd, rate) in enumerate(passes):
        xb, tb = epoch_batches(parts, batch, rngs, model["num_classes"])
        params, u, v = member_epochs(params, jnp.asarray(xb),
                                     jnp.asarray(tb), jnp.float32(rate),
                                     sgd=sgd, **kw)
        del xb, tb
        if sgd and (e + 1) % per_round == 0 and e + 1 < epochs:
            params = jax.tree.map(
                lambda a: jnp.broadcast_to(a.mean(0), a.shape), params)
    beta = solve_members(u, v, lam=kw["lam"])
    host = lambda t: jax.tree.map(np.asarray, t)
    members = host({"cnn": params, "beta": beta})
    averaged = jax.tree.map(lambda a: a.mean(0), members)
    return {"init": host(init), "members": members, "averaged": averaged}


@functools.partial(jax.jit, static_argnames=("pool", "precision"))
def member_scores(params_k, beta_k, x, *, pool: int, precision: str):
    """(k, n, C) ELM scores of n images under every member."""
    return jax.vmap(lambda p, b: mm(act(features(p, x, pool, precision)), b,
                                    precision))(params_k, beta_k)
