"""The plain reference of one SGD epoch of the paper's Map phase (arXiv
1610.02373, Algorithm 2, lines 7-14), step by step, built on the
primitives of ``chipbench.reference``; imports nothing of the program.

A member's step j, from params p_j on batch (x_j, t_j):

    H = act(features(p_j, x_j));  U += HᵀH;  V += HᵀT
    β = (I/λ + U)⁻¹ V
    p_{j+1} = p_j − α ∇p ½ mean‖act(features(p, x_j)) β − t_j‖²

* ``free_run``: the epoch as the algorithm runs it, from the shared init,
  each step from the last: the members, their β, the averaged model and
  the record of every step's starting params, shaped as the program hands
  them back. It stands in the program's place for the lower-precision
  control (``high``) and the planted faults (``half_batch``: the second
  half of every batch left out; ``stale_beta``: β solved from the sums
  before batch j is added).
* ``replay``: teacher forcing. Every step starts from the params the
  program recorded, p_j, with the sums recomputed from the recorded
  p_0 … p_j; it returns each step's update Δ_j = p_{j+1} − p_j and β of
  the epoch's sums. A wrong step then shows as that step's error, and
  does not grow over the steps after it.
* ``numbers``: what the check compares (``limits/<cell>.json``).

Batch order: member i draws one permutation from
``numpy.random.default_rng(shuffle_seed + i)`` and takes ⌊n_i/B⌋
batches; a member with fewer batches than the longest ends in padding
steps (mask 0) that add nothing and leave its params as they are.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import act, features, init_params, mm, solve


def epoch_batches(parts, batch: int, shuffle_seed: int, num_classes: int):
    """The first epoch's batches of every member, padded to the longest:
    xb (k, nb, B, H, W), one-hot tb (k, nb, B, C) and mask (k, nb)."""
    nb = max(len(x) // batch for x, _ in parts)
    k = len(parts)
    x0 = parts[0][0]
    xb = np.zeros((k, nb, batch) + x0.shape[1:], np.float32)
    tb = np.zeros((k, nb, batch, num_classes), np.float32)
    mask = np.zeros((k, nb), np.float32)
    for i, (x, y) in enumerate(parts):
        n = len(x) // batch
        idx = np.random.default_rng(shuffle_seed + i).permutation(
            len(x))[:n * batch]
        xb[i, :n] = x[idx].reshape((n, batch) + x.shape[1:])
        tb[i, :n] = np.eye(num_classes, dtype=np.float32)[y[idx]].reshape(
            n, batch, num_classes)
        mask[i, :n] = 1.0
    return xb, tb, mask


def _beta_and_grad(p, u, v, x, t, *, pool: int, lam: float, prec: str):
    """β from the sums, and the loss gradient at p on (x, t) under it."""
    beta = solve(u, v, lam)

    def loss(q):
        r = mm(act(features(q, x, pool, prec)), beta, prec) - t
        return 0.5 * jnp.mean(jnp.sum(r * r, axis=-1))

    return jax.grad(loss)(p)


def _add(p, u, v, x, t, m, prec: str, pool: int):
    h = act(features(p, x, pool, prec))
    return u + m * mm(h.T, h, prec), v + m * mm(h.T, t, prec)


@functools.partial(jax.jit, static_argnames=(
    "pool", "lam", "precision", "half_batch", "stale_beta"))
# repro: allow(missing-donate) the reference runs once a check; its buffers stay plain
def free_run(params_k, xb, tb, mask, lr, *, pool: int, lam: float,
             precision: str, half_batch: bool = False,
             stale_beta: bool = False):
    """One epoch of every member from ``params_k`` (leaves (k, ...)):
    (final params, the record of each step's starting params, leaves
    (k, nb, ...), β of the epoch's sums)."""
    if half_batch:
        half = xb.shape[2] // 2
        xb, tb = xb[:, :, :half], tb[:, :, :half]
    L = _feature_dim(params_k, xb.shape[-1], pool)

    def member(params, x_m, t_m, m_m):
        def step(carry, batch):
            p, u, v = carry
            x, t, m = batch
            u1, v1 = _add(p, u, v, x, t, m, precision, pool)
            g = _beta_and_grad(p, *((u, v) if stale_beta else (u1, v1)),
                               x, t, pool=pool, lam=lam, prec=precision)
            q = jax.tree.map(lambda a, b: jnp.where(m > 0, a - lr * b, a),
                             p, g)
            return (q, u1, v1), p

        zero = (params, jnp.zeros((L, L), jnp.float32),
                jnp.zeros((L, t_m.shape[-1]), jnp.float32))
        (p, u, v), rec = lax.scan(step, zero, (x_m, t_m, m_m))
        return p, rec, solve(u, v, lam)

    return jax.vmap(member)(params_k, xb, tb, mask)


@functools.partial(jax.jit, static_argnames=("pool", "lam", "precision"))
# repro: allow(missing-donate) the reference runs once a check; its buffers stay plain
def replay(record_k, xb, tb, mask, lr, *, pool: int, lam: float,
           precision: str = "highest"):
    """Teacher forcing: every member's steps from its recorded params
    (leaves (k, nb, ...)). Returns each step's update Δ_j (leaves
    (k, nb, ...); 0 on a padding step) and β of the epoch's sums."""
    L = _feature_dim(jax.tree.map(lambda a: a[:, 0], record_k),
                     xb.shape[-1], pool)

    def member(rec, x_m, t_m, m_m):
        def step(carry, batch):
            u, v = carry
            p, x, t, m = batch
            u, v = _add(p, u, v, x, t, m, precision, pool)
            g = _beta_and_grad(p, u, v, x, t, pool=pool, lam=lam,
                               prec=precision)
            return (u, v), jax.tree.map(lambda b: m * (-lr * b), g)

        zero = (jnp.zeros((L, L), jnp.float32),
                jnp.zeros((L, t_m.shape[-1]), jnp.float32))
        (u, v), delta = lax.scan(step, zero, (rec, x_m, t_m, m_m))
        return delta, solve(u, v, lam)

    return jax.vmap(member)(record_k, xb, tb, mask)


def _feature_dim(params_k, image: int, pool: int) -> int:
    n = image
    for st in params_k["stages"]:
        n = (n - st["w"].shape[-3] + 1) // pool
    return n * n * params_k["stages"][-1]["w"].shape[-1]


def _kw(model: dict) -> dict:
    return dict(pool=model["cnn_pool"], lam=float(model["elm_lambda"]))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def init(model: dict, init_seed: int):
    """The shared init of a job (host arrays)."""
    return _host(init_params(model["cnn_channels"], model["cnn_kernel"],
                             model["image_channels"],
                             jax.random.PRNGKey(init_seed)))


def free_job(model: dict, parts, *, init_seed: int, shuffle_seed: int,
             lr: float, batch: int, precision: str = "highest",
             half_batch: bool = False, stale_beta: bool = False) -> dict:
    """A one-epoch job as the reference runs it, shaped as the program's
    answer: ``record`` (leaves (nb, k, ...)), ``mask`` (nb, k), the
    members' ``cnn`` params and ``beta``, and the ``averaged`` model (the
    mean of the members'), all host arrays."""
    xb, tb, mask = epoch_batches(parts, batch, shuffle_seed,
                                 model["num_classes"])
    p0 = init(model, init_seed)
    k = len(parts)
    params_k = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape),
                            p0)
    p, rec, beta = _host(free_run(
        params_k, jnp.asarray(xb), jnp.asarray(tb), jnp.asarray(mask),
        jnp.float32(lr), precision=precision, half_batch=half_batch,
        stale_beta=stale_beta, **_kw(model)))
    members = {"cnn": p, "beta": beta}
    return {"record": jax.tree.map(lambda a: np.swapaxes(a, 0, 1), rec),
            "mask": mask.T > 0, "members": members,
            "averaged": jax.tree.map(lambda a: a.mean(0), members)}


# ---- the numbers the check compares ---------------------------------------

def _leaves(tree):
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def _rel(a, b) -> float:
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def step_errors(got: dict, delta_ref) -> np.ndarray:
    """(nb, k): per step and member, the worst over leaves of
    max |Δθ_prog − Δθ_ref| / max |Δθ_ref|, with Δθ_prog the difference of
    consecutive recorded params (the last step's end: the members' own).
    A step whose reference update is 0 (a padding step) reads 0 if the
    program's is 0 too, else infinity."""
    worst = None
    for rec, end, ref in zip(_leaves(got["record"]),
                             _leaves(got["members"]["cnn"]),
                             _leaves(delta_ref)):
        prog = np.concatenate([rec[1:], end[None]]) - rec
        axes = tuple(range(2, rec.ndim))
        err = np.max(np.abs(prog - ref), axis=axes)
        scale = np.max(np.abs(ref), axis=axes)
        ratio = np.where(scale > 0, err / np.where(scale > 0, scale, 1.0),
                         np.where(err > 0, np.inf, 0.0))
        worst = ratio if worst is None else np.maximum(worst, ratio)
    return worst


def numbers(model: dict, parts, got: dict, *, init_seed: int,
            shuffle_seed: int, lr: float, batch: int,
            precision: str = "highest") -> Dict[str, float]:
    """The check of one job's answer ``got`` (shaped as ``free_job``'s):

    * ``init_max_err``: the recorded p_0 of every member against the
      reference init;
    * ``step_max_err``: the worst of ``step_errors``, each step replayed
      from the recorded params;
    * ``replay_beta_err``: each member's β against β solved from the
      epoch's sums recomputed on the recorded params, max |Δβ| / max |β|;
    * ``average_max_err``: the averaged model's params and β against the
      mean of the members', max |Δ| / max |mean| over leaves.

    Also ``step_err_p50``/``step_err_p99`` and ``worst_step``, to read
    beside them (no limit)."""
    xb, tb, mask = epoch_batches(parts, batch, shuffle_seed,
                                 model["num_classes"])
    if not np.array_equal(mask.T > 0, np.asarray(got["mask"])):
        raise ValueError("the record's padding steps are not the batch "
                         "order's")
    record_k = jax.tree.map(lambda a: jnp.asarray(np.swapaxes(a, 0, 1)),
                            got["record"])
    delta, beta = _host(replay(record_k, jnp.asarray(xb), jnp.asarray(tb),
                               jnp.asarray(mask), jnp.float32(lr),
                               precision=precision, **_kw(model)))
    del xb, tb
    errs = step_errors(got, jax.tree.map(lambda a: np.swapaxes(a, 0, 1),
                                         delta))
    p0 = init(model, init_seed)
    init_err = max(_rel(rec[0, i], w)
                   for rec, w in zip(_leaves(got["record"]), _leaves(p0))
                   for i in range(rec.shape[1]))
    members, avg = got["members"], got["averaged"]
    beta_prog = np.asarray(members["beta"], np.float64)
    mean = [a.mean(0) for a in _leaves(members)]
    real = errs[np.asarray(got["mask"])]
    return {"init_max_err": init_err,
            "step_max_err": float(errs.max()),
            "replay_beta_err": max(_rel(beta_prog[i], beta[i])
                                   for i in range(len(beta))),
            "average_max_err": max(_rel(a, m) for a, m in
                                   zip(_leaves(avg), mean)),
            "step_err_p50": float(np.percentile(real, 50)),
            "step_err_p99": float(np.percentile(real, 99)),
            "worst_step": int(np.unravel_index(np.argmax(errs),
                                               errs.shape)[0])}
