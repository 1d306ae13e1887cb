"""Train/serve step builders used by the launcher, smoke tests and dry-run.

``make_train_step``     — standard CE training (the per-member Map step).
``make_elm_train_step`` — the paper-faithful variant: forward to features,
                          E²LM stats accumulation + ELM-error SGD.
``make_member_train_step`` + ``make_average_step`` — the multi-pod
distributed-averaging deployment (member dim over the 'pod' axis).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.core.averaging import (average_member_dim, broadcast_member_dim,
                                  psum_weighted_mean_members)
from repro.models import api
from repro.optim import apply_updates, clip_by_global_norm


def make_train_step(cfg, optimizer, lr_schedule,
                    clip: float = 1.0,
                    loss_fn: Optional[Callable] = None):
    loss_fn = loss_fn or (lambda p, b: api.loss_fn(cfg, p, b))

    def train_step(params, opt_state, step, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip)
        lr = lr_schedule(step)
        updates, opt_state = optimizer.update(grads, opt_state, params, step, lr)
        params = apply_updates(params, updates)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update(metrics)
        return params, opt_state, step + 1, out

    return train_step


def make_member_train_step(cfg, optimizer, lr_schedule, clip: float = 1.0,
                           spmd_axis_name: str | None = None):
    """Lift the train step over a leading member dim (Map phase: the member
    dim is sharded over 'pod', so members train with zero cross-pod
    communication between averaging events). Pass spmd_axis_name='pod' when
    lowering for the multi-pod mesh so in-model sharding constraints get the
    member axis prepended."""
    step = make_train_step(cfg, optimizer, lr_schedule, clip)
    return jax.vmap(step, in_axes=0, out_axes=0, spmd_axis_name=spmd_axis_name)


def make_average_step(weights=None, mesh=None):
    """Reduce phase (Alg. 2 lines 18-20): one cross-pod all-reduce mean,
    broadcast back as every member's next-round init.

    This is the ROUNDS CONTRACT: the returned step is exactly what a
    multi-round averaging run (``runner.ReduceConfig(rounds=r)``, or the
    launcher's ``--rounds``) applies between rounds — weighted by ``weights``
    (e.g. shard sizes) when the Reduce strategy is non-uniform, uniform
    otherwise. Applying it at round boundaries and once more at the end
    reproduces the parallel-SGD regime; applying it only at the end is the
    paper's single final average.

    ``mesh=None`` (default) returns the member-dim mean+broadcast and
    leaves partitioning to jit/GSPMD — the dry-run's lowering. With a
    ``mesh`` (must carry a 'pod' axis; the member count must divide it)
    the step is instead shard_map-ed explicitly and the whole tree mean is
    ONE flat-psum all-reduce (``averaging.psum_weighted_mean_members``) —
    the same collective contract as the mesh Map-phase executor's sync."""
    if mesh is None:
        def average_step(stacked_params):
            k = jax.tree.leaves(stacked_params)[0].shape[0]
            return broadcast_member_dim(
                average_member_dim(stacked_params, weights=weights), k)

        return average_step

    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import member_dim_specs

    if "pod" not in mesh.shape:
        raise ValueError(f"make_average_step needs a mesh with a 'pod' "
                         f"axis, got axes {tuple(mesh.shape)}")

    def average_step(stacked_params):
        k = jax.tree.leaves(stacked_params)[0].shape[0]
        pods = mesh.shape["pod"]
        if k % pods:
            raise ValueError(
                f"{k} members do not divide the {pods}-pod mesh — pad the "
                f"member dim (the mesh executor's pad-and-mask contract) "
                f"or use a divisible pod count")
        w = jnp.ones((k,), jnp.float32) if weights is None \
            else jnp.asarray(weights, jnp.float32)
        specs = member_dim_specs(stacked_params, mesh)

        def local(p, w_loc):
            avg = psum_weighted_mean_members(p, w_loc, "pod")
            k_local = jax.tree.leaves(p)[0].shape[0]
            return broadcast_member_dim(avg, k_local)

        return shard_map(local, mesh=mesh, in_specs=(specs, P("pod")),
                         out_specs=specs)(stacked_params, w)

    return average_step


def make_serve_step(cfg):
    def serve_step(params, cache, token, pos):
        return api.decode_step(cfg, params, cache, token, pos)

    return serve_step


def make_prefill_step(cfg):
    if cfg.is_encoder_only:
        # encoder-only "prefill" = full encode, logits out, no cache
        def encode_step(params, batch):
            logits, _ = api.module_of(cfg).forward(cfg, params, batch)
            return logits
        return encode_step

    def prefill_step(params, batch):
        return api.prefill(cfg, params, batch)

    return prefill_step


def init_train_state(cfg, optimizer, key, dtype=jnp.bfloat16):
    params = api.init_params(cfg, key, dtype)
    return params, optimizer.init(params), jnp.zeros((), jnp.int32)
