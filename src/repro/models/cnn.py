"""The paper's CNN feature learner (LeNet family, Fig. 1/3).

Architecture string such as 6c-2s-12c-2s (Table 4/5) or 3c-2s-9c-2s
(Table 2/3): conv (valid, k=5) -> ReLU -> mean-pool (down-sampling, scale 2)
per stage. The flattened last pooled map is the ELM hidden matrix H
(Fig. 2) after the paper's optimal-tanh activation — applied in
``repro.core.elm``, not here.

Convolution runs through ``repro.kernels.conv2d.ops`` which dispatches to
the Pallas TPU kernel on TPU and to ``jax.lax.conv`` on CPU
(``use_pallas=None`` = that auto policy; a bool forces the path). On the
kernel route the stack runs with the images on the minor axis,
(C, H, W, B): on a TPU that is the 128-lane axis, so the conv kernel,
bias, ReLU and pool all work on lane-dense arrays whatever the (1 to 12)
channel count. ``features`` pads the batch once with zero images to
whole lane blocks and transposes it in, and turns H back into (B, F) once
at the end, F in (h, w, c) order. XLA's route keeps (B, H, W, C).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_use_pallas
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.conv2d.kernel import LANES


def feature_dim(cfg) -> int:
    n, ch = cfg.image_size, cfg.image_channels
    for c in cfg.cnn_channels:
        n = (n - cfg.cnn_kernel + 1) // cfg.cnn_pool
        ch = c
    return n * n * ch


def init_params(cfg, key, dtype=jnp.float32):
    """Kernels W: (k, k, c_in, c_out) + bias per stage. The paper
    initialises all k machines with the SAME weights (Alg. 2 line 3) —
    callers reuse one init across members."""
    params = []
    ch_in = cfg.image_channels
    for i, ch_out in enumerate(cfg.cnn_channels):
        key, sub = jax.random.split(key)
        fan_in = cfg.cnn_kernel * cfg.cnn_kernel * ch_in
        w = jax.random.normal(sub, (cfg.cnn_kernel, cfg.cnn_kernel, ch_in, ch_out),
                              jnp.float32) * (2.0 / fan_in) ** 0.5
        params.append({"w": w.astype(dtype), "b": jnp.zeros((ch_out,), dtype)})
        ch_in = ch_out
    return {"stages": tuple(params)}


def logical_axes(cfg):
    return {"stages": tuple({"w": (None, None, None, "heads"), "b": ("heads",)}
                            for _ in cfg.cnn_channels)}


def _mean_pool(x, s):
    """Mean over s x s windows of axes 1 and 2 (H and W in both layouts)."""
    A, H, W, Z = x.shape
    x = x.reshape(A, H // s, s, W // s, s, Z)
    return jnp.mean(x, axis=(2, 4))


def _swap(x):
    """(B, H, W, C) <-> (C, H, W, B): its own inverse."""
    return x.transpose(3, 1, 2, 0)


def features(cfg, params, images, *, use_pallas: Optional[bool] = None):
    """images: (B, H, W) or (B, H, W, C) in [0,1]. Returns flat H (B, F),
    each row in (h, w, c) order.

    The kernel route runs the stack in (C, H, W, B), the batch padded to
    whole lane blocks; XLA's route (the CPU's) in (B, H, W, C), where its
    conv, bias gradient and pool sum in the order they always have."""
    x = images if images.ndim == 4 else images[..., None]
    x = x.astype(jnp.float32)
    b = x.shape[0]
    lanes = resolve_use_pallas(use_pallas)
    if lanes:
        x = _swap(jnp.pad(x, ((0, -b % LANES), (0, 0), (0, 0), (0, 0))))
    for st in params["stages"]:
        if lanes:
            x = conv_ops.conv2d_valid(x, st["w"], use_pallas=True)
            x = x + st["b"][:, None, None, None]
        else:
            x = _swap(conv_ops.conv2d_valid(_swap(x), st["w"],
                                            use_pallas=False)) + st["b"]
        x = _mean_pool(jax.nn.relu(x), cfg.cnn_pool)
    if lanes:
        x = _swap(x)[:b]
    return x.reshape(b, -1)
