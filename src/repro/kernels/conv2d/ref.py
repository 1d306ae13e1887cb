"""Pure-jnp oracle for the conv2d kernel (and the im2col decomposition)."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def conv2d_valid_ref(x, w):
    """x: (B,H,W,Cin), w: (kh,kw,Cin,Cout) -> (B,H-kh+1,W-kw+1,Cout)."""
    return lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def im2col(x, kh: int, kw: int):
    """(B,H,W,C) -> (B*OH*OW, kh*kw*C) patch matrix, columns ordered
    (kh, kw, C) like an HWIO kernel. Built as kh·kw static slices joined
    on the channel axis: the TPU compiler spends tens of seconds on a
    gather form, or on a stack along a new axis, at the 6c-12c shapes
    (B=200); the slices' transpose is pads, not a scatter."""
    B, H, W, C = x.shape
    OH, OW = H - kh + 1, W - kw + 1
    cols = [x[:, i:i + OH, j:j + OW, :] for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1).reshape(B * OH * OW, kh * kw * C)


def matmul_ref(x, w):
    return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)
