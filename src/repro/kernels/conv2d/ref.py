"""Pure-jnp oracle for the conv2d kernel, in its (C, H, W, B) layout."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def conv2d_valid_ref(x, w):
    """x: (Cin,H,W,B), w: (kh,kw,Cin,Cout) -> (Cout,H-kh+1,W-kw+1,B).
    XLA's conv runs on the NHWC transpose, the form the CPU compiler
    keeps as it is: given CHWN it rewrites the conv and drops its op name,
    and with it the ``conv2d`` scope."""
    y = lax.conv_general_dilated(
        x.astype(jnp.float32).transpose(3, 1, 2, 0), w.astype(jnp.float32),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y.transpose(3, 1, 2, 0)
