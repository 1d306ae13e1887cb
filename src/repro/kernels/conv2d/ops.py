"""Jitted public wrapper for the conv2d Pallas kernel.

``use_pallas=None`` (auto, the default) routes through im2col + the blocked
Pallas GEMM on TPU — compiled, on the hot path — and through the XLA
``jax.lax.conv`` reference on other backends. Forcing ``use_pallas=True``
off-TPU runs the kernel in interpret mode (the kernel body runs in Python,
validating the BlockSpec program for the TPU target); ``use_pallas=False``
always takes the XLA fallback. See ``repro.kernels`` for the policy.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels import resolve_interpret, resolve_use_pallas
from repro.kernels.conv2d import ref
from repro.kernels.conv2d.kernel import blocked_matmul


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _conv2d_valid(x, w, *, use_pallas: bool, interpret: bool):
    with jax.named_scope(scopes.CONV2D):
        if not use_pallas:
            return ref.conv2d_valid_ref(x, w).astype(x.dtype)
        B, H, W, C = x.shape
        kh, kw, _, Cout = w.shape
        OH, OW = H - kh + 1, W - kw + 1
        patches = ref.im2col(x, kh, kw)              # (B*OH*OW, kh*kw*C)
        wmat = w.reshape(kh * kw * C, Cout)
        out = blocked_matmul(patches, wmat, interpret=interpret)
        return out.reshape(B, OH, OW, Cout).astype(x.dtype)


def conv2d_valid(x, w, *, use_pallas: Optional[bool] = None):
    """x: (B,H,W,Cin), w: (kh,kw,Cin,Cout); valid conv, stride 1.

    The backend policy (use_pallas AND interpret) resolves OUTSIDE the jit
    so the resolved bools are the static cache keys — env overrides take
    effect on the next call, not never. (When called inside an enclosing
    jit, resolution happens at that trace's time and is baked into its
    cache entry.)"""
    return _conv2d_valid(x, w, use_pallas=resolve_use_pallas(use_pallas),
                         interpret=resolve_interpret(None))
