"""Jitted public wrapper for the conv2d Pallas kernel.

Activations are (C, H, W, B): the images on the minor (lane) axis, the
layout ``models.cnn.features`` keeps through its stages.
``use_pallas=None`` (auto, the default) routes through the lane-dense
Pallas kernel on TPU — compiled, on the hot path — and through the XLA
``jax.lax.conv`` reference on other backends. Forcing ``use_pallas=True``
off-TPU runs the kernel in interpret mode (the kernel body runs in Python,
validating the BlockSpec program for the TPU target); ``use_pallas=False``
always takes the XLA fallback. See ``repro.kernels`` for the policy.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro import scopes
from repro.kernels import resolve_interpret, resolve_use_pallas
from repro.kernels.conv2d import ref
from repro.kernels.conv2d.kernel import conv2d_valid as _pallas_conv


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _conv2d_valid(x, w, *, use_pallas: bool, interpret: bool):
    with jax.named_scope(scopes.CONV2D):
        if not use_pallas:
            return ref.conv2d_valid_ref(x, w).astype(x.dtype)
        return _pallas_conv(x, w, interpret=interpret).astype(x.dtype)


def conv2d_valid(x, w, *, use_pallas: Optional[bool] = None):
    """x: (Cin,H,W,B), w: (kh,kw,Cin,Cout) -> (Cout,OH,OW,B); valid conv,
    stride 1. On the kernel path B is padded to a multiple of 128 inside
    the call unless the caller has done so.

    The backend policy (use_pallas AND interpret) resolves OUTSIDE the jit
    so the resolved bools are the static cache keys — env overrides take
    effect on the next call, not never. (When called inside an enclosing
    jit, resolution happens at that trace's time and is baked into its
    cache entry.)"""
    return _conv2d_valid(x, w, use_pallas=resolve_use_pallas(use_pallas),
                         interpret=resolve_interpret(None))
