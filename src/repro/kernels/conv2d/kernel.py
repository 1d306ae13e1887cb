"""Pallas TPU kernel: valid convolution as im2col + blocked MXU matmul.

TPU adaptation of the paper's conv hot spot (DESIGN.md §8): the GPU
shared-memory-reuse argument (Scherer et al. 2010) becomes VMEM residency —
each (bm x bk) patch tile and (bk x bn) kernel tile is loaded into VMEM
once per grid step and feeds the 128x128 systolic MXU; a f32 VMEM scratch
accumulates across the K grid dimension.

The im2col patch extraction happens in ops.py (XLA handles gather/reshape
well); the kernel itself is the blocked GEMM, grid (M/bm, N/bn, K/bk).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision, out_vma, resolve_interpret

# MXU-aligned default tiles (multiples of 128 where the operand allows)
BM, BN, BK = 128, 128, 128


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x, w = x_ref[...], w_ref[...]
    acc_ref[...] += jnp.dot(x, w, precision=f32_precision(x, w),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pallas_matmul(x, w, tiles, interpret: bool):
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = tiles
    bm, bn, bk = min(bm, max(M, 8)), min(bn, max(N, 8)), min(bk, max(K, 8))
    Mp, Kp, Np = (-(-M // bm)) * bm, (-(-K // bk)) * bk, (-(-N // bn)) * bn
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    nk = Kp // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype,
                                       vma=out_vma(x, w)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]


# The backward is two more blocked GEMMs through the same kernel —
# dX = G·Wᵀ and dW = Xᵀ·G — so SGD through the conv (Alg. 2 lines 13-14)
# stays on the Pallas path instead of failing in pallas_call's JVP rule.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul(x, w, tiles, interpret):
    return _pallas_matmul(x, w, tiles, interpret)


def _matmul_fwd(x, w, tiles, interpret):
    return _pallas_matmul(x, w, tiles, interpret), (x, w)


def _matmul_bwd(tiles, interpret, res, g):
    x, w = res
    dx = _pallas_matmul(g, w.T.astype(g.dtype), tiles, interpret)
    dw = _pallas_matmul(x.T.astype(g.dtype), g, tiles, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _blocked_matmul(x, w, *, bm: int, bn: int, bk: int, interpret: bool):
    return _matmul(x, w, (bm, bn, bk), interpret)


def blocked_matmul(x, w, *, bm: int = BM, bn: int = BN, bk: int = BK,
                   interpret: Optional[bool] = None):
    """(M,K) @ (K,N) -> (M,N), f32 accumulation. Pads to tile multiples.
    Differentiable: the VJP runs the same kernel (``_matmul_bwd``).

    ``interpret=None`` derives the mode from the backend: compiled on TPU,
    interpreter elsewhere (``repro.kernels.resolve_interpret``). Resolved
    outside the jit so the resolved bool is the static cache key."""
    return _blocked_matmul(x, w, bm=bm, bn=bn, bk=bk,
                           interpret=resolve_interpret(interpret))
