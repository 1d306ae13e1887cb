"""Pallas TPU kernel: valid convolution with the images on the lanes.

The paper's convs are narrow (1 to 12 channels, 5x5 windows, 28 and 12
pixel maps), so the TPU's 128-lane axis is filled by the batch, not by
channels: activations are (C, H, W, B), B minor. A grid step takes a
128-image block whole — every channel and row of it sits in VMEM — and
each of the kh·kw·Cin window offsets is a slice along H (a major axis)
and W (sublanes) of that tile: the windows are built in VMEM, never as an
im2col matrix in HBM. The contraction over (ci, i, j) is f32
multiply-adds on the VPU, one weight (a scalar from SMEM) times a
(rows, OW, 128) slab at a time, accumulated in registers.

Every image's result is the same sequence of f32 operations on its own
lane, whatever else the block holds, so a row's features are bit-equal
across batch sizes (serving's bucket padding relies on it).

The backward stays in this layout (``custom_vjp``): dX is the same
kernel run on the cotangent padded by kh-1, kw-1 on each side with the
kernel flipped and its channel axes swapped (the transposed stencil);
dW multiplies each shifted input slab by the cotangent and reduces over
positions in the kernel, leaving one partial sum per lane, which the
caller sums over images.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_vma, resolve_interpret

LANES = 128      # images per grid step: one vreg's lane width
ACC_VREGS = 24   # the accumulator's (8, 128) registers per slab


def _row_block(oh: int, ow: int) -> int:
    """Rows per accumulator slab: the most output rows (a divisor of
    ``oh``) whose (rows, ow, 128) f32 slab fits ``ACC_VREGS`` registers."""
    per_row = -(-ow // 8)
    return max(r for r in range(1, oh + 1)
               if oh % r == 0 and r * per_row <= max(ACC_VREGS, per_row))


def _conv_kernel(w_ref, x_ref, o_ref, *, kh: int, kw: int):
    """o[co, r, c, :] = Σ_{ci,i,j} w[i, j, ci, co] · x[ci, r+i, c+j, :].
    w_ref: the HWIO kernel as one (1, kh·kw·Cin·Cout) row in SMEM (2-D,
    so a vmap's member axis stays a leading block dim); x_ref
    (Cin, H, W, 128) and o_ref (Cout, OH, OW, 128) in VMEM."""
    cin = x_ref.shape[0]
    cout, oh, ow, lanes = o_ref.shape
    rh = _row_block(oh, ow)

    def out_channel(co, carry):
        def row_block(r, carry):
            r0 = pl.multiple_of(r * rh, rh)

            def in_channel(ci, acc):
                for i in range(kh):
                    for j in range(kw):
                        wv = w_ref[0, ((i * kw + j) * cin + ci) * cout + co]
                        acc = acc + wv * x_ref[ci, pl.ds(r0 + i, rh),
                                               pl.ds(j, ow), :]
                return acc

            acc = jax.lax.fori_loop(
                0, cin, in_channel, jnp.zeros((rh, ow, lanes), jnp.float32))
            o_ref[co, pl.ds(r0, rh), :, :] = acc.astype(o_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, oh // rh, row_block, carry)

    jax.lax.fori_loop(0, cout, out_channel, 0)


def _weight_grad_kernel(x_ref, g_ref, o_ref, *, kh: int, kw: int):
    """o[0, ci·Cout + co, i·kw + j, :] = Σ_{r,c} x[ci, r+i, c+j, :] ·
    g[co, r, c, :]: each lane's share of dW[i, j, ci, co]."""
    cin = x_ref.shape[0]
    cout, oh, ow, lanes = g_ref.shape
    rh = _row_block(oh, ow)

    def pair(p, carry):
        ci, co = p // cout, p % cout
        for i in range(kh):
            for j in range(kw):
                acc = jnp.zeros((rh, ow, lanes), jnp.float32)
                for r0 in range(0, oh, rh):
                    acc = acc + (x_ref[ci, pl.ds(r0 + i, rh), pl.ds(j, ow), :]
                                 * g_ref[co, pl.ds(r0, rh), :, :])
                o_ref[0, p, pl.ds(i * kw + j, 1), :] = jnp.sum(
                    jnp.sum(acc, axis=0), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, cin * cout, pair, 0)


def _lane_blocks(b: int) -> int:
    assert b % LANES == 0, f"the image axis ({b}) must fill whole lane blocks"
    return b // LANES


def _forward(x, w, interpret: bool):
    """x (Cin, H, W, B), B a multiple of 128; w (kh, kw, Cin, Cout)."""
    cin, h, wd, b = x.shape
    kh, kw, _, cout = w.shape
    oh, ow = h - kh + 1, wd - kw + 1
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw),
        grid=(_lane_blocks(b),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((cin, h, wd, LANES), lambda l: (0, 0, 0, l)),
        ],
        out_specs=pl.BlockSpec((cout, oh, ow, LANES), lambda l: (0, 0, 0, l)),
        out_shape=jax.ShapeDtypeStruct((cout, oh, ow, b), x.dtype,
                                       vma=out_vma(x, w)),
        interpret=interpret,
    )(w.reshape(1, -1).astype(jnp.float32), x)


def _weight_grad(x, g, kh: int, kw: int, interpret: bool):
    """dW (kh, kw, Cin, Cout) of the valid conv: per-lane partial sums
    from the kernel, summed here over lane blocks and lanes."""
    cin, h, wd, b = x.shape
    cout, oh, ow, _ = g.shape
    nl = _lane_blocks(b)
    part = pl.pallas_call(
        functools.partial(_weight_grad_kernel, kh=kh, kw=kw),
        grid=(nl,),
        in_specs=[
            pl.BlockSpec((cin, h, wd, LANES), lambda l: (0, 0, 0, l)),
            pl.BlockSpec((cout, oh, ow, LANES), lambda l: (0, 0, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, cin * cout, kh * kw, LANES),
                               lambda l: (l, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nl, cin * cout, kh * kw, LANES),
                                       jnp.float32, vma=out_vma(x, g)),
        interpret=interpret,
    )(x, g)
    dw = part.sum(axis=(0, 3)).reshape(cin, cout, kh, kw)
    return dw.transpose(2, 3, 0, 1)


# The backward runs the same kernels, so SGD through the conv (Alg. 2
# lines 13-14) stays on the Pallas path in the lane layout.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(x, w, interpret):
    return _forward(x, w, interpret)


def _conv_fwd(x, w, interpret):
    return _forward(x, w, interpret), (x, w)


def _conv_bwd(interpret, res, g):
    x, w = res
    kh, kw = w.shape[:2]
    gp = jnp.pad(g, ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1), (0, 0)))
    dx = _forward(gp, w[::-1, ::-1].transpose(0, 1, 3, 2).astype(g.dtype),
                  interpret)
    dw = _weight_grad(x, g, kh, kw, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


# A trace names a kernel's ops after the innermost jit around it, as it
# names the elm_stats kernel's: this one's carry ``_conv2d_valid``, the
# name of the ops-level entry that holds the ``conv2d`` scope.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv2d_valid(x, w, *, interpret: bool):
    b = x.shape[-1]
    pad = -b % LANES
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
    y = _conv(x, w, interpret)
    return y[..., :b] if pad else y


def conv2d_valid(x, w, *, interpret: Optional[bool] = None):
    """x: (Cin, H, W, B), w: (kh, kw, Cin, Cout) -> (Cout, OH, OW, B),
    valid, stride 1, f32. B is padded with zero images to a multiple of
    128 and cut back; ``cnn.features`` pads once for the whole stack.
    Differentiable: the VJP runs the same kernels (``_conv_bwd``).

    ``interpret=None`` derives the mode from the backend: compiled on
    TPU, interpreter elsewhere (``repro.kernels.resolve_interpret``).
    Resolved outside the jit so the resolved bool is the static cache
    key."""
    return _conv2d_valid(x, w, interpret=resolve_interpret(interpret))
