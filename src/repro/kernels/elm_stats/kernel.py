"""Pallas TPU kernel: fused ELM sufficient statistics.

One pass over row-blocks of H computes BOTH Gram products the E²LM map
step needs (paper Eq. 3/4):   U = HᵀH  (L x L)   and   V = HᵀT  (L x C).

Fusing matters because H is the big operand (n >> L): the paper's map step
reads each H row block from HBM once and reuses it from VMEM for the U tile
row AND the V tile — halving HBM traffic versus two separate GEMMs (this is
the TPU translation of the paper's 'reuse loaded data as often as possible'
remark about GPU shared memory).

Grid (i over L tiles, j over L tiles, k over n tiles); the V accumulator
runs in the j==0 lane so every (i,k) pair touches it exactly once.

An optional per-row weight/validity mask (padded-batch support for the
masked stacked Map phase) scales the TRANSPOSED operand only — the row
weight enters each product exactly once, so U = Hᵀdiag(m)H and
V = Hᵀdiag(m)T hold for fractional weights, not just binary masks. The
mask rides as an (n, 1) column so its row-block streams with H's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_precision, out_vma, resolve_interpret

BL, BN = 128, 512  # L-tile and n(row)-tile


def _elm_stats_kernel(*refs, nk: int, masked: bool):
    if masked:
        h_i_ref, h_j_ref, t_ref, m_ref, u_ref, v_ref, acc_u, acc_v = refs
    else:
        h_i_ref, h_j_ref, t_ref, u_ref, v_ref, acc_u, acc_v = refs
    j = pl.program_id(1)
    k = pl.program_id(2)

    hi = h_i_ref[...]
    if masked:
        hi = hi * m_ref[...]  # (bn, 1) broadcasts over the bl columns

    @pl.when(k == 0)
    def _zero_u():
        acc_u[...] = jnp.zeros_like(acc_u)

    hj = h_j_ref[...]
    acc_u[...] += jnp.dot(hi.T, hj, precision=f32_precision(hi, hj),
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write_u():
        u_ref[...] = acc_u[...]

    # V lane: only while j == 0 (each (i,k) exactly once)
    @pl.when((j == 0) & (k == 0))
    def _zero_v():
        acc_v[...] = jnp.zeros_like(acc_v)

    @pl.when(j == 0)
    def _acc_v():
        t = t_ref[...]
        acc_v[...] += jnp.dot(hi.T, t, precision=f32_precision(hi, t),
                              preferred_element_type=jnp.float32)

    @pl.when((j == 0) & (k == nk - 1))
    def _write_v():
        v_ref[...] = acc_v[...]


@functools.partial(jax.jit, static_argnames=("bl", "bn", "interpret"))
def _elm_stats(h, t, mask, *, bl: int, bn: int, interpret: bool):
    n, L = h.shape
    n2, C = t.shape
    assert n == n2
    masked = mask is not None
    bl = min(bl, max(L, 8))
    bn = min(bn, max(n, 8))
    Lp, Np = (-(-L // bl)) * bl, (-(-n // bn)) * bn
    Cp = max(C, 8)
    hp = jnp.pad(h, ((0, Np - n), (0, Lp - L)))
    tp = jnp.pad(t, ((0, Np - n), (0, Cp - C)))
    nk = Np // bn
    in_specs = [
        pl.BlockSpec((bn, bl), lambda i, j, k: (k, i)),  # H rows, col-tile i
        pl.BlockSpec((bn, bl), lambda i, j, k: (k, j)),  # H rows, col-tile j
        pl.BlockSpec((bn, Cp), lambda i, j, k: (k, 0)),  # T rows
    ]
    operands = [hp, hp, tp]
    if masked:
        mp = jnp.pad(mask.astype(jnp.float32), (0, Np - n))[:, None]
        in_specs.append(pl.BlockSpec((bn, 1), lambda i, j, k: (k, 0)))
        operands.append(mp)
    vma = out_vma(*operands)
    u, v = pl.pallas_call(
        functools.partial(_elm_stats_kernel, nk=nk, masked=masked),
        grid=(Lp // bl, Lp // bl, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bl, bl), lambda i, j, k: (i, j)),
            pl.BlockSpec((bl, Cp), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Lp, Lp), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((Lp, Cp), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((bl, bl), jnp.float32),
                        pltpu.VMEM((bl, Cp), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return u[:L, :L], v[:L, :C]


def elm_stats(h, t, mask=None, *, bl: int = BL, bn: int = BN,
              interpret: Optional[bool] = None):
    """h: (n, L), t: (n, C), mask: optional (n,) row weights
    -> (U (L,L) f32, V (L,C) f32).

    ``interpret=None`` = auto: compiled on TPU, interpreter elsewhere.
    Resolved outside the jit so the resolved bool is the static cache key."""
    return _elm_stats(h, t, mask, bl=bl, bn=bn,
                      interpret=resolve_interpret(interpret))
