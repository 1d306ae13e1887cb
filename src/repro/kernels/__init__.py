# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared backend policy for the Pallas kernel dispatchers.

The conv2d and elm_stats dispatchers (the CNN-ELM hot path) take
``use_pallas`` and their kernels ``interpret``; both default to ``None`` =
*auto* (rmsnorm and swa_attention still use explicit bools — migrate them
when their model families hit a hot path):

* on TPU  -> Pallas kernels run COMPILED (``use_pallas=True, interpret=False``)
* elsewhere -> XLA reference path by default; if a caller forces
  ``use_pallas=True`` the kernel runs in interpret mode (the kernel body
  executes in Python, validating the BlockSpec program for the TPU target).

Environment overrides (for benchmarking / CI matrix runs):

* ``REPRO_USE_PALLAS=0|1``       — force the dispatcher decision
* ``REPRO_PALLAS_INTERPRET=0|1`` — force interpret mode on/off

Both flags resolve OUTSIDE the dispatcher jits, so the resolved bool is the
static cache key: each combination compiles once and an env-var change
takes effect on the next direct call. (A dispatcher traced inside an
enclosing jit bakes the resolution current at that trace into that cache
entry, as any env-dependent jit does.)
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _env_flag(name: str) -> Optional[bool]:
    raw = os.environ.get(name, "").strip().lower()
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    return None


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """None = auto: Pallas on TPU, XLA reference elsewhere."""
    if use_pallas is not None:
        return bool(use_pallas)
    env = _env_flag("REPRO_USE_PALLAS")
    if env is not None:
        return env
    return on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = auto: compiled on TPU, interpreter as the CPU fallback."""
    if interpret is not None:
        return bool(interpret)
    env = _env_flag("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env
    return not on_tpu()


def out_vma(*operands) -> frozenset:
    """The mesh axes a kernel's output varies over: the union of its
    operands'. A ``pallas_call`` inside ``shard_map`` (``check_vma`` on)
    needs this on every ``out_shape``; outside one it is empty."""
    return frozenset().union(*(jax.typeof(a).vma for a in operands))


def f32_precision(*operands):
    """Dot precision inside the kernels: full f32 products when every
    operand is f32 (the compiler's default may take fewer bf16 passes, and
    the ELM normal equations are ill-conditioned enough to show it);
    the default for narrower inputs, which Mosaic refuses to widen."""
    if all(a.dtype == jnp.float32 for a in operands):
        return jax.lax.Precision.HIGHEST
    return None
