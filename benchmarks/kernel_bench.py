"""Kernel micro-benchmarks.

Pallas interpret mode executes the kernel body in Python (correctness
only — wall time is meaningless for the TPU target), so the timed numbers
here are the XLA fallback paths; the Pallas kernels are validated via
allclose and characterised by their BlockSpec tiling (reported as derived
columns: VMEM working set, MXU utilisation of the tile shape).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_result, time_call
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.conv2d.kernel import LANES
from repro.kernels.elm_stats import ops as elm_ops
from repro.kernels.swa_attention import ops as swa_ops


def main():
    rng = np.random.default_rng(0)
    out = {}

    # conv2d — the paper's hot spot at its own geometry (28x28 k=5)
    # in the kernel's (C, H, W, B) layout, a grid step per LANES images
    x = jnp.asarray(rng.normal(size=(1, 28, 28, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 1, 6)).astype(np.float32))
    us = time_call(lambda a, b: conv_ops.conv2d_valid(a, b), x, w)
    vmem_kib = (1 * 28 * 32 + 6 * 24 * 24) * LANES * 4 / 1024
    emit("conv2d_28x28_k5_b256", us,
         f"block={LANES}_images;vmem_block_KiB={vmem_kib:.0f}")
    out["conv2d_us"] = us

    # fused elm stats vs two separate GEMMs (HBM-reuse argument)
    h = jnp.asarray(rng.normal(size=(100_000, 192)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(100_000, 10)).astype(np.float32))
    us_fused_path = time_call(lambda a, b: elm_ops.elm_stats(a, b), h, t)
    emit("elm_stats_n100k_L192", us_fused_path,
         "fused_U_V;hbm_reads_of_H=1(vs 2 unfused)")
    out["elm_stats_us"] = us_fused_path

    # fused rmsnorm: 1 HBM round-trip vs 3 unfused
    from repro.kernels.rmsnorm import ops as rms_ops
    xr = jnp.asarray(rng.normal(size=(8, 4096, 2560)).astype(np.float32))
    sc = jnp.ones((2560,), jnp.float32)
    us_rms = time_call(lambda a, s: rms_ops.rmsnorm(a, s), xr, sc)
    emit("rmsnorm_8x4096x2560", us_rms,
         "fused=1_hbm_round_trip;unfused=3;block_rows=256")
    out["rmsnorm_us"] = us_rms

    # sliding-window attention: O(S*W) vs O(S^2) reference
    q = jnp.asarray(rng.normal(size=(8, 2048, 64)).astype(np.float32))
    us_swa = time_call(
        lambda a: swa_ops.swa_attention(a, a, a, window=256), q)
    us_full = time_call(
        lambda a: swa_ops.swa_attention(a, a, a, window=2048), q)
    emit("swa_attention_S2048_W256", us_swa,
         f"vs_full_window_us={us_full:.0f};flops_ratio={2048/256:.0f}x")
    out["swa_us"] = us_swa
    save_result("kernel_bench", out)
    return out


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
