"""Paper Tables 4 & 5 — extended MNIST (IID partitions), 6c-2s-12c-2s.

Claim under test: with same-distribution partitions, the averaged CNN-ELM
matches the no-partition model (92.24 vs 92.23 at e=0; 92.40 vs 92.41 at
e=5). We reproduce the ORDERING/GAP structure on the synthetic analogue:
    |acc(average_k) - acc(monolithic)| small;  every member ~ monolithic.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import emit, save_result
from repro.configs.base import get_config, replace
from repro.core import cnn_elm
from repro.core.runner import (AveragingRun, MapConfig, ReduceConfig,
                               evaluate_model, kappa_model)
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

# CPU-scaled geometry: full 6c-12c kernels, smaller corpus than 240k
N_PER_CLASS = 150
K = 4
BATCH = 200


def run(epochs: int):
    cfg = get_config("cnn_elm_6c12c")
    ds = make_extended_mnist(n_per_class=N_PER_CLASS, seed=0)
    train, test = ds.split(n_test=800, seed=1)
    key = jax.random.PRNGKey(0)

    t0 = time.perf_counter()
    mono = cnn_elm.train_member(
        cfg, cnn.init_params(cfg, key),
        partition_iid(train.x, train.y, 1)[0], epochs=epochs,
        lr_schedule=dynamic_paper(0.05), batch_size=BATCH)
    t_mono = time.perf_counter() - t0

    # sequential backend: the members-run-one-after-another simulation the
    # scale-out time model below divides by K
    parts = partition_iid(train.x, train.y, K, seed=0)
    res = AveragingRun(
        cfg,
        MapConfig(epochs=epochs, lr_schedule=dynamic_paper(0.05),
                  batch_size=BATCH, backend="sequential"),
        ReduceConfig()).run(parts, key)

    # all K members scored through the batched ensemble surface: one
    # stacked dispatch per eval batch instead of a K-model Python loop
    member_accs = res.ensemble().evaluate(test.x, test.y)
    accs = {f"member_{i+1}_of_{K}": float(a)
            for i, a in enumerate(member_accs)}
    accs["monolithic"] = evaluate_model(cfg, mono, test.x, test.y)
    accs[f"average_{K}"] = evaluate_model(cfg, res.averaged, test.x, test.y)
    accs["kappa_average"] = kappa_model(cfg, res.averaged, test.x, test.y)
    # scale-out time model: parallel wall-time = slowest member (map) ~ total/K
    timing = {"t_monolithic_s": t_mono,
              "t_members_sequential_s": res.wall_time_s,
              "t_parallel_critical_path_s": res.wall_time_s / K}
    return accs, timing


def main():
    out = {}
    for epochs, table in ((0, "table4"), (2, "table5")):
        accs, timing = run(epochs)
        out[table] = {"epochs": epochs, **accs, **timing}
        gap = abs(accs[f"average_{K}"] - accs["monolithic"])
        emit(f"{table}_avg{K}_vs_mono_gap",
             timing["t_members_sequential_s"] * 1e6,
             f"acc_avg={accs[f'average_{K}']:.4f};acc_mono="
             f"{accs['monolithic']:.4f};gap={gap:.4f};"
             f"speedup={timing['t_monolithic_s']/timing['t_parallel_critical_path_s']:.2f}x")
    save_result("table45_mnist", out)
    return out


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
