"""Paper Tables 2 & 3 — not-MNIST (class-skewed partitions), 3c-2s-9c-2s.

Claims under test (synthetic analogue):
  1. members trained on skewed shards are far below the monolithic model
     (paper: 40.5/40.4 and 20-31 vs 72.9);
  2. the average recovers much of the gap but NOT all (67.9 at k=2);
  3. more partitions -> worse average (60.8 at k=5 < 67.9 at k=2);
  4. iterations do not rescue non-IID averaging (Table 3 vs 2).
"""
from __future__ import annotations

import jax

from benchmarks.common import emit, save_result
from repro.configs.base import get_config
from repro.core import cnn_elm
from repro.core.runner import (AveragingRun, MapConfig, ReduceConfig,
                               evaluate_model)
from repro.data.partition import partition_by_class, partition_iid
from repro.data.synthetic import make_not_mnist
from repro.models import cnn
from repro.optim.schedules import dynamic_paper

N_PER_CLASS = 120
BATCH = 200


def run(epochs: int):
    cfg = get_config("cnn_elm_3c9c")
    ds = make_not_mnist(n_per_class=N_PER_CLASS, seed=1)
    train, test = ds.split(n_test=800, seed=2)
    key = jax.random.PRNGKey(0)

    mono = cnn_elm.train_member(
        cfg, cnn.init_params(cfg, key),
        partition_iid(train.x, train.y, 1)[0], epochs=epochs,
        lr_schedule=dynamic_paper(0.05), batch_size=BATCH)
    res = {"monolithic": evaluate_model(cfg, mono, test.x, test.y)}

    for k in (2, 5):
        parts = partition_by_class(train.x, train.y, k)
        rr = AveragingRun(
            cfg,
            MapConfig(epochs=epochs, lr_schedule=dynamic_paper(0.05),
                      batch_size=BATCH, backend="sequential"),
            ReduceConfig()).run(parts, key)
        # every member scored in one batched ensemble pass
        for i, a in enumerate(rr.ensemble().evaluate(test.x, test.y)):
            res[f"member_{i+1}_of_{k}"] = float(a)
        res[f"average_{k}"] = evaluate_model(cfg, rr.averaged, test.x, test.y)
        res[f"t_total_{k}_s"] = rr.wall_time_s
    return res


def main():
    out = {}
    for epochs, table in ((0, "table2"), (2, "table3")):
        res = run(epochs)
        out[table] = {"epochs": epochs, **res}
        emit(f"{table}_noniid", res.get("t_total_2_s", 0) * 1e6,
             f"mono={res['monolithic']:.4f};avg2={res['average_2']:.4f};"
             f"avg5={res['average_5']:.4f};"
             f"worst_member={min(v for k2, v in res.items() if k2.startswith('member')):.4f}")
    save_result("table23_notmnist", out)
    return out


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
