"""Paper Fig. 7 — testing accuracy vs SGD iterations; static vs dynamic
learning rate. Claim: a wrong (too-large static) rate collapses accuracy
(Fig. 7b); the dynamic alpha=c/e rate is stable."""
from __future__ import annotations

import jax

from benchmarks.common import emit, save_result, time_call
from repro.configs.base import get_config
from repro.core import cnn_elm
from repro.core.runner import evaluate_model
from repro.data.partition import partition_iid
from repro.data.synthetic import make_extended_mnist
from repro.models import cnn
from repro.optim.schedules import constant, dynamic_paper


def main():
    cfg = get_config("cnn_elm_6c12c")
    ds = make_extended_mnist(n_per_class=100, seed=0)
    train, test = ds.split(n_test=600, seed=1)
    part = partition_iid(train.x, train.y, 1)[0]
    key = jax.random.PRNGKey(0)
    init = cnn.init_params(cfg, key)

    curves = {}
    for label, sched in (("dynamic_c0.05", dynamic_paper(0.05)),
                         ("static_0.05", constant(0.05)),
                         ("static_2.0_wrong", constant(2.0))):
        accs = []
        for e in range(0, 4):
            model = cnn_elm.train_member(cfg, init, part, epochs=e,
                                         lr_schedule=sched, batch_size=200)
            accs.append(evaluate_model(cfg, model, test.x, test.y))
        curves[label] = accs
        emit(f"fig7_{label}", 0.0,
             ";".join(f"e{e}={a:.4f}" for e, a in enumerate(accs)))
    save_result("fig7_iterations", curves)
    return curves


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
