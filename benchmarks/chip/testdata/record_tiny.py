"""Record ``spans_tiny.xplane.pb.gz``, the small traced window the tests of
``chipbench.oppaths`` and of the metric readers read, on one TPU chip:

  python3 benchmarks/chip/testdata/record_tiny.py

Inside one ``bench.window`` span it runs one Map+Reduce job of the
3c-2s-9c-2s configuration (k=4 members of 50 random images, batches of
10, two SGD epochs in two rounds, so that every device scope runs) and
then sends 7 single images to an ``EnsembleServer`` over the job's
members (max_batch 4, max_wait 2 ms), two at a time then one at a time.
Everything compiles in a warm-up job and burst before the trace starts.
Prints the job's work counts and the server's counters, which the tests
hold the readers to.
"""
import gzip
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import work  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core.runner import AveragingRun, MapConfig, ReduceConfig  # noqa: E402
from repro.data.partition import partition_iid  # noqa: E402
from repro.optim.schedules import dynamic_paper  # noqa: E402
from repro.serve import EnsembleServer, ServeConfig  # noqa: E402

OUT = os.path.join(HERE, "spans_tiny.xplane.pb.gz")
K, ROWS, BATCH, EPOCHS = 4, 50, 10, 2


def main():
    jax.config.update("jax_default_matmul_precision", "highest")
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "cnn_elm_3c9c.json")) as f:
        model = json.load(f)["model"]
    cfg = get_config("cnn_elm_3c9c")
    rng = np.random.default_rng(0)
    x = rng.random((K * ROWS, 28, 28), dtype=np.float32)
    y = rng.integers(0, cfg.num_classes, K * ROWS)
    parts = partition_iid(x, y, K, seed=0)
    run = AveragingRun(cfg, MapConfig(epochs=EPOCHS, batch_size=BATCH,
                                      lr_schedule=dynamic_paper(0.05),
                                      backend="stacked"),
                       ReduceConfig(rounds=2))
    res = run.run(parts, jax.random.PRNGKey(0))          # warm-up job
    server = EnsembleServer(res.ensemble().bucketed_scorer(max_batch=4),
                            ServeConfig(max_batch=4, max_wait_ms=2.0)
                            ).start()
    for f in server.submit_many(x[:3]):
        f.result(timeout=60)
    before = server.stats()

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.job"):
            res = run.run(parts, jax.random.PRNGKey(1))
            jax.block_until_ready((res.averaged.beta, res.stacked.beta))
        futures = []
        for group in ([0, 1], [2, 3], [4], [5], [6]):
            futures += server.submit_many(x[group])
            time.sleep(0.004)
        for f in futures:
            f.result(timeout=60)
    jax.profiler.stop_trace()
    after = server.stats()
    server.close()

    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as src, gzip.open(OUT, "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp)
    job = work.job(model, K, ROWS // BATCH, BATCH, EPOCHS)
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "trace": OUT,
        "bytes": os.path.getsize(OUT),
        "work": {k: job[k] for k in ("conv_flops", "conv_bytes")},
        "completed": after.completed - before.completed,
        "batches": after.batches - before.batches}))


if __name__ == "__main__":
    main()
