"""Smoke test of the CNN-ELM Map+Reduce path on a TPU, through the library
surface a user calls (the quickstart and ``launch/serve.py --ensemble``).

One chip (the default):

  python chip_smoke.py [--seed 0]

* data   — ``make_extended_mnist(n_per_class=1500)``: 60,000 images,
  10,000 held out for test, the rest split IID over k=4 members;
* kernels — the SGD epoch step at the published 6c-2s-12c-2s width, lowered
  and compiled; it must hold the Pallas kernels (``tpu_custom_call``);
* elm_only — ``epochs=0`` through the kernels; every member's β and the
  averaged β must match the plain-XLA f32 reference (``use_pallas=False``)
  within the elm_stats kernel tolerance of ``tests/test_kernels.py``;
* map_sgd — one SGD epoch (Alg. 2 lines 13-14 through the conv kernel's
  VJP), 62 steps per member, then the Reduce;
* evaluate — the averaged model and the k members on the 10,000 test
  images; the averaged model must be well above chance;
* reference_sgd — the same Map on the XLA reference; the averaged model's
  accuracy must be within 1 point of the kernel path's;
* serve — an ``EnsembleServer`` over the trained members answers 32
  requests: none fails or is dropped, and every answer agrees with the
  batched ``Ensemble`` scores.

Four chips (``--four-chips``) run only the scale-out path: the stacked
executor on the flat ``('pod',)`` mesh of four chips and on
``make_member_mesh(hosts=2)``, one member per chip, against the same
executor on the one-device member mesh (device 0). It shows the
members on 4 distinct devices, the sync's all-reduce count (1 flat, 2 on
``('host', 'pod')``) from the compiled programs, the ELM-only β equal to
stacked within the kernel tolerance, and the averaged model after the SGD
epoch within 1 point of stacked's accuracy.

The script refuses to run without a TPU, with the kernels forced off or
into interpret mode, and without the repo's ``src/`` beside it. Every phase
prints one line; wall seconds of a phase include its compiles (cold unless
the compile cache was warm) and are set-up, not speed. The last line is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import elm, executor  # noqa: E402
from repro.core.averaging import broadcast_member_dim  # noqa: E402
from repro.core.runner import (AveragingRun, Ensemble, MapConfig,  # noqa: E402
                               ReduceConfig, evaluate_model)
from repro.data.partition import partition_iid  # noqa: E402
from repro.data.synthetic import make_extended_mnist  # noqa: E402
from repro.kernels import resolve_interpret, resolve_use_pallas  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.optim.schedules import dynamic_paper  # noqa: E402

ARCH = "cnn_elm_6c12c"
N_PER_CLASS = 1500          # x 10 classes x 4 (clean + 3 noises) = 60,000
N_TEST = 10_000
K = 4
BATCH = 200
LR = 0.05
N_REQUESTS = 32
# tests/test_kernels.py's elm_stats tolerance, applied to β
BETA_RTOL, BETA_ATOL = 1e-4, 1e-3
ACC_GAP = 0.01              # kernel path vs reference, averaged model
MIN_ACC = 0.5               # chance is 0.1


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def phase(name: str, t0: float, **fields):
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase={name} ok wall_s={time.perf_counter() - t0:.3f} "
          f"(set-up: includes compile) {items}", flush=True)


def require_chip(n_chips: int):
    """No hidden fallback: the TPU backend, the compiled Pallas kernels."""
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX backend is "
                 f"{jax.default_backend()!r})")
    if not resolve_use_pallas(None) or resolve_interpret(None):
        sys.exit("chip_smoke: the kernel policy is overridden "
                 "(REPRO_USE_PALLAS=0 or REPRO_PALLAS_INTERPRET=1); the "
                 "smoke runs the compiled Pallas kernels only")
    if len(jax.devices()) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                 f"{len(jax.devices())}")


def load_data(seed: int):
    t0 = time.perf_counter()
    ds = make_extended_mnist(n_per_class=N_PER_CLASS, seed=seed)
    train, test = ds.split(n_test=N_TEST, seed=seed)
    parts = partition_iid(train.x, train.y, K, seed=seed)
    phase("data", t0, images=len(ds.x), test=len(test.x), members=K,
          rows_per_member=len(parts[0].x))
    return parts, test


def map_run(cfg, parts, key, *, epochs: int, use_pallas=None,
            backend: str = "stacked", mesh=None):
    lr = dynamic_paper(LR) if epochs else None
    return AveragingRun(cfg, MapConfig(epochs=epochs, lr_schedule=lr,
                                       batch_size=BATCH, backend=backend,
                                       use_pallas=use_pallas, mesh=mesh),
                        ReduceConfig()).run(parts, key)


def reference(fn, *args, **kwargs):
    """The plain f32 reference: XLA conv and stats (``use_pallas=False``)
    at full f32 matmul precision, which the TPU does not take by default."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, use_pallas=False, **kwargs)


def epoch_kernel_count(cfg, nb: int) -> int:
    """Compile the SGD epoch step the stacked Map dispatches and count the
    Pallas kernels in it."""
    F, C = cnn.feature_dim(cfg), cfg.num_classes
    params_k = jax.eval_shape(lambda: broadcast_member_dim(
        cnn.init_params(cfg, jax.random.PRNGKey(0)), K))
    stats_k = jax.eval_shape(lambda: elm.zero_stats_stacked(K, F, C))
    s = jax.ShapeDtypeStruct
    size = cfg.image_size
    lowered = executor._stacked_epoch.lower(
        cfg, params_k, stats_k, s((nb, K, BATCH, size, size), jnp.float32),
        s((nb, K, BATCH, C), jnp.float32), s((nb, K), jnp.float32),
        s((), jnp.float32), solve_each_batch=True,
        use_pallas=resolve_use_pallas(None), masked=False)
    return lowered.compile().as_text().count("tpu_custom_call")


def max_abs(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def tolerance_excess(got, want, rtol: float, atol: float) -> float:
    """max |got - want| / (atol + rtol·|want|): at most 1 is within
    ``np.testing.assert_allclose(got, want, rtol, atol)``."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want) / (atol + rtol * np.abs(want))))


def _beta_pairs(res, ref):
    return [(m.beta, r.beta) for m, r in zip(res.members + [res.averaged],
                                             ref.members + [ref.averaged])]


def beta_diff(res, ref) -> float:
    return max(max_abs(g, w) for g, w in _beta_pairs(res, ref))


def beta_excess(res, ref) -> float:
    """Every member's β and the averaged β against ``ref`` in units of the
    elm_stats kernel tolerance: at most 1 passes."""
    return max(tolerance_excess(g, w, BETA_RTOL, BETA_ATOL)
               for g, w in _beta_pairs(res, ref))


def serve(cfg, result, test):
    from repro.serve import EnsembleServer, ServeConfig
    t0 = time.perf_counter()
    ens = result.ensemble()
    images = test.x[:N_REQUESTS]
    server = EnsembleServer(ens.bucketed_scorer(max_batch=N_REQUESTS),
                            ServeConfig(max_batch=N_REQUESTS)).start()
    try:
        futures = server.submit_many(images)
        answers = [f.result(timeout=300) for f in futures]   # raises errors
    finally:
        server.close()
    stats = server.stats()
    check(stats.failed == 0 and stats.dropped == 0
          and stats.completed == N_REQUESTS,
          f"server: {stats.completed} answered, {stats.failed} failed, "
          f"{stats.dropped} dropped of {N_REQUESTS}")
    want = ens.member_scores(images)                         # (k, n, C)
    got = np.stack([a.member_scores for a in answers], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    check([a.label for a in answers] == list(ens.predict(images)),
          "served labels differ from the batched ensemble's")
    phase("serve", t0, requests=N_REQUESTS, failed=stats.failed,
          dropped=stats.dropped, batches=stats.batches,
          compiles=stats.compile_count)


def one_chip(seed: int):
    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(seed)
    parts, test = load_data(seed)

    t0 = time.perf_counter()
    n_kernels = epoch_kernel_count(cfg, nb=len(parts[0].x) // BATCH)
    check(n_kernels > 0, "the SGD epoch step holds no tpu_custom_call")
    phase("kernels", t0, tpu_custom_call=n_kernels)

    t0 = time.perf_counter()
    elm_only = map_run(cfg, parts, key, epochs=0)
    elm_ref = reference(map_run, cfg, parts, key, epochs=0)
    excess = beta_excess(elm_only, elm_ref)
    check(excess <= 1, f"ELM-only β off the reference by {excess} times "
                       f"the tolerance (rtol {BETA_RTOL}, atol {BETA_ATOL})")
    phase("elm_only", t0, beta_max_abs_diff=beta_diff(elm_only, elm_ref),
          beta_excess=excess, beta_rtol=BETA_RTOL, beta_atol=BETA_ATOL)

    t0 = time.perf_counter()
    result = map_run(cfg, parts, key, epochs=1)
    phase("map_sgd", t0, steps_per_member=len(parts[0].x) // BATCH,
          dispatches=result.dispatches)

    t0 = time.perf_counter()
    acc = evaluate_model(cfg, result.averaged, test.x, test.y)
    member_acc = result.ensemble().evaluate(test.x, test.y)
    check(np.isfinite(acc) and acc > MIN_ACC,
          f"averaged-model accuracy {acc} not above {MIN_ACC}")
    phase("evaluate", t0, averaged_acc=acc,
          member_acc=",".join(f"{a:.4f}" for a in member_acc))

    t0 = time.perf_counter()
    ref = reference(map_run, cfg, parts, key, epochs=1)
    ref_acc = reference(evaluate_model, cfg, ref.averaged, test.x, test.y)
    check(abs(acc - ref_acc) <= ACC_GAP,
          f"averaged accuracy {acc} vs reference {ref_acc}: gap above "
          f"{ACC_GAP}")
    phase("reference_sgd", t0, reference_acc=ref_acc,
          gap=abs(acc - ref_acc))

    serve(cfg, result, test)


def four_chips(seed: int):
    """Four chips vs the one-device member mesh. The ELM-only pass is
    held to the kernel β tolerance. After the SGD epoch the two agree through their predictions
    only: on the chip the k=1 shard and the k=4 batch compile to different
    summation orders, and 62 steps that each re-solve β from a
    ~1e6-conditioned normal matrix amplify that rounding."""
    from repro.analysis.hlo import audit_executor
    from repro.distributed import sharding
    from repro.launch.mesh import make_member_mesh

    cfg = get_config(ARCH)
    key = jax.random.PRNGKey(seed)
    parts, test = load_data(seed)

    t0 = time.perf_counter()
    stacked_elm = map_run(cfg, parts, key, epochs=0)
    stacked = map_run(cfg, parts, key, epochs=1)
    acc = evaluate_model(cfg, stacked.averaged, test.x, test.y)
    labels = Ensemble.from_models(cfg, [stacked.averaged]).predict(test.x)
    phase("stacked_device0", t0, averaged_acc=acc)

    for name, mesh in (("mesh_flat", make_member_mesh()),
                       ("mesh_host_pod", make_member_mesh(hosts=2))):
        t0 = time.perf_counter()
        init = broadcast_member_dim(cnn.init_params(cfg, key), K)
        placed = jax.device_put(init, sharding.member_dim_shardings(init,
                                                                    mesh))
        devices = {s.device for leaf in jax.tree.leaves(placed)
                   for s in leaf.addressable_shards}
        check(len(devices) == 4, f"{name}: members on {len(devices)} "
                                 f"devices, not 4")
        # the auditor compiles the mesh programs and holds the sync and
        # the Reduce to exactly `want` all-reduces (flat 1, host/pod 2)
        reports = {r.program: r.raise_if_failed()
                   for r in audit_executor(cfg, "mesh", mesh=mesh, k=K)}
        collectives = {name: next(c.detail for c in reports[name].checks
                                  if "all-reduce" in c.name)
                       for name in ("mesh/_sync", "mesh/_reduce")}

        elm_only = map_run(cfg, parts, key, epochs=0, backend="mesh",
                           mesh=mesh)
        res = map_run(cfg, parts, key, epochs=1, backend="mesh", mesh=mesh)
        mesh_acc = evaluate_model(cfg, res.averaged, test.x, test.y)
        mesh_labels = Ensemble.from_models(cfg, [res.averaged]).predict(
            test.x)
        params = [(la, lb) for a, b in zip(res.members + [res.averaged],
                                           stacked.members
                                           + [stacked.averaged])
                  for la, lb in zip(jax.tree.leaves(a.cnn_params),
                                    jax.tree.leaves(b.cnn_params))]
        fields = dict(
            devices=len(devices),
            sync=collectives["mesh/_sync"],
            reduce=collectives["mesh/_reduce"],
            elm_only_beta_max_abs_diff=beta_diff(elm_only, stacked_elm),
            elm_only_beta_excess=beta_excess(elm_only, stacked_elm),
            averaged_acc=mesh_acc,
            label_agreement=float(np.mean(mesh_labels == labels)),
            sgd_params_max_abs_diff=max(max_abs(a, b) for a, b in params),
            sgd_beta_max_abs_diff=beta_diff(res, stacked))
        check(fields["elm_only_beta_excess"] <= 1
              and abs(mesh_acc - acc) <= ACC_GAP,
              f"{name}: mesh differs from stacked (accuracy {acc}): "
              f"{fields}")
        phase(name, t0, **fields)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip member mesh against the "
                         "one-device member mesh on device 0")
    args = ap.parse_args(argv)
    require_chip(4 if args.four_chips else 1)
    cache = use_compile_cache()
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache}", flush=True)
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
