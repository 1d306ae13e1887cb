"""Serving launcher — two production paths behind one CLI.

**LM decode** (the default): batched prefill + decode for any decoder
arch (the same serve_step the dry-run lowers for decode_32k /
long_500k): prefill a batch of prompts, then decode N tokens against
the (ring-buffer / SSM) cache, reporting tokens/s.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_3b --reduced \
      --batch 4 --prompt-len 64 --gen 32

**CNN-ELM ensemble** (``--ensemble``): the ``repro.serve`` endpoint —
continuous batching under a latency SLO over a ``BucketedScorer``
(bucketed batch shapes, one XLA compile per bucket), driven by the
open-loop load generator; with ``--ckpt-dir`` it serves a training
run's newest ``round-<r>.npz`` and hot-reloads newer rounds live
(docs/serving.md).

  # self-contained: train k members, then serve synthetic open-loop load
  PYTHONPATH=src python -m repro.launch.serve --ensemble --k 4 \
      --rate 200 --requests 400
  # track a live training run's checkpoints
  PYTHONPATH=src python -m repro.launch.serve --ensemble \
      --ckpt-dir /path/to/run --rate 200 --requests 400
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_reduced_config, replace
from repro.core import trainer
from repro.launch.cache import use_compile_cache
from repro.models import api


def run_lm(args) -> dict:
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step "
                         "(see DESIGN.md §5)")
    if cfg.ssm_chunk > args.prompt_len:
        cfg = replace(cfg, ssm_chunk=max(8, args.prompt_len // 4))

    key = jax.random.PRNGKey(args.seed)
    params = api.init_params(cfg, key)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    prefill_fn = jax.jit(trainer.make_prefill_step(cfg))
    serve_fn = jax.jit(trainer.make_serve_step(cfg),
                       donate_argnums=(1,))

    t0 = time.time()
    logits, cache = prefill_fn(params, {"tokens": prompts})
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    # cache from prefill covers prompt_len; decode continues after it — for
    # transformer caches we re-init at full length to hold generated tokens
    if cfg.family in ("dense", "moe", "vlm"):
        total = args.prompt_len + args.gen
        cache = api.init_cache(cfg, args.batch, total)
        # replay prompt into the fresh cache (production would size prefill
        # cache up front; kept simple here)
        for t in range(args.prompt_len):
            logits, cache = serve_fn(params, cache, prompts[:, t:t + 1],
                                     jnp.asarray(t, jnp.int32))

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    for t in range(args.gen - 1):
        pos = jnp.asarray(args.prompt_len + t, jnp.int32)
        logits, cache = serve_fn(params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    out = jnp.concatenate(generated, axis=1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"# arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"# prefill: {t_prefill*1e3:.1f} ms   decode: {tps:.1f} tok/s")
    print("# sample token ids:", np.asarray(out[0, :16]).tolist())
    assert np.all(np.asarray(out) >= 0)
    return {"prefill_ms": t_prefill * 1e3, "tokens_per_s": tps}


def run_ensemble(args) -> dict:
    """The CNN-ELM ensemble endpoint: serve from ``--ckpt-dir`` (hot-
    reloading newer rounds) or from a freshly trained k-member run, then
    offer open-loop load and report tail latency."""
    from repro.checkpoint import run_state
    from repro.core.runner import AveragingRun, MapConfig, ReduceConfig
    from repro.data.partition import partition_iid
    from repro.data.synthetic import make_extended_mnist
    from repro.serve import (BucketedScorer, CheckpointWatcher,
                             EnsembleServer, ServeConfig, run_open_loop)

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family != "cnn":
        raise SystemExit(f"--ensemble serves CNN-ELM archs; {cfg.name} is "
                         f"family {cfg.family!r} (drop --ensemble for the "
                         "LM decode path)")
    ds = make_extended_mnist(n_per_class=60, seed=args.seed)
    train, test = ds.split(n_test=200)

    watcher = None
    if args.ckpt_dir:
        r = run_state.latest_ready_round(args.ckpt_dir)
        if r is None:
            raise SystemExit(f"no fully-written round-<r>.npz in "
                             f"{args.ckpt_dir}")
        members = run_state.restore_round(args.ckpt_dir, r).members
        print(f"# serving round {r} from {args.ckpt_dir} "
              f"(k={members.k}, hot-reload on)")
    else:
        result = AveragingRun(
            cfg, MapConfig(epochs=0, batch_size=200, backend="stacked"),
            ReduceConfig()).run(partition_iid(train.x, train.y, args.k),
                                jax.random.PRNGKey(args.seed))
        members = result.stacked
        print(f"# trained k={args.k} members in {result.wall_time_s:.1f}s")

    scorer = BucketedScorer(cfg, members, max_batch=args.max_batch)
    server = EnsembleServer(scorer, ServeConfig(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        combine=args.combine)).start()
    if args.ckpt_dir:
        watcher = CheckpointWatcher(args.ckpt_dir, server,
                                    poll_ms=args.poll_ms,
                                    start_round=r).start()
    print(f"# buckets {scorer.ladder.buckets} — "
          f"{scorer.compile_count()} compiles (one per bucket, pinned)")

    rep = run_open_loop(server, test.x, rate_per_s=args.rate,
                        n_requests=args.requests, seed=args.seed)
    if watcher is not None:
        watcher.stop()
    server.close()
    stats = server.stats()
    scorer.assert_compile_budget()
    swaps = len(watcher.swaps) if watcher is not None else 0
    print(f"# offered {rep.offered_per_s:.0f}/s → achieved "
          f"{rep.achieved_per_s:.0f} imgs/s   p50 {rep.p50_ms:.2f} ms  "
          f"p95 {rep.p95_ms:.2f} ms  p99 {rep.p99_ms:.2f} ms")
    print(f"# {stats.completed} answered, {stats.failed} failed, "
          f"{stats.dropped} dropped, {swaps} hot swaps, "
          f"mean batch occupancy {stats.mean_occupancy:.1f}")
    return {"images_per_s": rep.achieved_per_s, "p50_ms": rep.p50_ms,
            "p95_ms": rep.p95_ms, "p99_ms": rep.p99_ms,
            "compile_count": stats.compile_count, "swaps": swaps}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="default: qwen3_8b (LM) / cnn_elm_6c12c "
                         "(--ensemble)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # LM decode path
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--greedy", action="store_true", default=True)
    # CNN-ELM ensemble path
    ap.add_argument("--ensemble", action="store_true",
                    help="serve a CNN-ELM ensemble (repro.serve) instead "
                         "of LM decode")
    ap.add_argument("--k", type=int, default=4,
                    help="members to train when no --ckpt-dir is given")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve (and hot-reload) a training run's "
                         "round-<r>.npz checkpoints")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--combine", default="mean", choices=("mean", "vote"))
    ap.add_argument("--poll-ms", type=float, default=50.0)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="offered open-loop load, images/s")
    ap.add_argument("--requests", type=int, default=400)
    args = ap.parse_args(argv)
    if args.arch is None:
        args.arch = "cnn_elm_6c12c" if args.ensemble else "qwen3_8b"
    return run_ensemble(args) if args.ensemble else run_lm(args)


if __name__ == "__main__":
    use_compile_cache()
    main()
