"""Training launcher — distributed-averaging (paper Alg. 1/2) over any
assigned architecture, on whatever devices exist.

On real hardware each member occupies one pod (the dry-run lowers that
exact layout); on this CPU container the members are simulated
sequentially — the algorithm (disjoint partitions, zero communication
between averaging events, weight-average reduce) is identical.

Sync policies (``--sync-policy``): ``cadence`` is the fixed
``--avg-period``/``--rounds`` contract above; ``drift`` replaces it with
drift-TRIGGERED averaging — each member's per-step loss (computed at the
pre-update params, i.e. prequentially) feeds a
``repro.stream.DriftDetector`` (score = -loss) and an averaging event
fires while ANY member is drifting. ``--drift-at N`` injects a
distribution shift at step N (every member's token stream switches
domains) to exercise the recovery loop; the CNN-ELM analogue, with
sliding-window ELM stats, lives in ``repro.stream`` / docs/streaming.md.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b --reduced \
      --steps 50 --members 4 --avg-period 10
  PYTHONPATH=src python -m repro.launch.train --preset lm100m --steps 200
  PYTHONPATH=src python -m repro.launch.train --preset lm100m --steps 60 \
      --non-iid --sync-policy drift --drift-at 30 --drift-threshold 0.5
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import list_steps, restore_checkpoint, save_checkpoint
from repro.configs.base import get_config, get_reduced_config, replace
from repro.core import trainer
from repro.core.averaging import average_trees
from repro.data.lm_data import TokenDatasetSpec, synthetic_token_batches
from repro.launch.cache import use_compile_cache
from repro.models import api

# a ~100M-param dense config for the end-to-end example driver
LM100M = dict(name="lm100m", family="dense", num_layers=12, d_model=768,
              num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
              vocab_size=32768)


def make_cfg(args):
    if args.preset == "lm100m":
        from repro.configs.base import ArchConfig
        return ArchConfig(**LM100M)
    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.seq and cfg.ssm_chunk > args.seq:
        cfg = replace(cfg, ssm_chunk=max(8, args.seq // 4))
    return cfg


def make_batch_fn(cfg, args, member: int, seed_offset: int = 0):
    """Member-partitioned data stream: disjoint domains when --non-iid
    (the paper's not-MNIST regime), all domains otherwise. A non-zero
    ``seed_offset`` re-seeds the domain mixtures — the --drift-at
    injected distribution shift (same member/domain layout, new
    concept)."""
    spec = TokenDatasetSpec(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            batch_size=args.batch, num_domains=2 * args.members,
                            seed=args.seed + seed_offset)
    if args.non_iid:
        domains = [2 * member, 2 * member + 1]
    else:
        domains = None
    gen = synthetic_token_batches(spec, member=member, domains=domains)

    def next_batch():
        toks, tgt = next(gen)
        return {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgt)}

    return next_batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--preset", choices=["", "lm100m"], default="")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--members", type=int, default=2)
    ap.add_argument("--avg-period", type=int, default=0,
                    help="0 = single final average (paper-faithful)")
    ap.add_argument("--rounds", type=int, default=0,
                    help="spread R averaging events over --steps (the "
                         "parallel-SGD rounds contract, same as "
                         "runner.ReduceConfig(rounds=R)); overrides "
                         "--avg-period; 0 = use --avg-period")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", choices=["adamw", "sgd", "momentum"],
                    default="adamw")
    ap.add_argument("--schedule", choices=["constant", "cosine", "wsd",
                                           "dynamic"], default="cosine")
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--sync-policy", choices=["cadence", "drift"],
                    default="cadence",
                    help="cadence = --avg-period/--rounds; drift = fire an "
                         "averaging event while any member's DriftDetector "
                         "(fed -loss prequentially) signals concept drift")
    ap.add_argument("--drift-threshold", type=float, default=0.5,
                    help="score drop below the EWMA baseline that flags "
                         "drift (loss units under --sync-policy drift)")
    ap.add_argument("--drift-alpha", type=float, default=0.2)
    ap.add_argument("--drift-warmup", type=int, default=5)
    ap.add_argument("--drift-at", type=int, default=0,
                    help="inject a distribution shift at this step (every "
                         "member's stream re-seeds its domain mixtures); "
                         "0 = no injected shift")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (full member train "
                         "state: params + optimizer state, atomic "
                         "tmp-rename into --ckpt-dir; 0 = final save only)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest step checkpoint in "
                         "--ckpt-dir: restores every member's params + "
                         "optimizer state and fast-forwards each data "
                         "stream, so the continuation matches the "
                         "uninterrupted run")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    if args.ckpt_every and not args.ckpt_dir:
        raise SystemExit("--ckpt-every needs --ckpt-dir")
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")
    if args.resume and args.drift_at:
        raise SystemExit("--resume does not replay an injected --drift-at "
                         "shift's stream switch — rerun without --resume")
    if args.drift_at < 0:
        raise SystemExit(f"--drift-at must be >= 0, got {args.drift_at}")

    cfg = make_cfg(args)
    opt = {"adamw": optim.adamw, "sgd": optim.sgd,
           "momentum": optim.momentum}[args.optimizer]()
    sched = {
        "constant": lambda: optim.constant(args.lr),
        "cosine": lambda: optim.cosine(args.lr, args.steps,
                                       warmup_steps=max(1, args.steps // 20)),
        "wsd": lambda: optim.wsd(args.lr, max(1, args.steps // 10),
                                 int(args.steps * 0.7), max(1, args.steps // 5)),
        "dynamic": lambda: optim.dynamic_paper(args.lr),
    }[args.schedule]()

    step_fn = jax.jit(trainer.make_train_step(cfg, opt, sched))
    # the rounds contract: --rounds R == one averaging event every
    # steps/R steps (runner.ReduceConfig(rounds=R) at LM scale); each event
    # applies trainer.make_average_step — the exact mean+broadcast program
    # the multi-pod dry-run lowers (pass mesh= for the explicit one-
    # all-reduce shard_map variant on real pods)
    if args.rounds:
        if args.rounds < 1:
            raise SystemExit(f"--rounds must be >= 1, got {args.rounds}")
        if args.steps % args.rounds:
            raise SystemExit(f"--steps {args.steps} must split evenly into "
                             f"--rounds {args.rounds}")
        avg_period = args.steps // args.rounds
    else:
        avg_period = args.avg_period

    key = jax.random.PRNGKey(args.seed)
    init_params = api.init_params(cfg, key)  # same init for all members (Alg.2 l.3)
    members = [(init_params, opt.init(init_params), jnp.zeros((), jnp.int32))
               for _ in range(args.members)]
    batch_fns = [make_batch_fn(cfg, args, m) for m in range(args.members)]

    def save_states(step):
        """Atomic per-member train-state checkpoint (params + optimizer
        state; the step cursor rides the filename/metadata)."""
        for m_i, (p, o, _) in enumerate(members):
            save_checkpoint(args.ckpt_dir, f"state-{m_i}", step,
                            {"params": p, "opt": o},
                            {"arch": cfg.name, "members": args.members})

    start_step = 0
    if args.resume:
        # anchor on the newest step EVERY member has: per-member saves are
        # individually atomic but not atomic as a set, so a kill between
        # member writes must fall back to the last complete step
        common = set(list_steps(args.ckpt_dir, "state-0"))
        for m_i in range(1, args.members):
            common &= set(list_steps(args.ckpt_dir, f"state-{m_i}"))
        if not common:
            raise SystemExit(
                f"--resume: no complete 'state-*' step for all "
                f"{args.members} members in {args.ckpt_dir}")
        last = max(common)
        members = []
        for m_i in range(args.members):
            tree, meta = restore_checkpoint(args.ckpt_dir, f"state-{m_i}",
                                            last)
            p = jax.tree.map(jnp.asarray, tree["params"])
            # sgd's state is the empty tuple, which serialises to nothing —
            # a missing key restores as a fresh (equally empty) init
            o = jax.tree.map(jnp.asarray, tree.get("opt", opt.init(p)))
            members.append((p, o, jnp.asarray(meta["step"], jnp.int32)))
        start_step = last
        # fast-forward every member's data stream: each consumed step drew
        # exactly one batch, so the continuation replays the same order
        for fn in batch_fns:
            for _ in range(start_step):
                fn()
        print(f"# resumed from step {start_step} in {args.ckpt_dir}")

    n_params = cfg.param_count()
    print(f"# arch={cfg.name} params={n_params/1e6:.1f}M members={args.members} "
          f"avg_period={avg_period or 'final'} non_iid={args.non_iid}")

    def apply_sync(members):
        """One averaging event: the host-side f32 mean, shared by every
        member — numerically the rounds contract
        (``trainer.make_average_step``) without materialising a k-wide
        stacked + broadcast copy of the params per sync; on a real pod
        mesh the device-resident ``make_average_step(mesh=...)`` (one
        all-reduce) replaces this."""
        avg = average_trees([m[0] for m in members])
        return [(avg, o, s) for (_, o, s) in members]

    detectors = None
    if args.sync_policy == "drift":
        from repro.stream import DriftDetector
        detectors = [DriftDetector(threshold=args.drift_threshold,
                                   alpha=args.drift_alpha,
                                   warmup=args.drift_warmup)
                     for _ in range(args.members)]

    history = []
    sync_steps = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        if args.drift_at and step == args.drift_at:
            # the injected concept shift: every member's stream switches
            # to re-seeded domain mixtures mid-run
            batch_fns = [make_batch_fn(cfg, args, m, seed_offset=9973)
                         for m in range(args.members)]
            print(f"# drift injected at step {step}", flush=True)
        losses = []
        new_members = []
        for m, (p, o, s) in enumerate(members):
            p, o, s, metrics = step_fn(p, o, s, batch_fns[m]())
            new_members.append((p, o, s))
            losses.append(float(metrics["loss"]))
        members = new_members
        if detectors is not None:
            # metrics['loss'] is evaluated at the PRE-update params on the
            # incoming batch — the prequential score, negated so higher is
            # better; sync while ANY member is in the drifting state
            if any([d.update(-l) for d, l in zip(detectors, losses)]):
                members = apply_sync(members)
                sync_steps.append(step + 1)
        elif avg_period and (step + 1) % avg_period == 0:
            members = apply_sync(members)
            sync_steps.append(step + 1)
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            save_states(step + 1)  # post-update AND post-sync state
        history.append(losses)
        if (step + 1) % args.log_every == 0:
            print(f"step {step+1:5d} losses=" +
                  " ".join(f"{l:.4f}" for l in losses) +
                  f" ({time.time()-t0:.1f}s)", flush=True)
    if args.sync_policy == "drift":
        print(f"# drift policy fired {len(sync_steps)} syncs at steps "
              f"{sync_steps}")

    averaged = average_trees([m[0] for m in members])
    # final evaluation: averaged vs members on a held-out IID stream
    eval_fn = jax.jit(lambda p, b: api.loss_fn(cfg, p, b)[0])
    eval_batch_fn = make_batch_fn(cfg, replace_args(args), member=10_000)
    eval_batches = [eval_batch_fn() for _ in range(4)]
    avg_loss = float(np.mean([float(eval_fn(averaged, b)) for b in eval_batches]))
    member_losses = [
        float(np.mean([float(eval_fn(p, b)) for b in eval_batches]))
        for (p, _, _) in members]
    print(f"# eval: averaged={avg_loss:.4f} members=" +
          " ".join(f"{l:.4f}" for l in member_losses))

    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, "averaged", args.steps, averaged,
                        {"arch": cfg.name, "eval_loss": avg_loss})
        for i, (p, _, _) in enumerate(members):
            save_checkpoint(args.ckpt_dir, f"member-{i}", args.steps, p)
        print(f"# checkpoints written to {args.ckpt_dir}")

    return {"eval_averaged": avg_loss, "eval_members": member_losses,
            "history": history, "sync_steps": sync_steps}


def replace_args(args):
    import copy
    a = copy.copy(args)
    a.non_iid = False  # held-out eval is always the full distribution
    return a


if __name__ == "__main__":
    use_compile_cache()
    main()
