"""Find a serving cell's knee: the highest offered rate whose achieved
rate keeps up with no growing backlog. One process, one set-up, one
window per rate.

  python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
      --seconds <s> --rates 200,400,800

Each rate prints the serving driver's lines (achieved rate, p50/p95/p99,
the p95 of the first and the last fifth of the window, how late the
generator ran). The rate a cell offers is fixed in its traffic file.
"""
import argparse
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from chipbench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    env = harness.load_env(harness.ROOT, args.workload, args.seed,
                           args.seconds, False)
    harness.require_chip(int(env.cell["chips"]))
    harness.prepare(env)
    from chipbench.drivers import open_loop
    state = open_loop.setup(env)
    harness.settle()
    print(f"setup_s={time.monotonic() - T0}", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        env.traffic["rate_per_s"] = rate
        out = open_loop.window(state, env)
        for line in out["lines"]:
            print(f"rate={rate} {line}", flush=True)
    open_loop.close(state)


if __name__ == "__main__":
    main()
