"""Readings of the step-checked SGD cells' control and planted faults
against the plain reference, at the cell's own size, on the chip.

  python3 benchmarks/chip/calibrate_steps.py --workload train-6c12c-k4 \
      --seeds 101,102,103

One line per seed with every compared number of every variant
(``chipbench/drivers/train_sgd.readings``): the reference at ``high`` in
the program's place, and the planted faults. A limit lies above what the
program reads over a dozen seeds and below what the control or a fault
reads; ``limits/<cell>.json`` records both.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from chipbench import harness  # noqa: E402
from chipbench.drivers import train_sgd  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=None)
    args = ap.parse_args()
    env = harness.load_env(harness.ROOT, args.workload, 0, 0.0, False)
    harness.require_chip(1)          # the reference runs on one chip
    harness.prepare(env)
    variants = (args.variants.split(",") if args.variants
                else train_sgd.VARIANTS)
    out = train_sgd.readings(env, [int(s) for s in args.seeds.split(",")],
                             variants)
    print(json.dumps({"workload": args.workload, "readings": out}))


if __name__ == "__main__":
    main()
