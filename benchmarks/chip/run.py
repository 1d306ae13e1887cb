"""The chip benchmark: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU. The cell, its
configuration, traffic mix, limits and metrics are read from
``BENCHMARK.json`` and the files beside this script (see
``chipbench/harness.py``). Exits non-zero, printing no result, without a
TPU, with fewer chips than the cell asks for, or with the kernel policy
overridden (``REPRO_USE_PALLAS`` / ``REPRO_PALLAS_INTERPRET``).
"""
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    main(T0)
