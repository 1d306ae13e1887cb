"""Benchmark driver — one section per paper table/figure + kernels +
roofline. Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    failures = []
    from benchmarks import (e2lm_scaling, elastic_resume, fig7_iterations,
                            hierarchical_reduce, kernel_bench, map_phase,
                            reduce_strategies, roofline, serve_ensemble,
                            stream_map, table23_notmnist, table45_mnist)
    for mod in (kernel_bench, e2lm_scaling, map_phase, hierarchical_reduce,
                reduce_strategies, elastic_resume, serve_ensemble,
                stream_map, table45_mnist, table23_notmnist,
                fig7_iterations, roofline):
        try:
            mod.main()
        except Exception as e:  # keep the suite going; report at the end
            failures.append((mod.__name__, e))
            traceback.print_exc()
    if failures:
        for name, e in failures:
            print(f"FAILED,{name},{type(e).__name__}:{e}")
        sys.exit(1)


if __name__ == '__main__':
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
