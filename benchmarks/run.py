"""Benchmark harness — one module per paper claim (deliverable d).

Prints ``name,us_per_call,derived`` CSV per the harness contract:
  * operator selection crossover  (paper §3 Sparse Operations)
  * plan selection per arch/shape (paper §1/§3 compiler claim)
  * parfor scaling, collective-free (paper §3 Distributed Operations)
  * kernel micro-benchmarks       (paper §3 BLAS/GPU backend)
  * roofline terms from the dry-run artifacts (deliverable g)
"""

import sys
import traceback

from repro.compile_cache import enable_compile_cache

from benchmarks import (bench_engine, bench_kernels,
                        bench_operator_selection, bench_parfor,
                        bench_plan_cache, bench_plan_selection,
                        bench_roofline, bench_router)


def main() -> int:
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_operator_selection, bench_plan_selection,
                bench_plan_cache, bench_engine, bench_router, bench_parfor,
                bench_kernels, bench_roofline):
        try:
            for row in mod.run():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{mod.__name__},0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc()
            failed.append(mod.__name__)
    if failed:
        print(f"# {len(failed)} bench(es) failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
