"""Benchmark harness — one module per paper table/figure + the roofline
reader.  Prints ``name,us_per_call,derived`` CSV (see DESIGN.md §7).

  python -m benchmarks.run            # all
  python -m benchmarks.run paper dq   # substring filter
"""

import sys
import traceback


def main() -> None:
    from repro.sim.execache import enable_persistent_cache
    enable_persistent_cache()
    from benchmarks import (bench_analysis, bench_belief, bench_dq_tradeoff,
                            bench_geo_calibration, bench_kernels, bench_obs,
                            bench_optimizers, bench_paper_example,
                            bench_roofline, bench_scaling, bench_scenarios,
                            bench_search, bench_serve, bench_structured)
    suites = [
        ("paper_example", bench_paper_example.run),
        ("dq_tradeoff", bench_dq_tradeoff.run),
        ("optimizers", bench_optimizers.run),
        ("scaling", bench_scaling.run),
        ("scenarios", bench_scenarios.run),
        ("structured", bench_structured.run),
        ("search", bench_search.run),
        ("serve", bench_serve.run),
        ("obs", bench_obs.run),
        ("analysis", bench_analysis.run),
        ("kernels", bench_kernels.run),
        ("geo_calibration", bench_geo_calibration.run),
        ("belief", bench_belief.run),
        ("roofline", bench_roofline.run),
    ]
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if filters and not any(f in name for f in filters):
            continue
        try:
            for row in fn():
                print(row)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},NaN,FAILED")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
