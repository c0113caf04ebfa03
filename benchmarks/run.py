"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Mapping to the paper:

  bench_attention  -> Fig 3   (attention latency vs beam width)
  bench_memory     -> Fig 4/15/16 (block copies; peak KV memory)
  bench_invalid    -> Fig 5   (invalid-item fraction without filtering)
  bench_beam       -> Fig 11  (sorting with early termination)
  bench_e2e        -> Fig 13/14 (latency vs RPS, xGR vs paged baseline)
  bench_kernel     -> Fig 17  (kernel efficiency, v5e roofline model)
  bench_schedule   -> Fig 18  (xSchedule ablation)
  bench_overload   -> ISSUE 9 (goodput/shed curves past saturation)
"""

import sys


def main() -> None:
    from benchmarks import (bench_attention, bench_beam, bench_e2e,
                            bench_invalid, bench_kernel, bench_memory,
                            bench_overload, bench_schedule)
    print("name,us_per_call,derived")
    for mod in (bench_memory, bench_kernel, bench_beam, bench_invalid,
                bench_attention, bench_schedule, bench_e2e,
                bench_overload):
        print(f"# --- {mod.__name__} ---", file=sys.stderr)
        mod.main()


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
