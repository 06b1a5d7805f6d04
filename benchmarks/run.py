# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import sys


def main() -> None:
    from . import (bench_fig6_end_to_end, bench_fig7_components,
                   bench_fig8_phases, bench_kernels, bench_scaling,
                   bench_streaming)
    from repro.engine.compile import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod in (bench_fig6_end_to_end, bench_fig7_components,
                bench_fig8_phases, bench_kernels, bench_scaling,
                bench_streaming):
        try:
            mod.run(print_rows=True)
        except Exception as exc:  # keep the harness going; report at the end
            failures.append((mod.__name__, exc))
            print(f"{mod.__name__},NaN,FAILED:{exc}")
    if failures:
        sys.exit(f"{len(failures)} benchmark modules failed")


if __name__ == "__main__":
    main()
