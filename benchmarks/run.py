"""Benchmark harness — one function per paper figure/table.

Prints ``name,value,derived`` CSV.  Run:  PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks.paper_figs import (
        fig07_timeline,
        fig08_rber,
        fig11_esp,
        fig12_intra_mws,
        fig13_inter_mws,
        fig14_power,
        fig17_performance,
        fig18_energy,
        table3_overheads,
    )
    from benchmarks.tpu_kernels import (
        fused_count_bench,
        mws_vs_parabit,
        popcount_bench,
        signcomp_bench,
    )

    benches = [
        fig07_timeline,
        fig08_rber,
        fig11_esp,
        fig12_intra_mws,
        fig13_inter_mws,
        fig14_power,
        fig17_performance,
        fig18_energy,
        table3_overheads,
        mws_vs_parabit,
        fused_count_bench,
        popcount_bench,
        signcomp_bench,
    ]
    print("name,value,derived")
    failures = 0
    for bench in benches:
        try:
            for name, value, derived in bench():
                print(f"{name},{value},{derived}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{bench.__name__},ERROR,{type(e).__name__}: {e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
