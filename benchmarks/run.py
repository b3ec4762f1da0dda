"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Model training for the
draft/target pairs is cached under $REPRO_BENCH_CACHE (default /tmp), so
the first invocation trains the pairs (~3 min CPU) and later runs reuse
them.

Usage:  PYTHONPATH=src python -m benchmarks.run [table1 table3 ...]
"""
from __future__ import annotations

import sys
import time
import traceback

SUITES = ("table1", "table2", "table3", "table4", "table5", "table6",
          "table7", "table8", "table9", "table10", "table11", "fig6",
          "fig9", "roofline")


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    want = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    failures = 0
    for suite in want:
        t0 = time.monotonic()
        try:
            if suite == "table1":
                from benchmarks.table1_static_heterogeneous import run
            elif suite == "table2":
                from benchmarks.table2_signal_correlation import run
            elif suite == "table3":
                from benchmarks.table3_latency_speedup import run
            elif suite == "table4":
                from benchmarks.table4_low_acceptance import run
            elif suite == "table5":
                from benchmarks.table5_paged_capacity import run
            elif suite == "table6":
                from benchmarks.table6_pipeline_overlap import run
            elif suite == "table7":
                from benchmarks.table7_drafter_matrix import run
            elif suite == "table8":
                from benchmarks.table8_prefix_cache import run
            elif suite == "table9":
                from benchmarks.table9_quant_kv import run
            elif suite == "table10":
                from benchmarks.table10_saturation import run
            elif suite == "table11":
                from benchmarks.table11_slo import run
            elif suite == "fig6":
                from benchmarks.fig6_sensitivity import run
            elif suite == "fig9":
                from benchmarks.fig9_scalability_slcap import run
            elif suite == "roofline":
                from benchmarks.roofline import run
            else:
                raise KeyError(suite)
            for row in run():
                print(row)
        except Exception as e:
            failures += 1
            print(f"{suite}/ERROR,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
        finally:
            print(f"{suite}/total,{(time.monotonic() - t0) * 1e6:.0f},done",
                  file=sys.stderr)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
