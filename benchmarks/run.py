"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for:
  fig3  merge overhead            (paper Fig. 3)
  fig6  per-epoch e2e latency     (paper Fig. 6)
  fig7  GPU-CPU I/O breakdown     (paper Fig. 7)
  fig8  storage-tier bandwidth    (paper Fig. 8)
  fig9  feature-size ablation     (paper Fig. 9)
  tableIII memory ablation        (paper Table III)
  roofline (§Roofline, from dry-run artifacts when present)
  kernel microbench
"""
from __future__ import annotations

import sys
import traceback

from benchmarks import (
    fig3_merge_overhead,
    fig6_e2e_latency,
    fig7_io_breakdown,
    fig8_bandwidth,
    fig9_feature_ablation,
    tableiii_memory_ablation,
    roofline,
    kernel_bench,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    fig3_merge_overhead,
    fig6_e2e_latency,
    fig7_io_breakdown,
    fig8_bandwidth,
    fig9_feature_ablation,
    tableiii_memory_ablation,
    roofline,
    kernel_bench,
]


def main() -> int:
    """Run every figure; a phase that raises is reported and the rest still
    run, but the exit code is 1 whenever any phase failed."""
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for mod in MODULES:
        try:
            for row in mod.run():
                print(row)
        except Exception as err:  # noqa: BLE001
            print(f"{mod.__name__},0.0,ERROR:{type(err).__name__}:{err}")
            traceback.print_exc()
            failed.append(mod.__name__)
    if failed:
        print(f"failed phases: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
