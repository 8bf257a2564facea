"""Table III — per-epoch latency under shrinking GPU memory constraints.

Paper claim: baselines OOM as the budget drops below their minimum
footprint (MaxMemory/UCG first, then ETC) while AIRES keeps running with
gracefully increasing latency.
"""
from __future__ import annotations

from typing import List

from benchmarks.common import (
    SCALE, budget_for, csv_row, dataset, feature_spec, run_sched,
)

from repro.launch.compile_cache import enable_compile_cache

# (dataset, budgets GB) straight from Table III.
CASES = [
    ("kV1r", [24, 21, 19]),
    ("kP1a", [16, 14, 12]),
    ("socLJ1", [11, 10, 8]),
]
SCHEDS = ["maxmemory", "ucg", "etc", "aires"]


def run() -> List[str]:
    rows = [f"# tableIII memory-constraint ablation (scale={SCALE})"]
    for name, budgets in CASES:
        a = dataset(name)
        feat = feature_spec(a)
        for gb in budgets:
            budget = budget_for(name, a, feat, budget_gb=gb)
            cells = []
            for sched in SCHEDS:
                m = run_sched(sched, a, feat, budget, name).metrics
                cells.append("-" if m.oom else f"{m.makespan_s*1e3:.2f}ms")
            rows.append(csv_row(
                f"tableIII/{name}/{gb}GB", 0.0,
                ";".join(f"{s}={c}" for s, c in zip(SCHEDS, cells))))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
