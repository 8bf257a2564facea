"""Fig. 8 — GPU/CPU↔SSD bandwidth utilization: dual-way (GDS + PCIe) vs
single-path baselines.

Paper claim: the dual-way path strategy raises storage-tier bandwidth
utilization across all datasets because GDS and PCIe channels run
concurrently (Fig. 5 Phase I).
"""
from __future__ import annotations

from typing import List

from benchmarks.common import (
    SCALE, budget_for, csv_row, dataset, feature_spec, run_sched,
)

from repro.launch.compile_cache import enable_compile_cache

DATASETS = ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a", "kA2a", "kV1r"]


def run() -> List[str]:
    rows = [f"# fig8 storage-tier bandwidth (scale={SCALE})"]
    for name in DATASETS:
        a = dataset(name)
        feat = feature_spec(a)
        budget = budget_for(name, a, feat)
        for sched in ("etc", "aires"):
            m = run_sched(sched, a, feat, budget, name).metrics
            if m.oom:
                rows.append(csv_row(f"fig8/{name}/{sched}", 0.0, "OOM"))
                continue
            storage_bytes = sum(
                v for k, v in m.bytes_by_path.items() if k in ("gds", "sio"))
            storage_secs = max(
                (v for k, v in m.seconds_by_path.items()
                 if k in ("gds", "sio")), default=0.0)  # channels overlap
            eff_bw = storage_bytes / max(storage_secs, 1e-12) / 1e9
            rows.append(csv_row(
                f"fig8/{name}/{sched}", storage_secs * 1e6,
                f"effective_storage_bw_gbps={eff_bw:.2f}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
