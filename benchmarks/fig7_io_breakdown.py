"""Fig. 7 — GPU↔CPU I/O breakdown (DMA + UM traffic only, as the paper
counts CUDA memcpy/UM ops; GDS traffic is *not* GPU-CPU and is excluded).

Paper claim: AIRES cuts transferred bytes by up to 84.2 % (kA2a, vs
MaxMemory) and both bytes and latency by ~70–75 % vs ETC on kV1r.
"""
from __future__ import annotations

from typing import List

from benchmarks.common import (
    SCALE, budget_for, csv_row, dataset, feature_spec, run_sched,
)

from repro.launch.compile_cache import enable_compile_cache

DATASETS = ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a", "kA2a", "kV1r"]
SCHEDS = ["maxmemory", "ucg", "etc", "aires"]


def _dma_um(metrics) -> tuple:
    b = sum(v for k, v in metrics.bytes_by_path.items() if k in ("dma", "um"))
    s = sum(v for k, v in metrics.seconds_by_path.items() if k in ("dma", "um"))
    return b, s


def run() -> List[str]:
    rows = [f"# fig7 GPU-CPU I/O breakdown (scale={SCALE})"]
    for name in DATASETS:
        a = dataset(name)
        feat = feature_spec(a)
        budget = budget_for(name, a, feat)
        base_bytes = None
        for sched in SCHEDS:
            m = run_sched(sched, a, feat, budget, name).metrics
            if m.oom:
                rows.append(csv_row(f"fig7/{name}/{sched}", 0.0, "OOM"))
                continue
            b, s = _dma_um(m)
            if sched == "maxmemory":
                base_bytes = b
            red = (f";reduction_vs_maxmem={100 * (1 - b / base_bytes):.1f}%"
                   if base_bytes and sched != "maxmemory" else "")
            rows.append(csv_row(
                f"fig7/{name}/{sched}", s * 1e6,
                f"dma_um_bytes={b}{red}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
