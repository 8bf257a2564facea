"""Fig. 6 — end-to-end per-epoch latency, AIRES vs baselines, 5 datasets.

Paper claim: AIRES averages 1.8× / 1.7× / 1.5× over MaxMemory / UCG / ETC.
Per-epoch = forward + backward streaming cycles of the layer chain.

Two accountings share `gcn_epoch`:
  * simulate (this file's sweep): backward modeled as backward_factor=2×
    the forward stream — the paper's §V-A accounting at full dataset scale.
  * execute (--execute): a real forward+backward pass through the
    differentiable AiresSpGEMM engine on a further-scaled graph — the
    backward genuinely streams the transposed RoBW plan; the CSV reports
    streamed segments and wire bytes per phase.
"""
from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.common import (
    FEATURE_DIM, SCALE, budget_for, csv_row, dataset, feature_spec,
)
from repro.core import gcn_epoch
from repro.io.tiers import PAPER_GPU_SYSTEM
from repro.launch.compile_cache import enable_compile_cache

DATASETS = ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a"]
SCHEDS = ["maxmemory", "ucg", "etc", "aires"]


def run() -> List[str]:
    rows = [f"# fig6 per-epoch latency (scale={SCALE})"]
    speedups = {s: [] for s in SCHEDS if s != "aires"}
    for name in DATASETS:
        a = dataset(name)
        feat = feature_spec(a)
        budget = budget_for(name, a, feat)
        spans = {}
        for sched in SCHEDS:
            em = gcn_epoch(a, feat, [np.zeros((FEATURE_DIM, FEATURE_DIM))] * 2,
                           sched, PAPER_GPU_SYSTEM, budget, dataset=name,
                           mode="simulate", backward_factor=2.0)
            spans[sched] = em.epoch_makespan_s
        for sched in SCHEDS:
            sp = spans[sched] / spans["aires"]
            if sched != "aires":
                speedups[sched].append(sp)
            rows.append(csv_row(
                f"fig6/{name}/{sched}", spans[sched] * 1e6,
                f"speedup_vs_aires_inverse={sp:.2f}"))
    for sched, v in speedups.items():
        rows.append(csv_row(f"fig6/avg/{sched}", 0.0,
                            f"aires_speedup={np.mean(v):.2f}"
                            f";paper={'1.8' if sched=='maxmemory' else '1.7' if sched=='ucg' else '1.5'}"))
    return rows


def run_execute(scale_down: float = 0.05) -> List[str]:
    """Real fwd+bwd epoch on a reduced graph: per-phase streamed accounting.

    The graphs are scaled a further `scale_down` below SCALE: execute mode
    runs the Pallas kernel in interpret mode on CPU, so this is a
    correctness/accounting artifact, not a latency measurement.
    """
    from repro.core import AiresConfig
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    rows = ["# fig6 execute-mode epoch (real forward+backward streaming)"]
    for name in DATASETS[:2]:
        a = normalized_adjacency(generate_graph(
            scaled_spec(SUITESPARSE_SPECS[name], SCALE * scale_down), seed=0))
        n = a.n_rows
        rng = np.random.default_rng(0)
        f = 32
        h0 = rng.standard_normal((n, f)).astype(np.float32)
        ws = [rng.standard_normal((f, f)).astype(np.float32)] * 2
        budget = int((a.nbytes() + 3 * h0.nbytes) * 0.7) + (1 << 16)
        em = gcn_epoch(
            a, h0, ws, "aires", PAPER_GPU_SYSTEM, budget, mode="execute",
            dataset=name,
            engine_config=AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
        fwd_segs = sum(s.segments for s in em.forward_stream)
        bwd_segs = sum(s.segments for s in em.backward_stream)
        fwd_bytes = sum(s.uploaded_bytes for s in em.forward_stream)
        bwd_bytes = sum(s.uploaded_bytes for s in em.backward_stream)
        rows.append(csv_row(
            f"fig6exec/{name}/aires", em.wall_seconds * 1e3,
            f"fwd_segments={fwd_segs};bwd_segments={bwd_segs};"
            f"fwd_bytes={fwd_bytes};bwd_bytes={bwd_bytes}"))
    return rows


if __name__ == "__main__":
    import sys

    enable_compile_cache()
    out = run_execute() if "--execute" in sys.argv else run()
    print("\n".join(out))
