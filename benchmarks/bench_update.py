"""Evolving-graph bench — delta updates vs evict-and-reregister.

After warming a serving engine's segment cache on one graph, applies edge
deltas of growing size k through two arms built on identical engines,
budgets and plans:

  * delta — ``ServingEngine.update_graph``: prepared plans migrate
            incrementally (only touched row blocks re-tile), and exactly
            the stale segment keys are invalidated. The post-update epoch
            re-streams precisely ``retiled_bytes``.
  * full  — the pre-ISSUE-7 recipe: ``evict_graph`` + ``register_graph``
            with the updated CSR. Every brick re-tiles and the post-update
            epoch re-streams the whole wire footprint.

Edge lists nest (delta k uses the first k edges of one shuffled pool), so
the delta arm's touched-row set — and its re-tiled byte count — grows
monotonically with k while the full arm stays flat at the graph's total
wire bytes: update cost scales with the delta, not the graph.

Writes BENCH_update.json: per-k segments re-tiled/reused, re-tiled bytes,
post-update and warm-epoch uploads, and update wall time for both arms.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import SCALE, dataset
from repro.core import plan_memory_dense_features
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import EngineConfig, InferenceRequest, ServingEngine
from repro.sparse import apply_edge_updates

GRAPH = "socLJ1"
WIDTH = 32                 # request feature width
HIDDEN = 16                # single GCN layer, WIDTH -> HIDDEN
DELTA_SIZES = (1, 4, 16, 64)
A_FRAC = 0.15              # graph fraction resident -> several segments

ARM_KEYS = (
    "edges_changed", "rows_touched", "segments_total", "segments_retiled",
    "segments_reused", "retiled_bytes", "uploaded_after_bytes",
    "cache_hit_after_bytes", "warm_after_bytes", "update_seconds",
)


def serving_budget(a) -> int:
    est = plan_memory_dense_features(a, a.n_rows, WIDTH, float("inf"))
    return int(est.m_b + est.m_c + A_FRAC * a.nbytes())


def make_engine(a, budget: int) -> ServingEngine:
    eng = ServingEngine(EngineConfig(device_budget_bytes=budget,
                                     max_batch_features=WIDTH))
    eng.register_graph("g", a)
    return eng


def build_workload(a, seed: int):
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((a.n_rows, WIDTH)).astype(np.float32)
    w = [rng.standard_normal((WIDTH, HIDDEN)).astype(np.float32)]
    return h, w


def edge_pool(a, seed: int, n: int) -> List[tuple]:
    """One shuffled pool of distinct (row, col, value) edges; delta k uses
    the first k, so touched-row sets nest as k grows."""
    rng = np.random.default_rng(seed + 1)
    seen, pool = set(), []
    while len(pool) < n:
        r = int(rng.integers(a.n_rows))
        c = int(rng.integers(a.shape[1]))
        if (r, c) in seen:
            continue
        seen.add((r, c))
        pool.append((r, c, float(rng.standard_normal())))
    return pool


def epoch(eng: ServingEngine, h, w):
    eng.submit(InferenceRequest("g", h, w))
    return eng.run_batch()


def run_delta_arm(a, budget: int, h, w, edges) -> Dict[str, object]:
    eng = make_engine(a, budget)
    epoch(eng, h, w)                       # cold: tile + upload everything
    epoch(eng, h, w)                       # warm: cache fully resident
    rep = eng.update_graph("g", inserts=edges)
    after = epoch(eng, h, w)
    warm = epoch(eng, h, w)
    return {
        "edges_changed": rep.delta.n_changed,
        "rows_touched": int(rep.delta.touched_rows.size),
        "segments_total": rep.segments_retiled + rep.segments_reused,
        "segments_retiled": rep.segments_retiled,
        "segments_reused": rep.segments_reused,
        "retiled_bytes": rep.retiled_bytes,
        "uploaded_after_bytes": after.uploaded_bytes,
        "cache_hit_after_bytes": after.cache_hit_bytes,
        "warm_after_bytes": warm.uploaded_bytes,
        "update_seconds": rep.wall_seconds,
    }


def run_full_arm(a, budget: int, h, w, edges) -> Dict[str, object]:
    eng = make_engine(a, budget)
    epoch(eng, h, w)
    epoch(eng, h, w)
    t0 = time.perf_counter()
    new, delta = apply_edge_updates(a, inserts=edges)
    eng.evict_graph("g")
    eng.register_graph("g", new)
    update_s = time.perf_counter() - t0
    after = epoch(eng, h, w)               # re-tiles + re-uploads everything
    warm = epoch(eng, h, w)
    n_segments = after.segments_streamed // max(1, after.aggregation_passes)
    return {
        "edges_changed": delta.n_changed,
        "rows_touched": int(delta.touched_rows.size),
        "segments_total": n_segments,
        "segments_retiled": n_segments,
        "segments_reused": 0,
        "retiled_bytes": after.uploaded_bytes,
        "uploaded_after_bytes": after.uploaded_bytes,
        "cache_hit_after_bytes": after.cache_hit_bytes,
        "warm_after_bytes": warm.uploaded_bytes,
        "update_seconds": update_s,
    }


def validate_report(report: Dict[str, object]) -> None:
    """Schema + acceptance check for BENCH_update.json (CI smoke job)."""
    for key in ("scale", "graph", "seed", "deltas"):
        assert key in report, f"missing top-level key {key!r}"
    for key in ("name", "n_rows", "nnz", "segments", "wire_total_bytes"):
        assert key in report["graph"], f"graph missing {key!r}"
    deltas = report["deltas"]
    assert deltas, "no delta sizes recorded"
    prev_retiled = -1
    for i, entry in enumerate(deltas):
        assert set(entry) == {"k", "arms"}, sorted(entry)
        assert set(entry["arms"]) == {"delta", "full"}
        for arm, summary in entry["arms"].items():
            missing = [k for k in ARM_KEYS if k not in summary]
            assert not missing, f"{arm} arm missing {missing}"
            for k in ARM_KEYS:
                assert isinstance(summary[k], (int, float)), (arm, k)
        d, f = entry["arms"]["delta"], entry["arms"]["full"]
        # The post-update epoch re-streams exactly the re-tiled bricks,
        # untouched bricks keep hitting, and the next epoch is free.
        assert d["uploaded_after_bytes"] == d["retiled_bytes"], entry["k"]
        assert d["warm_after_bytes"] == 0, entry["k"]
        assert f["warm_after_bytes"] == 0, entry["k"]
        # Delta cost never exceeds the full re-register, and is strictly
        # below it at the smallest k (the headline acceptance criterion).
        assert d["uploaded_after_bytes"] <= f["uploaded_after_bytes"], \
            entry["k"]
        if i == 0:
            assert d["uploaded_after_bytes"] < f["uploaded_after_bytes"], (
                "delta arm must beat evict-and-reregister at small k")
            assert d["segments_reused"] > 0
        # Nested edge pools: re-tiled bytes grow monotonically with k —
        # cost tracks the delta, not the graph.
        assert d["retiled_bytes"] >= prev_retiled, entry["k"]
        prev_retiled = d["retiled_bytes"]


def _jsonable(obj):
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    return obj


def run(delta_sizes, seed: int) -> Dict[str, object]:
    a = dataset(GRAPH)
    budget = serving_budget(a)
    h, w = build_workload(a, seed)
    pool = edge_pool(a, seed, max(delta_sizes))

    probe = make_engine(a, budget)
    cold = epoch(probe, h, w)
    n_segments = cold.segments_streamed // max(1, cold.aggregation_passes)

    report = {
        "scale": SCALE,
        "graph": {
            "name": GRAPH, "n_rows": a.n_rows, "nnz": a.nnz,
            "segments": n_segments, "wire_total_bytes": cold.uploaded_bytes,
        },
        "seed": seed,
        "deltas": [
            {"k": k, "arms": {
                "delta": run_delta_arm(a, budget, h, w, pool[:k]),
                "full": run_full_arm(a, budget, h, w, pool[:k]),
            }}
            for k in delta_sizes
        ],
    }
    return _jsonable(report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--deltas", default=",".join(map(str, DELTA_SIZES)),
                    help="comma-separated edge-delta sizes")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default="BENCH_update.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    sizes = sorted({int(k) for k in args.deltas.split(",") if k.strip()})
    report = run(sizes, args.seed)
    validate_report(report)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")

    g = report["graph"]
    print(f"graph {g['name']}: {g['n_rows']} rows, {g['nnz']} nnz, "
          f"{g['segments']} segments, wire={g['wire_total_bytes']}")
    for entry in report["deltas"]:
        d, f = entry["arms"]["delta"], entry["arms"]["full"]
        print(f"k={entry['k']:4d} delta: retiled={d['segments_retiled']}"
              f"/{d['segments_total']} segs "
              f"uploaded={d['uploaded_after_bytes']} "
              f"({d['update_seconds']*1e3:.1f}ms)  "
              f"full: uploaded={f['uploaded_after_bytes']} "
              f"({f['update_seconds']*1e3:.1f}ms)")
    print(f"wrote {args.out} (scale={SCALE})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
