"""Serving bench — round-based drains vs the continuous step loop.

Replays the same arrival traces (Poisson and Gamma-modulated bursty) through
two serving arms built on identical engines, budgets and modeled costs:

  * round       — ``replay_round``: arrivals are admitted only between full
                  ``run_batch`` drains, the engine's native cadence.
  * continuous  — ``replay_continuous``: a ``ContinuousServer`` admits between
                  every column-concat group and re-prioritizes per step.

Both arms share one virtual timeline whose unit is the modeled cost of a
single mid-width pass (``unit_cost_s``), so arrival rates and deadlines are
expressed in load units and the comparison is scale-invariant: the CI smoke
job runs the same driver at AIRES_BENCH_SCALE=1e-4.

Writes BENCH_serve.json: per-arm p50/p99 latency, goodput, deadline-miss
rate, and uploaded/cache-hit byte accounting.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Callable, Dict, List, Tuple

import numpy as np

from benchmarks.common import SCALE
from repro.core import EDFOrderingPass, plan_memory_dense_features
from repro.data import (
    SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import (
    ContinuousServer, EngineConfig, InferenceRequest, ServingEngine,
    VirtualClock, bursty_trace, poisson_trace, replay_continuous,
    replay_round, summarize,
)

# Two graphs with different stream profiles (power-law social vs near-planar
# road) so EDF group ordering has real choices to make. rUSA is held at 0.2×
# the socLJ1 scale to keep per-pass costs comparable.
GRAPHS: Dict[str, float] = {"socLJ1": 1.0, "rUSA": 0.2}
WIDTHS: Tuple[int, ...] = (16, 32, 48)   # heterogeneous request widths
HIDDEN = 16                              # single GCN layer, w -> HIDDEN
DEADLINE_UNITS = 3.0                     # deadline = 3x one mid-width pass
POISSON_RHO = 0.8                        # offered load, passes per unit time
BURSTY_RHO = 3.5
BURST_SHAPE = 0.25                       # Gamma shape: smaller = burstier
EPISODE = 16                             # arrivals per rate-modulation draw

ARM_KEYS = (
    "offered", "served", "on_time", "expired", "rejected", "deadline_misses",
    "deadline_miss_rate", "p50_latency_s", "p99_latency_s", "mean_latency_s",
    "goodput_rps", "makespan_s", "groups_served", "uploaded_bytes",
    "cache_hit_bytes", "promoted_bytes", "ici_bytes", "aggregation_passes",
)


def build_graphs():
    graphs = {}
    for name, mult in GRAPHS.items():
        spec = scaled_spec(SUITESPARSE_SPECS[name], SCALE * mult)
        graphs[name] = normalized_adjacency(generate_graph(spec, seed=0))
    return graphs


def serving_budget(graphs) -> int:
    """Big enough for any single graph's stream plan, small enough that the
    segment cache keeps mattering across graph switches."""
    budget = 0
    for a in graphs.values():
        est = plan_memory_dense_features(a, a.n_rows, 64, float("inf"))
        budget = max(budget, int(est.m_b + est.m_c + 0.6 * a.nbytes()))
    return budget


def make_engine(graphs, budget: int, clock: VirtualClock) -> ServingEngine:
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=budget, clock=clock,
        plan_passes=[EDFOrderingPass(clock=clock)]))
    for name, a in graphs.items():
        eng.register_graph(name, a)
    return eng


def build_workload(graphs, seed: int):
    """Per-(graph, width) feature matrices + shared weights, and the
    Arrival -> InferenceRequest factory both arms use."""
    rng = np.random.default_rng(seed)
    feats = {(n, w): rng.standard_normal((a.n_rows, w)).astype(np.float32)
             for n, a in graphs.items() for w in WIDTHS}
    weights = {w: rng.standard_normal((w, HIDDEN)).astype(np.float32)
               for w in WIDTHS}

    def make_request(arr) -> InferenceRequest:
        return InferenceRequest(
            arr.graph, feats[(arr.graph, arr.feature_dim)],
            [weights[arr.feature_dim]], deadline_s=arr.deadline_s)

    return feats, weights, make_request


def probe_unit_cost(graphs, budget: int, feats, weights) -> float:
    """Modeled cost of one mid-width pass on the largest graph: the virtual
    time unit that rates and deadlines are quoted in."""
    probe = make_engine(graphs, budget, VirtualClock())
    mid = WIDTHS[len(WIDTHS) // 2]
    name = max(graphs, key=lambda n: graphs[n].n_rows)
    return probe.estimate_request_cost(
        InferenceRequest(name, feats[(name, mid)], [weights[mid]]))


def make_trace(kind: str, n: int, unit: float, graphs, seed: int):
    deadline = DEADLINE_UNITS * unit
    if kind == "poisson":
        return poisson_trace(
            n=n, rate_hz=POISSON_RHO / unit, graphs=sorted(graphs),
            seed=seed, feature_dim=WIDTHS, deadline_s=deadline)
    if kind == "bursty":
        return bursty_trace(
            n=n, base_rate_hz=BURSTY_RHO / unit, graphs=sorted(graphs),
            seed=seed, feature_dim=WIDTHS, deadline_s=deadline,
            burst_shape=BURST_SHAPE, episode=EPISODE)
    raise ValueError(f"unknown trace kind {kind!r}")


def run_trace(kind: str, n: int, seed: int, graphs, budget: int,
              make_request: Callable, unit: float) -> Dict[str, object]:
    trace = make_trace(kind, n, unit, graphs, seed)
    round_report = replay_round(
        make_engine(graphs, budget, VirtualClock()), trace, make_request)
    cont_report = replay_continuous(
        ContinuousServer(make_engine(graphs, budget, VirtualClock())),
        trace, make_request)
    rho = POISSON_RHO if kind == "poisson" else BURSTY_RHO
    return {
        "trace": {
            "kind": kind, "requests": n, "seed": seed,
            "offered_load_rho": rho,
            "deadline_units": DEADLINE_UNITS,
            "widths": list(WIDTHS),
            "burst_shape": BURST_SHAPE if kind == "bursty" else None,
            "episode": EPISODE if kind == "bursty" else None,
        },
        "arms": {
            "round": summarize(round_report),
            "continuous": summarize(cont_report),
        },
    }


def validate_report(report: Dict[str, object]) -> None:
    """Schema check for BENCH_serve.json (used by the CI smoke job)."""
    for key in ("scale", "unit_cost_s", "requests", "seed", "traces"):
        assert key in report, f"missing top-level key {key!r}"
    assert report["traces"], "no traces recorded"
    for entry in report["traces"]:
        assert set(entry) == {"trace", "arms"}, sorted(entry)
        assert entry["trace"]["kind"] in ("poisson", "bursty")
        assert set(entry["arms"]) == {"round", "continuous"}
        for arm, summary in entry["arms"].items():
            missing = [k for k in ARM_KEYS if k not in summary]
            assert not missing, f"{arm} arm missing {missing}"
            for k in ARM_KEYS:
                assert isinstance(summary[k], (int, float)), (arm, k)
            assert summary["offered"] == entry["trace"]["requests"]


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


def run(traces: List[str], n: int, seed: int) -> Dict[str, object]:
    graphs = build_graphs()
    budget = serving_budget(graphs)
    feats, weights, make_request = build_workload(graphs, seed)
    unit = probe_unit_cost(graphs, budget, feats, weights)
    report = {
        "scale": SCALE,
        "unit_cost_s": unit,
        "requests": n,
        "seed": seed,
        "traces": [run_trace(kind, n, seed, graphs, budget, make_request, unit)
                   for kind in traces],
    }
    return _jsonable(report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traces", default="poisson,bursty",
                    help="comma-separated subset of {poisson,bursty}")
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    kinds = [k.strip() for k in args.traces.split(",") if k.strip()]
    report = run(kinds, args.requests, args.seed)
    validate_report(report)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")

    for entry in report["traces"]:
        kind = entry["trace"]["kind"]
        for arm in ("round", "continuous"):
            s = entry["arms"][arm]
            print(f"{kind:8s} {arm:10s} p50={s['p50_latency_s']:.3e}s "
                  f"p99={s['p99_latency_s']:.3e}s "
                  f"miss={s['deadline_misses']}/{s['offered']} "
                  f"goodput={s['goodput_rps']:.1f}rps "
                  f"uploaded={s['uploaded_bytes']} "
                  f"cache_hit={s['cache_hit_bytes']}")
    print(f"wrote {args.out} (scale={SCALE})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
