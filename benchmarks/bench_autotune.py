"""Autotune bench — online cost calibration + schedule search, measured.

Two sections, one JSON artifact (BENCH_autotune.json):

  * calibration — a ServingEngine whose `CostCalibrator` watches traffic
    against a *drifted* ground-truth system (every path's bandwidth at
    0.7x and setup latency at 3x the static spec; HBM untouched). Each
    window predicts per-(graph, width) request costs, measures the true
    makespan under the drifted spec, then feeds the window's transfer
    records back into the calibrator. The on-arm's mean |error| must
    shrink strictly window over window (trust-blended fits converge
    geometrically); the off-arm (static spec) stays at its initial error.

  * autotune — `ServingEngine.autotune` per (graph, system), recording
    the default vs tuned predicted makespan (tuned <= default by
    construction: the default arm is always a candidate) plus a roofline
    cross-check: the default plan's makespan can never beat
    max_path(path_bytes / path_bw), the same per-resource bound
    benchmarks/roofline.py computes from the shared TierSpec constants.

  * bitexact — a calibrator with zero observations prices and serves
    byte-identically to no calibrator at all (the off-by-default
    guarantee the golden pipeline tests pin).

Deterministic: every "actual" is a modeled estimate under the drifted
spec, never wall clock, so CI can assert the monotone properties at
AIRES_BENCH_SCALE=1e-4.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List

import numpy as np

from benchmarks.bench_serve import _jsonable, build_graphs, serving_budget
from benchmarks.common import SCALE
from repro.core import CostCalibrator
from repro.core.analysis import path_byte_totals
from repro.core.pipeline import CacheProbeOp, TransferOp
from repro.io.tiers import (
    PAPER_GPU_SYSTEM,
    Path,
    TieredMemorySystem,
    TierSpec,
    TPU_V5E_SYSTEM,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import (
    EngineConfig,
    InferenceRequest,
    ServingEngine,
    VirtualClock,
)

WIDTHS = (16, 32, 48)
HIDDEN = 16
WINDOWS = 5
BW_DRIFT = 0.7      # ground-truth bandwidth = 0.7x the static spec
LAT_DRIFT = 3.0     # ground-truth setup latency = 3x the static spec
SYSTEMS: Dict[str, TierSpec] = {
    "tpu_v5e": TPU_V5E_SYSTEM,
    "paper_gpu": PAPER_GPU_SYSTEM,
}


def drifted_spec(base: TierSpec) -> TierSpec:
    """The ground-truth system the static spec has drifted away from.
    Only per-path bw/latency move — `hbm_bw` and the host constants stay,
    so every modeled discrepancy is observable from transfer records."""
    return dataclasses.replace(
        base,
        bw={p: b * BW_DRIFT for p, b in base.bw.items()},
        latency_s={p: l * LAT_DRIFT for p, l in base.latency_s.items()},
    )


def make_engine(graphs, budget: int, spec: TierSpec = TPU_V5E_SYSTEM,
                calibrator: CostCalibrator = None) -> ServingEngine:
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=budget, clock=VirtualClock(), tier_spec=spec,
        calibrator=calibrator))
    for name, a in graphs.items():
        eng.register_graph(name, a)
    return eng


def template_request(name: str, a, width: int) -> InferenceRequest:
    h = np.zeros((a.n_rows, width), np.float32)
    w = [np.zeros((width, HIDDEN), np.float32)]
    return InferenceRequest(name, h, w)


def replay_plan_transfers(plan, tms: TieredMemorySystem) -> None:
    """Charge every transfer the plan declares (cold reading: cache
    probes charge their miss) through `tms` — the observation stream a
    real deployment's TieredMemorySystem would have recorded."""
    for bound in plan.ops:
        op = bound.op
        t = op if isinstance(op, TransferOp) else (
            op.miss if isinstance(op, CacheProbeOp) else None)
        if t is not None and t.nbytes > 0:
            tms.transfer(t.path, t.src, t.dst, t.nbytes, tag=t.tag)


def run_calibration(graphs, budget: int) -> Dict[str, object]:
    base = TPU_V5E_SYSTEM
    true_spec = drifted_spec(base)
    cal = CostCalibrator()
    eng = make_engine(graphs, budget, calibrator=cal)
    windows: List[Dict[str, object]] = []
    for w in range(WINDOWS):
        true_tms = TieredMemorySystem(true_spec)
        errs, off_errs = [], []
        for name, a in graphs.items():
            for width in WIDTHS:
                req = template_request(name, a, width)
                predicted = eng.estimate_request_cost(req)
                off_predicted = eng.estimate_request_cost(req, spec=base)
                plan = eng._engines[name].stream_plan(
                    a, (a.n_rows, width), spec=true_spec)
                actual = plan.estimate(true_spec).makespan_s
                errs.append(abs(predicted - actual))
                off_errs.append(abs(off_predicted - actual))
                replay_plan_transfers(plan, true_tms)
        records = cal.observe_records(true_tms.transfers)
        windows.append({
            "window": w,
            "calibrated_mean_abs_error_s": float(np.mean(errs)),
            "uncalibrated_mean_abs_error_s": float(np.mean(off_errs)),
            "records_observed": records,
            "generation": cal.generation,
        })
    return {
        "bw_drift": BW_DRIFT, "latency_drift": LAT_DRIFT,
        "windows": windows,
        "path_estimates": [
            {"path": e.path.value, "n_obs": e.n_obs, "rounds": e.rounds,
             "bw": e.bw, "latency_s": e.latency_s, "trust": e.trust}
            for e in cal.estimates(base)],
    }


def run_autotune(graphs, budget: int) -> List[Dict[str, object]]:
    rows = []
    for sys_name, spec in SYSTEMS.items():
        eng = make_engine(graphs, budget, spec=spec)
        for name, a in graphs.items():
            tuned = eng.autotune(name)
            # Roofline cross-check on the default plan: its modeled
            # makespan cannot beat the busiest path's bytes/bw bound
            # (the same per-resource reading benchmarks/roofline.py
            # derives from this very TierSpec).
            plan = eng._engines[name].stream_plan(
                a, (a.n_rows, eng.config.max_batch_features), spec=spec)
            totals = path_byte_totals(plan)
            bound = max((nbytes / spec.bw[Path(p)]
                         for p, nbytes in totals.items()), default=0.0)
            rows.append({
                "system": sys_name, "graph": name,
                "default_makespan_s": tuned.default_makespan_s,
                "tuned_makespan_s": tuned.predicted_makespan_s,
                "predicted_speedup": tuned.predicted_speedup,
                "min_bytes": tuned.min_bytes,
                "pass_order": list(tuned.pass_order),
                "ell_buckets": (list(tuned.ell_buckets)
                                if tuned.ell_buckets else None),
                "ell_bytes": tuned.ell_bytes,
                "default_ell_bytes": tuned.default_ell_bytes,
                "roofline_bound_s": bound,
                "is_default": tuned.is_default,
            })
    return rows


def run_bitexact(graphs, budget: int) -> Dict[str, object]:
    def one_batch(calibrator):
        rng = np.random.default_rng(7)
        eng = make_engine(graphs, budget, calibrator=calibrator)
        for name, a in graphs.items():
            h = rng.standard_normal((a.n_rows, HIDDEN)).astype(np.float32)
            w = [rng.standard_normal((HIDDEN, HIDDEN)).astype(np.float32)]
            eng.submit(InferenceRequest(name, h, w))
        return eng.run_batch()

    off = one_batch(None)
    on = one_batch(CostCalibrator())   # zero observations = identity
    predictions_equal = (
        [l.predicted_s for l in off.request_latency]
        == [l.predicted_s for l in on.request_latency])
    outputs_equal = all(
        np.array_equal(r0.output, r1.output)
        for r0, r1 in zip(off.results, on.results))
    return {
        "predictions_equal": bool(predictions_equal),
        "outputs_equal": bool(outputs_equal),
        "uploaded_bytes_equal": off.uploaded_bytes == on.uploaded_bytes,
    }


def validate_report(report: Dict[str, object]) -> None:
    """Schema + property check for BENCH_autotune.json (CI smoke job)."""
    for key in ("scale", "calibration", "autotune", "bitexact"):
        assert key in report, f"missing top-level key {key!r}"
    windows = report["calibration"]["windows"]
    assert len(windows) >= 3, "need >= 3 calibration windows"
    errs = [w["calibrated_mean_abs_error_s"] for w in windows]
    for i in range(1, len(errs)):
        assert errs[i] < errs[i - 1], (
            f"calibrated error not strictly decreasing at window {i}: "
            f"{errs[i - 1]:.3e} -> {errs[i]:.3e}")
    off = [w["uncalibrated_mean_abs_error_s"] for w in windows]
    assert errs[-1] < off[-1], "calibration never beat the static spec"
    assert report["autotune"], "no autotune rows"
    for row in report["autotune"]:
        assert row["tuned_makespan_s"] <= row["default_makespan_s"] + 1e-12, (
            f"tuned arm worse than default on {row['system']}/{row['graph']}")
        assert row["default_makespan_s"] >= row["roofline_bound_s"] - 1e-12, (
            f"makespan beats the roofline bound on "
            f"{row['system']}/{row['graph']}")
        assert row["ell_bytes"] <= row["default_ell_bytes"]
    for key, ok in report["bitexact"].items():
        assert ok, f"calibration-off bit-exactness violated: {key}"


def run() -> Dict[str, object]:
    graphs = build_graphs()
    budget = serving_budget(graphs)
    report = {
        "scale": SCALE,
        "widths": list(WIDTHS),
        "calibration": run_calibration(graphs, budget),
        "autotune": run_autotune(graphs, budget),
        "bitexact": run_bitexact(graphs, budget),
    }
    return _jsonable(report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_autotune.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = run()
    validate_report(report)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")

    for w in report["calibration"]["windows"]:
        print(f"window {w['window']}: calibrated |err| "
              f"{w['calibrated_mean_abs_error_s']:.3e}s vs static "
              f"{w['uncalibrated_mean_abs_error_s']:.3e}s "
              f"({w['records_observed']} records)")
    for row in report["autotune"]:
        print(f"{row['system']:9s} {row['graph']:8s} default "
              f"{row['default_makespan_s']:.3e}s -> tuned "
              f"{row['tuned_makespan_s']:.3e}s "
              f"(x{row['predicted_speedup']:.3f}, "
              f"min_bytes={row['min_bytes']}, "
              f"order={'>'.join(row['pass_order'])}, "
              f"buckets={row['ell_buckets']})")
    print(f"bitexact: {report['bitexact']}")
    print(f"wrote {args.out} (scale={SCALE})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
