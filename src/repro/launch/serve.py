"""Serving launcher: batched decode with KV/recurrent state, or GCN serving.

`serve(cfg, params, prompts, steps)` prefRuns a prefill then `steps` decode
iterations for a batch of requests; the same serve_step is what the
dry-run lowers at decode_32k / long_500k shapes.

`--mode gcn` instead drives the out-of-core GCN serving engine
(repro.runtime.engine): registered graphs, queued requests, batched
streamed aggregation with the tiered segment cache — prints per-epoch
uploaded vs cache-hit wire bytes.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (
    init_params, encode, init_decode_state, decode_step,
)


def serve(cfg, params, prompts: np.ndarray, steps: int = 8):
    """prompts (B, S0) int32 → generated tokens (B, steps)."""
    b, s0 = prompts.shape
    state = init_decode_state(cfg, b, max_len=s0 + steps + 1)
    enc_out = None
    if cfg.is_enc_dec:
        audio = jnp.zeros((b, cfg.audio_frames, cfg.d_model), jnp.float32)
        enc_out = encode(cfg, params, audio)

    # Prefill token-by-token through the decode path (teacher-forced) —
    # keeps one compiled step; a chunked prefill is the production variant.
    step_fn = jax.jit(lambda p, t, st: decode_step(cfg, p, t, st,
                                                   enc_out=enc_out))
    logits = None
    for t in range(s0):
        logits, state = step_fn(params, jnp.asarray(prompts[:, t:t+1]), state)
    out = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for _ in range(steps):
        out.append(np.asarray(tok)[:, 0])
        logits, state = step_fn(params, tok, state)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return np.stack(out, axis=1)


def serve_gcn(scale: float = 1e-4, batch: int = 4, epochs: int = 2,
              cache: bool = True, feature_dim: int = 16, seed: int = 0,
              cache_shards: int = 1, workers: int = 1,
              passes: bool = False, calibrate: bool = False,
              autotune: bool = False, summary_out=None):
    """Drive the multi-graph GCN serving engine; returns per-epoch reports.

    `cache_shards > 1` partitions each worker's cache device tier across
    shards (remote hits ride ICI); `workers > 1` runs replicated engines
    against the same graphs with a shared `CacheDirectory`, so one worker's
    demoted bricks serve the others' misses. With one worker the reports
    are a flat per-epoch list (back-compat); with several, a list of
    per-epoch lists, one report per worker.

    `passes` routes every batch through the plan-rewrite pipeline
    (repro.core.passes): shard-aware brick placement, transfer coalescing
    and earliest-deadline-first batch ordering.

    `calibrate` attaches a `CostCalibrator` to every worker: each batch's
    `RequestLatency` stream refits the cost model, so later epochs price
    against the calibrated spec. `autotune` runs the schedule autotuner
    per graph after the first epoch and installs the winners. A caller
    dict in `summary_out` receives per-epoch calibrated vs uncalibrated
    mean |error| and the installed `TunedSchedule` descriptions.
    """
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )
    from repro.io import CacheDirectory
    from repro.runtime import EngineConfig, InferenceRequest, ServingEngine

    from repro.core import (
        CostCalibrator, EDFOrderingPass, ShardPlacementPass,
        TransferCoalescingPass, plan_memory_dense_features,
    )

    rng = np.random.default_rng(seed)
    graphs = {
        name: normalized_adjacency(generate_graph(
            scaled_spec(SUITESPARSE_SPECS[name], scale), seed=i))
        for i, name in enumerate(("socLJ1", "rUSA"))
    }
    # Feasible for the engine's pinned plan width (64), small enough that
    # streaming still splits into several segments per graph.
    budget = max(
        int(est.m_b + est.m_c + 0.6 * a.nbytes())
        for a in graphs.values()
        for est in [plan_memory_dense_features(a, a.n_rows, 64,
                                               float("inf"))])
    directory = CacheDirectory() if workers > 1 else None
    plan_passes = ([ShardPlacementPass(), TransferCoalescingPass(),
                    EDFOrderingPass()] if passes else None)
    engines = []
    for wid in range(workers):
        eng = ServingEngine(
            EngineConfig(device_budget_bytes=budget, cache_enabled=cache,
                         cache_shards=cache_shards, worker_id=wid,
                         plan_passes=plan_passes,
                         calibrator=CostCalibrator() if calibrate else None),
            directory=directory)
        for name, a in graphs.items():
            eng.register_graph(name, a)
        engines.append(eng)

    # Fixed-spec baseline predictions for the calibration comparison: one
    # template request per graph, priced against the *uncalibrated*
    # tier_spec (spec= bypasses the calibrated memo).
    uncal_cost = {}
    if calibrate:
        for name, a in graphs.items():
            h0 = np.zeros((a.n_rows, feature_dim), np.float32)
            w0 = [np.zeros((feature_dim, feature_dim), np.float32)]
            uncal_cost[name] = engines[0].estimate_request_cost(
                InferenceRequest(name, h0, w0),
                spec=engines[0].config.tier_spec)

    epoch_errors = []  # (calibrated mean |err|, uncalibrated mean |err|)
    reports = []
    for epoch in range(epochs):
        epoch_reports = []
        for eng in engines:
            for name, a in graphs.items():
                for _ in range(batch):
                    h = rng.standard_normal(
                        (a.n_rows, feature_dim)).astype(np.float32)
                    w = [rng.standard_normal(
                        (feature_dim, feature_dim)).astype(np.float32)]
                    eng.submit(InferenceRequest(name, h, w))
            epoch_reports.append(eng.run_batch())
        if calibrate:
            lats = [l for r in epoch_reports for l in r.request_latency]
            if lats:
                epoch_errors.append((
                    sum(abs(l.error_s) for l in lats) / len(lats),
                    sum(abs(l.processing_s - uncal_cost[l.graph])
                        for l in lats) / len(lats)))
        if autotune and epoch == 0:
            for eng in engines:
                for name in graphs:
                    eng.autotune(name, install=True)
        reports.append(epoch_reports[0] if workers == 1 else epoch_reports)
    if summary_out is not None:
        summary_out["epoch_errors"] = epoch_errors
        summary_out["installed_schedules"] = {
            name: tuned.describe()
            for name, tuned in engines[0].installed_schedules.items()}
    return reports


def serve_continuous(scale: float = 1e-4, trace: str = "poisson",
                     requests: int = 24, seed: int = 0,
                     feature_dim: int = 16):
    """Replay an arrival trace through the continuous step loop.

    Builds the same two-graph engine as `serve_gcn` but on a shared
    `VirtualClock`, generates a Poisson or Gamma-modulated bursty trace
    whose rate and deadlines are quoted in units of one modeled pass,
    and streams it through a `ContinuousServer`. Returns the
    `(ServeReport, summary_dict)` pair."""
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )
    from repro.runtime import (
        ContinuousServer, EngineConfig, InferenceRequest, ServingEngine,
        VirtualClock, bursty_trace, poisson_trace, replay_continuous,
        summarize,
    )
    from repro.core import EDFOrderingPass, plan_memory_dense_features

    rng = np.random.default_rng(seed)
    graphs = {
        name: normalized_adjacency(generate_graph(
            scaled_spec(SUITESPARSE_SPECS[name], scale), seed=i))
        for i, name in enumerate(("socLJ1", "rUSA"))
    }
    budget = max(
        int(est.m_b + est.m_c + 0.6 * a.nbytes())
        for a in graphs.values()
        for est in [plan_memory_dense_features(a, a.n_rows, 64,
                                               float("inf"))])
    clock = VirtualClock()
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=budget, clock=clock,
        plan_passes=[EDFOrderingPass(clock=clock)]))
    for name, a in graphs.items():
        eng.register_graph(name, a)

    feats = {name: rng.standard_normal(
        (a.n_rows, feature_dim)).astype(np.float32)
        for name, a in graphs.items()}
    weights = rng.standard_normal(
        (feature_dim, feature_dim)).astype(np.float32)
    unit = eng.estimate_request_cost(
        InferenceRequest("socLJ1", feats["socLJ1"], [weights]))
    maker = poisson_trace if trace == "poisson" else bursty_trace
    rate_key = "rate_hz" if trace == "poisson" else "base_rate_hz"
    arrivals = maker(n=requests, graphs=sorted(graphs), seed=seed,
                     feature_dim=feature_dim, deadline_s=3.0 * unit,
                     **{rate_key: 1.5 / unit})

    def make_request(arr):
        return InferenceRequest(arr.graph, feats[arr.graph], [weights],
                                deadline_s=arr.deadline_s)

    report = replay_continuous(ContinuousServer(eng), arrivals, make_request)
    return report, summarize(report)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "gcn", "continuous"),
                    default="lm")
    ap.add_argument("--arch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--no-cache", action="store_true",
                    help="gcn mode: disable the tiered segment cache")
    ap.add_argument("--cache-shards", type=int, default=1,
                    help="gcn mode: partition the cache device tier over "
                         "this many mesh shards (remote hits ride ICI)")
    ap.add_argument("--workers", type=int, default=1,
                    help="gcn mode: replicated serving workers sharing a "
                         "CacheDirectory (dedups demotion copies)")
    ap.add_argument("--passes", action="store_true",
                    help="gcn mode: route batches through the plan-rewrite "
                         "pipeline (shard placement, transfer coalescing, "
                         "EDF batch ordering)")
    ap.add_argument("--calibrate", action="store_true",
                    help="gcn mode: fit the cost model online from each "
                         "batch's latency stream and reprice against it")
    ap.add_argument("--autotune", action="store_true",
                    help="gcn mode: autotune + install the plan schedule "
                         "per graph after the first epoch")
    ap.add_argument("--trace", choices=("poisson", "bursty"),
                    default="poisson",
                    help="continuous mode: arrival process to replay")
    ap.add_argument("--requests", type=int, default=24,
                    help="continuous mode: number of arrivals in the trace")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.mode == "continuous":
        _, summary = serve_continuous(trace=args.trace,
                                      requests=args.requests,
                                      seed=args.seed)
        print(f"{args.trace} trace: {summary['served']}/{summary['offered']} "
              f"served in {summary['groups_served']} groups, "
              f"{summary['on_time']} on time "
              f"(miss rate {summary['deadline_miss_rate']:.0%}); "
              f"p50 {summary['p50_latency_s']*1e3:.2f} ms, "
              f"p99 {summary['p99_latency_s']*1e3:.2f} ms, "
              f"goodput {summary['goodput_rps']:.1f} req/s; "
              f"uploaded {summary['uploaded_bytes']} B, "
              f"cache-hit {summary['cache_hit_bytes']} B")
        return

    if args.mode == "gcn":
        summary = {}
        reports = serve_gcn(batch=args.batch, epochs=args.epochs,
                            cache=not args.no_cache,
                            cache_shards=args.cache_shards,
                            workers=args.workers, passes=args.passes,
                            calibrate=args.calibrate,
                            autotune=args.autotune, summary_out=summary)
        for e, rep in enumerate(reports):
            for wid, r in enumerate(rep if isinstance(rep, list) else [rep]):
                lat = r.request_latency
                err = (sum(abs(l.error_s) for l in lat) / len(lat)
                       if lat else 0.0)
                print(f"epoch {e} worker {wid}: {len(r.results)} requests, "
                      f"{r.aggregation_passes} streamed passes, "
                      f"uploaded {r.uploaded_bytes} B, "
                      f"cache-hit {r.cache_hit_bytes} B "
                      f"(promoted {r.promoted_bytes} B, "
                      f"ici {r.ici_bytes} B, "
                      f"peer-served {r.directory_hit_bytes} B, "
                      f"dup-avoided {r.duplicate_avoided_bytes} B, "
                      f"hit rate {r.hit_rate:.0%}) in {r.wall_seconds:.2f}s; "
                      f"mean |predicted-actual| {err*1e3:.2f} ms")
        for e, (cal_err, uncal_err) in enumerate(
                summary.get("epoch_errors", [])):
            print(f"epoch {e}: calibrated mean |err| {cal_err*1e3:.2f} ms "
                  f"vs uncalibrated {uncal_err*1e3:.2f} ms")
        for name, desc in summary.get("installed_schedules", {}).items():
            print(f"installed {desc}")
        return

    if args.arch is None:
        ap.error("--arch is required in lm mode")
    cfg = get_config(args.arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(args.batch, args.prompt_len), dtype=np.int32)
    t0 = time.perf_counter()
    tokens = serve(cfg, params, prompts, steps=args.steps)
    dt = time.perf_counter() - t0
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print(tokens)


if __name__ == "__main__":
    main()
