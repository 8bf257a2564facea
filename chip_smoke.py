#!/usr/bin/env python3
"""Bring-up smoke test: the streamed GCN train and serve path on a TPU.

    python chip_smoke.py [--scale S] [--seed N] [--four-chips]

One process, no child processes, at the `gcn_paper` widths (F=256, hidden
256-256, 64 classes) with random weights made from `--seed`.

  graph   a uniform-degree graph with kV2a's vertex and edge counts times
          `--scale` (default 1e-2: 550,400 vertices), normalized to Â, and a
          device budget under which every streamed pass, forward and
          transposed, has at least four RoBW segments.
  train   one forward and one transposed streamed pass checked against
          Â·H and Âᵀ·G by `segment_sum` over the edges; then three steps of
          `jax.value_and_grad(gcn_loss)` through `AiresSpGEMM` and AdamW.
          Each step streams the forward passes and the transposed backward
          passes through the Block-ELL Pallas kernel. Step 1's loss and
          dL/dh0 are checked against the same model over `segment_sum`.
  serve   a `ServingEngine` answers four F=256 requests per epoch for two
          epochs with `run_batch`; every output is checked against the
          same reference, and epoch 2 must hit the segment cache.

The references run under `jax.default_matmul_precision("float32")`; the
program under test runs at the precision it states itself.
`--four-chips` runs only the sharded serving path: the engine over
`make_cache_mesh(4)` against a one-shard engine on the same graph, requests
and epochs. Outputs must be equal and the bricks must sit on four devices.

Times printed here are smoke timings, not benchmark numbers. The last line
of standard output is the JSON result; any failure exits non-zero first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

GRAPH = "kV2a"
FEATURES = 256            # gcn_paper: F=256, hidden (256, 256), 64 classes
STEPS = 3
REQUESTS = 4
EPOCHS = 2
MIN_SEGMENTS = 4          # every streamed pass, forward and transposed
TILE = 8                  # bm = bk = align: the engine's Block-ELL bricks
# The references run at float32 precision. So must the program: the kernel
# and the float32 model's combination matmuls state Precision.HIGHEST. At the
# chip's default (one bfloat16 pass) relu masks near zero flip against the
# reference and dL/dh0 moves by about 15% of its maximum.
# Tolerances, as max |x - ref| / max |ref| unless noted:
AGG_RTOL = 1e-4           # one streamed pass, forward or transposed
OUT_RTOL = 1e-3           # served outputs, three layers
LOSS_RTOL = 1e-6          # |loss - ref| / |ref|
# dL/dh0 as ||g - ref|| / ||ref||: even at float32 a preactivation within
# rounding of zero can flip its relu mask, which moves single elements of
# the gradient by their whole value, so its max error is printed only.
GRAD_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileCounter:
    """Counts XLA compilations through JAX's monitoring events: backend
    compiles, and how many of them the persistent cache answered."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.BACKEND:
            self.requests += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        return self.requests, self.cache_hits, self.seconds

    def report(self, phase: str, mark) -> None:
        requests = self.requests - mark[0]
        hits = self.cache_hits - mark[1]
        log(f"{phase}: {requests - hits} programs compiled, {hits} loaded "
            f"from the persistent cache, {self.seconds - mark[2]:.3f} s in "
            "backend compile (smoke timing)")


def _ref_aggregate(edges, h):
    """Â·H by `segment_sum` over the COO edges (rows sorted)."""
    import jax

    rows, cols, vals = edges
    return jax.ops.segment_sum(vals[:, None] * h[cols], rows,
                               num_segments=h.shape[0],
                               indices_are_sorted=True)


def _ref_aggregate_t(edges, g):
    """Âᵀ·G, the transposed pass: the same edges summed by column."""
    import jax

    rows, cols, vals = edges
    return jax.ops.segment_sum(vals[:, None] * g[rows], cols,
                               num_segments=g.shape[0])


def device_edges(a):
    import jax.numpy as jnp

    rows = np.repeat(np.arange(a.n_rows, dtype=np.int32), np.diff(a.indptr))
    return (jnp.asarray(rows), jnp.asarray(a.indices, dtype=jnp.int32),
            jnp.asarray(a.data))


def rel_err(x, ref) -> float:
    import jax.numpy as jnp

    x, ref = jnp.asarray(x), jnp.asarray(ref)
    return float(jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref)))


def norm_err(x, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))


def device_memory() -> dict:
    """Device 0's allocator statistics; empty where the backend has none."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def build_graph(scale: float, seed: int):
    """Â of the smoke graph and a budget giving ≥4 segments per pass.

    `generate_sbm_graph` with one block draws every edge uniformly, so
    in- and out-degrees are both Poisson. `generate_graph`'s uniform
    family clips out-of-range endpoints onto vertices 0 and n-1, which then
    take in-degree ~n/120 (4,600 at 1e-2) and pad the transposed pass's
    first segment to 1,024 tiles per row block.
    """
    from repro.core import calc_mem, plan_memory_dense_features
    from repro.data import (
        SUITESPARSE_SPECS, generate_sbm_graph, normalized_adjacency,
        scaled_spec,
    )

    spec = scaled_spec(SUITESPARSE_SPECS[GRAPH], scale)
    a = normalized_adjacency(generate_sbm_graph(
        spec.n_vertices, spec.n_edges, n_blocks=1, seed=seed))
    est = plan_memory_dense_features(a, a.n_rows, FEATURES, float("inf"))
    # Eq. 7: what the budget leaves after M_B + M_C is the segment budget.
    # A fifth of the whole CSR gives five or six segments in either
    # direction (Aᵀ has the same rows and nnz).
    budget = int(est.m_b + est.m_c) + calc_mem(a.n_rows, a.nnz) // 5
    return a, budget


def prepare(engine, a) -> dict:
    """Plan and densify both directions; print what each pass streams."""
    info = {}
    for direction, transpose in (("forward", False), ("transposed", True)):
        plan = engine.stream_plan(a, (a.n_rows, FEATURES),
                                  transpose=transpose)
        ells = [ell for _, ell in plan.stream_payloads()]
        largest = max(ells, key=lambda e: e.blocks.shape[0] * e.ell_width)
        info[direction] = ells
        log(f"{direction} pass: {len(ells)} segments, "
            f"{sum(e.nbytes() for e in ells)} brick bytes, largest segment "
            f"(n_rb, ell_w) = {largest.blocks.shape[:2]}, "
            f"ell_w by segment {[e.ell_width for e in ells]}")
        check(len(ells) >= MIN_SEGMENTS,
              f"{direction} pass has {len(ells)} < {MIN_SEGMENTS} segments")
    return info


def check_mosaic(ell, n_rows: int, interpret: bool) -> None:
    """The kernel of one segment call lowers to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.bcsr_spmm import bcsr_spmm_pallas

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    text = bcsr_spmm_pallas.lower(
        spec(ell.blocks.shape, jnp.float32),
        spec(ell.col_tile.shape, jnp.int32),
        spec(ell.n_tiles.shape, jnp.int32),
        spec((n_rows, FEATURES), jnp.float32),
        bm=ell.bm, bk=ell.bk, interpret=interpret).as_text()
    if not interpret:
        check("tpu_custom_call" in text,
              "the segment kernel did not lower to a Mosaic custom call")
        log("segment kernel lowers to tpu_custom_call (Mosaic)")


def train_phase(a, budget: int, seed: int, interpret: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.gcn_paper import CONFIG as cfg
    from repro.core import AiresConfig, AiresSpGEMM
    from repro.models.gcn import gcn_init, gcn_loss
    from repro.train import make_optimizer

    n = a.n_rows
    engine = AiresSpGEMM(AiresConfig(
        device_budget_bytes=budget, bm=TILE, bk=TILE, align=TILE,
        interpret=interpret))
    t0 = time.perf_counter()
    ells = prepare(engine, a)
    log(f"train: host prep (RoBW + densify, both directions) "
        f"{time.perf_counter() - t0:.3f} s (smoke timing)")
    check_mosaic(ells["forward"][0], n, interpret)

    # h0 is drawn on the host, so that before the first streamed pass the
    # device holds h0 and nothing else of size: the pass's peak is its own.
    rng = np.random.default_rng(seed)
    h0 = jnp.asarray(rng.standard_normal((n, cfg.feature_dim),
                                         dtype=np.float32))
    held = device_memory().get("bytes_in_use")
    x, pull_back = jax.vjp(lambda h: engine(a, h), h0)
    x.block_until_ready()
    peak = device_memory().get("peak_bytes_in_use")
    largest = max(e.nbytes() for e in ells["forward"])
    log(f"train: first streamed pass: device bytes in use {held} before it,"
        f" peak {peak} through it; device budget {budget} B; H and X "
        f"{2 * h0.nbytes} B, largest segment's bricks {largest} B")

    k_p, k_l, k_g = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = gcn_init(cfg, k_p)
    labels = jax.random.randint(k_l, (n,), 0, cfg.n_classes)
    edges = device_edges(a)

    # One forward and one transposed streamed pass against Â·H and Âᵀ·G.
    g = jax.random.normal(k_g, (n, cfg.feature_dim), jnp.float32)
    (dh,) = pull_back(g)
    for what, got, want in (
            ("Â·h0", x, jax.jit(_ref_aggregate)(edges, h0)),
            ("Âᵀ·g", dh, jax.jit(_ref_aggregate_t)(edges, g))):
        err = rel_err(got, want)
        log(f"train: streamed {what} vs float32 reference: max rel err "
            f"{err:.3e} (tolerance {AGG_RTOL:.0e})")
        check(err <= AGG_RTOL, f"streamed {what} differs from the reference")
    del x, dh, g

    @jax.jit
    def ref_loss_grad(params, h0, labels, edges):
        agg = lambda _a, h: _ref_aggregate(edges, h)
        return jax.value_and_grad(
            lambda h: gcn_loss(cfg, params, a, h, labels, engine=agg))(h0)

    with jax.default_matmul_precision("float32"):
        ref_loss, ref_gh = ref_loss_grad(params, h0, labels, edges)

    loss_grad = jax.value_and_grad(
        lambda p, h: gcn_loss(cfg, p, a, h, labels, engine=engine),
        argnums=(0, 1))
    init_opt, update = make_optimizer("adamw")
    opt = init_opt(params)
    for step in range(1, STEPS + 1):
        marks = len(engine.forward_stats_log), len(engine.backward_stats_log)
        t0 = time.perf_counter()
        loss, (g_params, g_h0) = loss_grad(params, h0)
        params, opt = update(params, g_params, opt)
        jax.block_until_ready((loss, g_h0, params, opt))
        wall = time.perf_counter() - t0
        fwd = engine.forward_stats_log[marks[0]:]
        bwd = engine.backward_stats_log[marks[1]:]
        log(f"train step {step}: loss {float(loss):.6f}, {wall:.3f} s wall "
            f"(smoke timing); streamed passes fwd "
            f"{[s.segments for s in fwd]} bwd {[s.segments for s in bwd]} "
            f"segments, {sum(s.uploaded_bytes for s in fwd + bwd)} B "
            "uploaded")
        check(bool(jnp.isfinite(loss)), f"step {step} loss is not finite")
        check(len(fwd) == len(bwd) == 3, "each layer streams fwd and bwd")
        check(min(s.segments for s in fwd + bwd) >= MIN_SEGMENTS,
              "a streamed pass had fewer than four segments")
        if step == 1:
            loss_err = abs(float(loss) - float(ref_loss)) / abs(
                float(ref_loss))
            grad_err = norm_err(g_h0, ref_gh)
            log(f"train step 1 vs float32 reference: loss {float(loss):.6f}"
                f" vs {float(ref_loss):.6f}, rel err {loss_err:.3e} "
                f"(tolerance {LOSS_RTOL:.0e}); dL/dh0 norm rel err "
                f"{grad_err:.3e} (tolerance {GRAD_RTOL:.0e}), max rel err "
                f"{rel_err(g_h0, ref_gh):.3e}")
            check(loss_err <= LOSS_RTOL, "step 1 loss differs from reference")
            check(grad_err <= GRAD_RTOL,
                  "step 1 dL/dh0 differs from reference")


def make_requests(a, seed: int):
    """REQUESTS feature matrices and one 3-layer gcn_paper weight chain."""
    import jax
    from repro.configs.gcn_paper import CONFIG as cfg
    from repro.models.gcn import gcn_init

    rng = np.random.default_rng(seed)
    feats = [rng.standard_normal((a.n_rows, FEATURES), dtype=np.float32)
             for _ in range(REQUESTS)]
    params = gcn_init(cfg, jax.random.PRNGKey(seed + 1))
    weights = [np.asarray(params[f"w{i}"])
               for i in range(len(cfg.hidden_dims) + 1)]
    return feats, weights


def serve_references(a, feats, weights):
    """The engine's request semantics, h ← relu((Â h) W) and a linear last
    layer, in float32 over `segment_sum`."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ref(ws, edges, h):
        for i, w in enumerate(ws):
            h = _ref_aggregate(edges, h) @ w
            if i < len(ws) - 1:
                h = jax.nn.relu(h)
        return h

    edges = device_edges(a)
    ws = [jnp.asarray(w) for w in weights]
    with jax.default_matmul_precision("float32"):
        return [ref(ws, edges, jnp.asarray(f)) for f in feats]


def serve_epochs(engine, feats, weights, label: str, on_epoch=None):
    """Submit every request once per epoch; returns the outputs per epoch."""
    from repro.runtime import InferenceRequest

    outputs = []
    for epoch in range(1, EPOCHS + 1):
        for f in feats:
            engine.submit(InferenceRequest(GRAPH, f, weights))
        t0 = time.perf_counter()
        rep = engine.run_batch()
        wall = time.perf_counter() - t0
        log(f"{label} epoch {epoch}: {len(rep.results)} requests, "
            f"{rep.aggregation_passes} streamed passes, "
            f"{rep.segments_streamed} segments, uploaded "
            f"{rep.uploaded_bytes} B, cache-hit {rep.cache_hit_bytes} B "
            f"(promoted {rep.promoted_bytes} B, ici {rep.ici_bytes} B), "
            f"{wall:.3f} s wall (smoke timing)")
        check(len(rep.results) == REQUESTS, f"{label} epoch {epoch} lost "
              "requests")
        outputs.append([r.output for r in rep.results])
        if on_epoch is not None:
            on_epoch(epoch, rep)
    return outputs


def engine_config(budget: int, interpret: bool, cache_device_bytes=None):
    from repro.runtime import EngineConfig

    return EngineConfig(device_budget_bytes=budget,
                        cache_device_bytes=cache_device_bytes,
                        max_batch_features=FEATURES, bm=TILE, bk=TILE,
                        align=TILE, interpret=interpret)


def serve_phase(a, budget: int, seed: int, interpret: bool = False) -> None:
    from repro.runtime import ServingEngine

    feats, weights = make_requests(a, seed)
    refs = serve_references(a, feats, weights)
    engine = ServingEngine(engine_config(budget, interpret))
    engine.register_graph(GRAPH, a)
    hits = []
    outputs = serve_epochs(engine, feats, weights, "serve",
                           on_epoch=lambda e, rep: hits.append(
                               rep.cache_hit_bytes))
    for epoch, outs in enumerate(outputs, start=1):
        errs = [rel_err(o, r) for o, r in zip(outs, refs)]
        log(f"serve epoch {epoch} vs float32 reference: max rel err "
            f"{max(errs):.3e} (tolerance {OUT_RTOL:.0e})")
        check(max(errs) <= OUT_RTOL, f"serve epoch {epoch} output differs "
              "from the reference")
    check(hits[-1] > 0, "epoch 2 served nothing from the segment cache")


def brick_bytes_by_device() -> dict:
    """Bytes of live 4-D arrays (Block-ELL bricks) on each device."""
    import jax

    held = {}
    for arr in jax.live_arrays():
        if arr.ndim != 4:
            continue
        for shard in arr.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + (
                shard.data.nbytes)
    return held


def four_chip_phase(a, budget: int, seed: int,
                    interpret: bool = False) -> None:
    from repro.launch.mesh import make_cache_mesh
    from repro.runtime import ServingEngine

    feats, weights = make_requests(a, seed)
    mesh = make_cache_mesh(4)
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    # A device tier of 1 GiB per shard holds every brick a shard owns (the
    # default, the streaming budget split four ways, is smaller than one
    # brick at 1e-2), so epoch 1 leaves bricks resident on every chip.
    config = engine_config(budget, interpret, cache_device_bytes=4 << 30)
    control = ServingEngine(config)
    control.register_graph(GRAPH, a)
    want = serve_epochs(control, feats, weights, "1-shard control")
    del control
    held = {}

    def after_epoch(epoch, rep):
        if epoch == 1:
            held.update(brick_bytes_by_device())
            log(f"sharded epoch 1: brick bytes by device {held}, "
                f"ici {rep.ici_bytes} B")

    sharded = ServingEngine(config, mesh=mesh)
    sharded.register_graph(GRAPH, a)
    got = serve_epochs(sharded, feats, weights, "4-shard mesh",
                       on_epoch=after_epoch)
    same = all(np.array_equal(g, w) for ge, we in zip(got, want)
               for g, w in zip(ge, we))
    log(f"4-shard outputs equal the 1-shard control: {same}")
    check(same, "sharded outputs differ from the 1-shard control")
    on = [d for d in mesh_ids if held.get(d, 0) > 0]
    check(len(on) == 4, f"bricks sit on devices {on}, not on all of "
          f"{mesh_ids}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1e-2,
                    help="fraction of kV2a's vertex and edge counts")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving path on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: jax.devices()[0] is {dev.platform!r}"
            f" ({dev.device_kind})")
    if args.four_chips and len(devices) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 TPU devices, "
                         f"found {len(devices)}")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    log(f"devices {devices}; device_kind {dev.device_kind!r}; "
        f"compile cache {cache_dir}")

    mark = counter.mark()
    t0 = time.perf_counter()
    a, budget = build_graph(args.scale, args.seed)
    log(f"graph {GRAPH} x {args.scale:g} (uniform degree): {a.n_rows} rows, "
        f"{a.nnz} nnz, CSR {a.nbytes()} B, device budget {budget} B; "
        f"generated and normalized in {time.perf_counter() - t0:.3f} s "
        "(smoke timing)")
    counter.report("graph", mark)

    phases = ([("four_chips", four_chip_phase)] if args.four_chips else
              [("train", train_phase), ("serve", serve_phase)])
    for name, phase in phases:
        mark = counter.mark()
        t0 = time.perf_counter()
        phase(a, budget, args.seed)
        log(f"{name} phase done in {time.perf_counter() - t0:.3f} s "
            "(smoke timing)")
        counter.report(name, mark)
        stats = device_memory()
        if "peak_bytes_in_use" in stats:
            log(f"{name}: device 0 peak bytes in use "
                f"{stats['peak_bytes_in_use']} (references included)")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
