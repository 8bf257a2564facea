"""Closed-loop whole-graph GAT inference through the serving engine.

`in_flight` clients each hold one request: a feature matrix over the whole
graph and a GAT weight chain of its own (W, a_src, a_dst per layer). When
a batch returns, each client sends its next request. The engine plans at
the configuration's `plan_width` and keeps `cache_device_bytes` of bricks
on the device; each request streams its three attention passes alone.
Set-up builds Â (whose nonzeros are A + I, the attention's mask),
registers it, prices one request (which prepares the streamed plan),
makes the feature pool and the weight chains from the seed, and serves
one 1-request batch, which compiles every program the window runs. The
window starts no batch after `seconds` and ends with the last.

`correct`: a sample of the window's requests, drawn from the seed, is
served again by the plain reference (`bench/lib/gat_reference.py`, in
blocks of rows); each output's widest gap to it, max |out - ref| /
max |ref|, has to stay under the limit.
"""
from __future__ import annotations

import functools
import gc
import shutil
import time

import jax
import numpy as np

from bench.lib import gat_reference, gat_work, graphs
from bench.lib import trace as trace_lib
from bench.lib.common import (
    KERNELS, Check, Context, Outcome, Window, memory_peak_bytes, prng_key,
    program_csr, rel_max_gap, span,
)

GRAPH = "graph"
WARMUP = -1
# The attention kernel is the Mosaic custom call of `gat_attn_pallas`'s
# program.
GAT_KERNELS = {**KERNELS, "gat_attn":
               r'^jit_gat_attn_pallas .*custom_call_target="tpu_custom_call"'}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_features(key, n: int, f: int):
    return jax.random.normal(key, (n, f), jax.numpy.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_weights(key, dims: tuple, chains: int):
    """`chains` weight chains, plus one for the warm-up, in one call:
    per layer W (F_in, K·F') scaled by F_in^-1/2, a_src and a_dst (K, F')
    by F'^-1/2."""
    out = []
    for i, (f_in, k, f) in enumerate(dims):
        kw, ks, kd = jax.random.split(jax.random.fold_in(key, i), 3)
        normal = lambda key, shape: jax.random.normal(
            key, (chains + 1, *shape), jax.numpy.float32)
        out.append({"w": normal(kw, (f_in, k * f)) * f_in ** -0.5,
                    "a_src": normal(ks, (k, f)) * f ** -0.5,
                    "a_dst": normal(kd, (k, f)) * f ** -0.5})
    return out


class Inputs:
    """Request i's features and weights, held on the host as a client's."""

    def __init__(self, config: dict, traffic: dict, n: int, seed: int):
        k_f, k_w = jax.random.split(prng_key(seed))
        self.feats = [np.asarray(_make_features(jax.random.fold_in(k_f, i),
                                                n, config["feature_dim"]))
                      for i in range(traffic["feature_pool"])]
        self.chains = traffic["weight_chains"]
        self.weights = jax.tree_util.tree_map(
            np.asarray, _make_weights(k_w, tuple(gat_work.layers(config)),
                                      self.chains))

    def features(self, i: int) -> np.ndarray:
        return self.feats[max(i, 0) % len(self.feats)]

    def chain(self, i: int) -> list:
        j = self.chains if i == WARMUP else i % self.chains
        return [{k: v[j] for k, v in layer.items()}
                for layer in self.weights]


def model(config: dict):
    from repro.models.gat import GATConfig

    return GATConfig(
        name=config["name"], feature_dim=config["feature_dim"],
        heads=tuple(config["heads"]), head_dims=tuple(config["head_dims"]),
        concat=tuple(config["concat"]), skip=tuple(config["skip"]),
        negative_slope=config["negative_slope"], dtype=config["dtype"])


class Program:
    """The system under test, set up once for a graph."""

    def __init__(self, config: dict, a, gat, interpret=None, spans=None):
        from repro.runtime import EngineConfig, ServingEngine

        tile = config["tile"]
        self.model = gat
        self.engine = ServingEngine(EngineConfig(
            device_budget_bytes=int(config["device_budget_bytes"]),
            cache_device_bytes=int(config["cache_device_bytes"]),
            max_batch_features=config["plan_width"], bm=tile["bm"],
            bk=tile["bk"], align=tile["align"], interpret=interpret))
        self.engine.register_graph(GRAPH, program_csr(a))
        self.spans = spans

    def prepare(self, inputs: Inputs) -> None:
        """Pricing a request prepares the streamed plan (RoBW, densify)."""
        with span("host_prep", self.spans):
            self.engine.estimate_request_cost(self.request(inputs, 0))

    def request(self, inputs: Inputs, i: int):
        from repro.runtime import InferenceRequest

        return InferenceRequest(GRAPH, inputs.features(i), inputs.chain(i),
                                model=self.model)

    def serve(self, inputs: Inputs, ids: list):
        """Submit requests `ids` and run one batch; the outputs that came
        back, by id."""
        ours = {int(self.engine.submit(self.request(inputs, i))): i
                for i in ids}
        report = self.engine.run_batch()
        outputs = {ours[r.request_id]: r.output for r in report.results
                   if r.request_id in ours}
        return outputs, report


class GatWindow(Window):
    """The harness's window; its trace is reduced with the attention
    kernel among the kernels."""

    def reduce_trace(self):
        if self.trace_dir is None:
            return None
        try:
            pd = trace_lib.load(trace_lib.find_xplane(self.trace_dir))
            return trace_lib.reduce(pd, GAT_KERNELS)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def reference_outputs(config, a, inputs: Inputs, ids, precision="highest"):
    pattern = gat_reference.Pattern(a)
    for i in ids:
        yield i, np.asarray(gat_reference.forward(
            config, inputs.chain(i), pattern, inputs.features(i), precision))


def run(ctx: Context) -> Outcome:
    config, traffic = ctx.config, ctx.traffic
    gat = model(config)        # a program without GAT fails here, at once
    spans: dict = {}
    mark = ctx.compiles.mark()
    with span("graph", spans):
        a = graphs.build(config)
    program = Program(config, a, gat, ctx.interpret, spans)
    with span("data", spans):
        inputs = Inputs(config, traffic, a.n, ctx.seed)
    program.prepare(inputs)
    with span("warmup", spans):
        program.serve(inputs, [WARMUP])
    setup_compiles = ctx.compiles.since(mark)
    setup_s = time.perf_counter() - ctx.t_start

    k = traffic["in_flight"]
    trace_batch = traffic["trace_batch"] if ctx.trace else -1
    rng = np.random.default_rng(ctx.seed)
    sample: list = []          # reservoir of (request id, served output)
    size = traffic["compare_requests"]
    totals = dict(uploaded=0, promoted=0, hit=0, requests=0)
    window = GatWindow(ctx)
    batches = submitted = 0
    window.start()
    while True:
        ids = list(range(submitted, submitted + k))
        submitted += k
        with window.traced(batches == trace_batch), span("batch"):
            outputs, rep = program.serve(inputs, ids)
        batches += 1
        totals["uploaded"] += rep.uploaded_bytes
        totals["promoted"] += rep.promoted_bytes
        totals["hit"] += rep.cache_hit_bytes
        for i in sorted(outputs):             # reservoir sampling
            totals["requests"] += 1
            if len(sample) < size:
                sample.append((i, outputs[i]))
            else:
                j = int(rng.integers(0, totals["requests"]))
                if j < size:
                    sample[j] = (i, outputs[i])
        if window.elapsed() >= ctx.seconds and batches > trace_batch:
            break
    window.stop()
    peak = memory_peak_bytes()

    served = totals["requests"]
    reduced = window.reduce_trace()
    requested = totals["uploaded"] + totals["hit"]
    dims = gat_work.layers(config)
    record = {
        "spans": {**spans, "compile": setup_compiles["seconds"]},
        "compiles": {"setup": setup_compiles, "window": window.compiles},
        "counters": {"requests": served, "units": served,
                     "batches": batches,
                     "bus_bytes": totals["uploaded"] + totals["promoted"],
                     "requested_brick_bytes": requested,
                     "tier_hit_bytes": totals["hit"] - totals["promoted"]},
        "trace": reduced,
        "traced": None if reduced is None else {
            "attn_passes": [(a.n, a.nnz, h, f) for _, h, f in dims] * k,
            "model_flops": k * gat_work.gat_forward_flops(a.n, a.nnz, dims),
        },
    }
    del program
    gc.collect()

    with span("reference"):
        served_out = dict(sample)
        gap = max((rel_max_gap(served_out[i], ref) for i, ref in
                   reference_outputs(config, a, inputs, sorted(served_out))),
                  default=float("inf"))
    checks = [Check("out_gap", gap, ctx.limits["out_gap"]),
              Check("unanswered", submitted - served, 0)]
    return Outcome(attempted=submitted, failed=submitted - served,
                   end_to_end={"serve_s_per_req":
                               window.seconds / max(served, 1),
                               "setup_s": setup_s,
                               "peak_hbm_gb": (peak or 0) / 1e9},
                   record=record, checks=checks, memory_peak_bytes=peak)
