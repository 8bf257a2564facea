"""Closed-loop whole-graph GCN inference through the serving engine.

`in_flight` clients each hold one request: an F-wide feature matrix over
the whole graph and a weight chain of its own. When a batch returns, each
client sends its next request. Set-up builds Â, registers it with a
`ServingEngine`, prices one request (which prepares the streamed plan),
makes the feature pool and the weight chains from the seed on the device,
and serves one 1-request batch, which compiles every program the window
runs. The window starts no batch after `seconds` and ends with the last.

`correct`: a sample of the window's requests, drawn from the seed, is
served again by the plain reference; each output's widest gap to it,
max |out - ref| / max |ref|, has to stay under the limit.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import numpy as np

from bench.lib import graphs, reference, work
from bench.lib.common import (
    Check, Context, Outcome, Window, device_edges,
    memory_peak_bytes, prng_key, program_csr, rel_max_gap, span,
)

GRAPH = "graph"
WARMUP = -1


def dims(config: dict) -> tuple:
    return (config["feature_dim"], *config["hidden_dims"],
            config["n_classes"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_features(key, n: int, f: int):
    return jax.random.normal(key, (n, f), jax.numpy.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_weights(key, dims_: tuple, chains: int):
    """`chains` weight chains, plus one for the warm-up, in one call."""
    return [jax.random.normal(jax.random.fold_in(key, i),
                              (chains + 1, d_in, d_out), jax.numpy.float32)
            * d_in ** -0.5
            for i, (d_in, d_out) in enumerate(zip(dims_[:-1], dims_[1:]))]


class Inputs:
    """Request i's features and weights, held on the host as a client's.

    The feature pool is made one matrix at a time, so that set-up holds no
    more of it on the device than one request does."""

    def __init__(self, config: dict, traffic: dict, n: int, seed: int):
        k_f, k_w = jax.random.split(prng_key(seed))
        self.feats = [np.asarray(_make_features(jax.random.fold_in(k_f, i),
                                                n, config["feature_dim"]))
                      for i in range(traffic["feature_pool"])]
        self.chains = traffic["weight_chains"]
        self.weights = [np.asarray(w) for w in
                        _make_weights(k_w, dims(config), self.chains)]

    def features(self, i: int) -> np.ndarray:
        return self.feats[max(i, 0) % len(self.feats)]

    def chain(self, i: int) -> list:
        j = self.chains if i == WARMUP else i % self.chains
        return [w[j] for w in self.weights]


class Program:
    """The system under test, set up once for a graph."""

    def __init__(self, config: dict, a, interpret=None, spans=None):
        from repro.runtime import EngineConfig, ServingEngine

        tile = config["tile"]
        self.engine = ServingEngine(EngineConfig(
            device_budget_bytes=int(config["device_budget_bytes"]),
            max_batch_features=config["feature_dim"], bm=tile["bm"],
            bk=tile["bk"], align=tile["align"], interpret=interpret))
        self.engine.register_graph(GRAPH, program_csr(a))
        self.spans = spans

    def prepare(self, inputs: Inputs) -> None:
        """Pricing a request prepares the streamed plan (RoBW, densify)."""
        with span("host_prep", self.spans):
            self.engine.estimate_request_cost(self.request(inputs, 0))

    @staticmethod
    def request(inputs: Inputs, i: int):
        from repro.runtime import InferenceRequest

        return InferenceRequest(GRAPH, inputs.features(i), inputs.chain(i))

    def serve(self, inputs: Inputs, ids: list):
        """Submit requests `ids` and run one batch; the outputs that came
        back, by id."""
        ours = {int(self.engine.submit(self.request(inputs, i))): i
                for i in ids}
        report = self.engine.run_batch()
        outputs = {ours[r.request_id]: r.output for r in report.results
                   if r.request_id in ours}
        return outputs, report


def reference_outputs(config, a, inputs: Inputs, ids, precision="highest"):
    edges = device_edges(a)
    n_layers = len(config["hidden_dims"]) + 1
    with jax.default_matmul_precision("float32"):
        for i in ids:
            yield i, np.asarray(reference.serve_forward(
                [jax.numpy.asarray(w) for w in inputs.chain(i)], edges,
                jax.numpy.asarray(inputs.features(i)), n_layers, precision))


def run(ctx: Context) -> Outcome:
    config, traffic = ctx.config, ctx.traffic
    spans: dict = {}
    mark = ctx.compiles.mark()
    with span("graph", spans):
        a = graphs.build(config)
    program = Program(config, a, ctx.interpret, spans)
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
    window = Window(ctx)
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
            "passes": [(a.n, a.n, a.nnz, f) for f in dims(config)[:-1]] * k,
            "model_flops": k * work.gcn_forward_flops(a.n, a.nnz,
                                                      dims(config)),
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
