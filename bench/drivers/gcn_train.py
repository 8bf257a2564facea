"""Closed-loop full-batch GCN training: one epoch after another.

Set-up builds Â, prepares the streamed directions, makes the features,
labels and weights from the seed on the device, builds the program's step
(`make_gcn_train_step` over `AiresSpGEMM`, AdamW) and drives it through
step 1, which compiles every program the window runs. The window takes the
same step and state on: epoch after epoch, starting none after `seconds`.
It ends with the last epoch, and holds at least steps 2 and 3.

`correct` follows the first three steps of that object against the plain
reference (`bench.lib.reference`): each step's loss, the first gradient as
AdamW holds it after step 1 (m / (1 - b1)), and the change of the
parameters over the three steps, both by the worst leaf.
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
    leaf_norm_gaps, memory_peak_bytes, prng_key, program_csr, span,
)

CHECK_STEPS = 3


def dims(config: dict) -> tuple:
    return (config["feature_dim"], *config["hidden_dims"],
            config["n_classes"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_data(key, n: int, dims_: tuple):
    """Features, labels and initial weights, in one call on the device."""
    import jax.numpy as jnp

    k_h, k_l, k_p = jax.random.split(key, 3)
    h0 = jax.random.normal(k_h, (n, dims_[0]), jnp.float32)
    labels = jax.random.randint(k_l, (n,), 0, dims_[-1])
    params = {}
    for i, (d_in, d_out) in enumerate(zip(dims_[:-1], dims_[1:])):
        params[f"w{i}"] = (jax.random.normal(jax.random.fold_in(k_p, i),
                                             (d_in, d_out), jnp.float32)
                           * d_in ** -0.5)
        params[f"b{i}"] = jnp.zeros((d_out,), jnp.float32)
    return h0, labels, params


def make_data(config: dict, n: int, seed: int):
    return _make_data(prng_key(seed), n, dims(config))


def hyper(traffic: dict) -> dict:
    return {k: traffic[k] for k in ("lr", "b1", "b2", "eps", "weight_decay")}


class Program:
    """The system under test, set up once for a graph."""

    def __init__(self, config: dict, a, interpret=None, spans=None):
        from repro.core import AiresConfig, AiresSpGEMM
        from repro.models.gcn import GCNConfig

        tile = config["tile"]
        self.a = program_csr(a)
        self.cfg = GCNConfig(name=config["name"],
                             feature_dim=config["feature_dim"],
                             hidden_dims=tuple(config["hidden_dims"]),
                             n_classes=config["n_classes"],
                             out_of_core=True, dtype=config["dtype"])
        self.engine = AiresSpGEMM(AiresConfig(
            device_budget_bytes=int(config["device_budget_bytes"]),
            bm=tile["bm"], bk=tile["bk"], align=tile["align"],
            interpret=interpret))
        with span("host_prep", spans):
            for transpose in (False, True):
                self.engine.stream_plan(self.a, (a.n, config["feature_dim"]),
                                        transpose=transpose)

    def step_fn(self, h0, labels, traffic: dict):
        from repro.train.loop import make_gcn_train_step

        h = hyper(traffic)
        return make_gcn_train_step(self.cfg, self.engine, self.a, h0, labels,
                                   optimizer="adamw", **h)

    def passes(self) -> list:
        return self.engine.forward_stats_log + self.engine.backward_stats_log


def pass_widths(config: dict) -> list:
    """Feature widths of one step's streamed passes: each layer's input
    forward, and the gradient of each layer's input but the first."""
    d = dims(config)
    return list(d[:-1]) + list(d[1:-1])


def compare(ctx_limits: dict, b1: float, losses, m1: dict, p0: dict,
            p3: dict, ref_losses, ref_g1: dict, ref_p3: dict) -> list:
    """The three numbers `correct` is judged by, beside their limits."""
    loss_gap = max(abs(float(l) - float(r)) / abs(float(r))
                   for l, r in zip(losses, ref_losses))
    g1 = {k: np.asarray(v) / (1.0 - b1) for k, v in m1.items()}
    grad_gap = max(leaf_norm_gaps(g1, ref_g1).values())
    # Leaves whose reference gradient is under a thousandth of the median
    # leaf's move by round-off alone under Adam: left out of the change.
    g_norm = {k: float(np.linalg.norm(np.asarray(v))) for k, v in
              ref_g1.items()}
    median = float(np.median(list(g_norm.values())))
    keep = {k for k, v in g_norm.items() if v >= 1e-3 * median}
    change = {k: np.asarray(p3[k]) - np.asarray(p0[k]) for k in p0}
    ref_change = {k: np.asarray(ref_p3[k]) - np.asarray(p0[k]) for k in p0}
    delta_gap = max(leaf_norm_gaps(change, ref_change, keep).values())
    return [Check("loss_gap", loss_gap, ctx_limits["loss_gap"]),
            Check("grad_gap", grad_gap, ctx_limits["grad_gap"]),
            Check("delta_gap", delta_gap, ctx_limits["delta_gap"])]


def reference_steps(config, traffic, a, h0, labels, p0, precision="highest",
                    mask=None):
    with jax.default_matmul_precision("float32"):
        return reference.train_steps(
            p0, device_edges(a), h0, labels, CHECK_STEPS,
            len(config["hidden_dims"]) + 1, hyper(traffic), precision, mask)


def run(ctx: Context) -> Outcome:
    config, traffic = ctx.config, ctx.traffic
    spans: dict = {}
    mark = ctx.compiles.mark()
    with span("graph", spans):
        a = graphs.build(config)
    program = Program(config, a, ctx.interpret, spans)
    with span("data", spans):
        h0, labels, p0 = make_data(config, a.n, ctx.seed)
        jax.block_until_ready((h0, labels, p0))
    init_opt, step = program.step_fn(h0, labels, traffic)
    with span("warmup", spans):
        opt = init_opt(p0)
        loss, params, opt = step(p0, opt)
        jax.block_until_ready((loss, params, opt))
    losses, m1 = [loss], dict(opt["m"])
    setup_compiles = ctx.compiles.since(mark)
    setup_s = time.perf_counter() - ctx.t_start

    trace_epoch = traffic["trace_epoch"] if ctx.trace else -1
    program.engine.reset_stats_logs()
    window = Window(ctx)
    epochs, p3 = 0, None
    window.start()
    while True:
        with window.traced(epochs == trace_epoch), span("epoch"):
            loss, params, opt = step(params, opt)
            jax.block_until_ready((loss, params, opt))
        epochs += 1
        losses.append(loss)
        if len(losses) == CHECK_STEPS:
            p3 = params
        if (window.elapsed() >= ctx.seconds and len(losses) >= CHECK_STEPS
                and epochs > trace_epoch):
            break
    window.stop()
    peak = memory_peak_bytes()

    passes = program.passes()
    bus = sum(s.uploaded_bytes + s.promoted_bytes for s in passes)
    n_finite = sum(bool(np.isfinite(float(l))) for l in losses[1:])
    reduced = window.reduce_trace()
    widths = pass_widths(config)
    record = {
        "spans": {**spans, "compile": setup_compiles["seconds"]},
        "compiles": {"setup": setup_compiles, "window": window.compiles},
        "counters": {"epochs": epochs, "units": epochs, "bus_bytes": bus},
        "trace": reduced,
        "traced": None if reduced is None else {
            "passes": [(a.n, a.n, a.nnz, f) for f in widths],
            "model_flops": work.gcn_train_flops(a.n, a.nnz, dims(config)),
        },
    }
    losses = [float(l) for l in losses[:CHECK_STEPS]]
    p0, p3 = jax.device_get(p0), jax.device_get(p3)
    del program, step, params, opt, init_opt, passes
    gc.collect()

    with span("reference"):
        ref_losses, ref_g1, ref_p3 = reference_steps(
            config, traffic, a, h0, labels, p0)
        ref_losses = [float(l) for l in ref_losses]
        ref_g1, ref_p3 = jax.device_get(ref_g1), jax.device_get(ref_p3)
    checks = compare(ctx.limits, traffic["b1"], losses, m1, p0, p3,
                     ref_losses, ref_g1, ref_p3)
    return Outcome(attempted=epochs, failed=epochs - n_finite,
                   end_to_end={"epoch_s": window.seconds / epochs,
                               "setup_s": setup_s,
                               "peak_hbm_gb": (peak or 0) / 1e9},
                   record=record, checks=checks, memory_peak_bytes=peak)
