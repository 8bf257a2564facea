#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--out FILE]

One process sets the cell up once and then, for each seed, reads:

  program   the gaps between what the program produces at the cell's size
            and the plain reference: the lower readings;
  control   the reference computed at the next precision below the one the
            configuration states ("high": three bfloat16 passes), put in
            the program's place: an upper reading;
  faults    the reference with a fault planted, put in the program's place:
            training, half of the nodes left out of the loss (the mean over
            the rest); serving, one served answer altered (a row zeroed)
            and half of Â's rows left out of every aggregation. A training
            state left unchanged reads 1 on grad_gap and delta_gap by
            their definition and needs no run.

Training drives the program's step through the three steps that a run
compares; serving serves `compare_requests` requests of the cell's batch
size. Runs on the chip only, like `run.py`; one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INF = {k: float("inf") for k in ("loss_gap", "grad_gap", "delta_gap",
                                 "out_gap")}


def train_readings(driver, program, config, traffic, a, seed,
                   control_only=False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    h0, labels, p0 = driver.make_data(config, a.n, seed)
    if control_only:
        return _train_control(driver, config, traffic, a, h0, labels, p0)
    init_opt, step = program.step_fn(h0, labels, traffic)
    params, opt = p0, init_opt(p0)
    losses = []
    for i in range(driver.CHECK_STEPS):
        loss, params, opt = step(params, opt)
        jax.block_until_ready((loss, params, opt))
        losses.append(float(loss))
        if i == 0:
            m1 = jax.device_get(opt["m"])
    p0, p3 = jax.device_get(p0), jax.device_get(params)
    del step, params, opt, init_opt

    def ref(precision="highest", mask=None):
        l, g, p = driver.reference_steps(config, traffic, a, h0, labels, p0,
                                         precision, mask)
        return [float(x) for x in l], jax.device_get(g), jax.device_get(p)

    def gaps(got_losses, got_m1, got_p3, want):
        return {c.name: c.value for c in driver.compare(
            INF, traffic["b1"], got_losses, got_m1, p0, got_p3, *want)}

    highest = ref()
    b1 = traffic["b1"]
    as_m1 = lambda g: {k: np.asarray(v) * (1 - b1) for k, v in g.items()}
    out = {"program": gaps(losses, m1, p3, highest)}
    for name, kwargs in (
            ("control", {"precision": "high"}),
            ("half_batch", {"mask": jnp.arange(a.n) % 2 == 0})):
        l, g, p = ref(**kwargs)
        out[name] = gaps(l, as_m1(g), p, highest)
    out["state_unchanged"] = gaps(losses[:1] * len(losses),
                                  {k: 0 * v for k, v in m1.items()}, p0,
                                  highest)
    return out


def _train_control(driver, config, traffic, a, h0, labels, p0) -> dict:
    """The control alone: the reference at "high" against "highest"."""
    import jax
    import numpy as np

    p0 = jax.device_get(p0)
    want, got = (driver.reference_steps(config, traffic, a, h0, labels, p0,
                                        precision)
                 for precision in ("highest", "high"))
    want, got = jax.device_get(want), jax.device_get(got)
    m1 = {k: np.asarray(v) * (1 - traffic["b1"]) for k, v in got[1].items()}
    return {"control": {c.name: c.value for c in driver.compare(
        INF, traffic["b1"], got[0], m1, p0, got[2], *want)}}


def serve_readings(driver, program, config, traffic, a, seed,
                   control_only=False) -> dict:
    import numpy as np

    from bench.lib import graphs
    from bench.lib.common import rel_max_gap

    inputs = driver.Inputs(config, traffic, a.n, seed)
    k, want = traffic["in_flight"], traffic["compare_requests"]
    if control_only:
        ids = list(range(want))
        ref = dict(driver.reference_outputs(config, a, inputs, ids))
        control = driver.reference_outputs(config, a, inputs, ids, "high")
        return {"control": {"out_gap": max(
            rel_max_gap(out, ref[i]) for i, out in control)}}
    served = {}
    for start in range(0, want, k):
        outputs, _ = program.serve(inputs, list(range(start, start + k)))
        served.update(outputs)
    ids = sorted(served)[:want]
    ref = dict(driver.reference_outputs(config, a, inputs, ids))
    control = dict(driver.reference_outputs(config, a, inputs, ids, "high"))
    keep = a.rows() < a.n // 2                 # half of Â's rows left out
    half = graphs.Csr(np.concatenate([a.indptr[:a.n // 2 + 1],
                                      np.full(a.n - a.n // 2,
                                              a.indptr[a.n // 2])]),
                      a.indices[keep], a.data[keep], a.n)
    half_out = dict(driver.reference_outputs(config, half, inputs, ids))

    def altered(x):
        x = x.copy()
        x[len(x) // 3] = 0.0
        return x

    worst = lambda got: max(rel_max_gap(got[i], ref[i]) for i in ids)
    return {"program": {"out_gap": worst(served)},
            "control": {"out_gap": worst(control)},
            "answer_altered": {"out_gap": worst({i: altered(ref[i])
                                                 for i in ids})},
            "half_rows": {"out_gap": worst(half_out)}}


READINGS = {"gcn_train": train_readings, "gcn_serve": serve_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    ap.add_argument("--control-only", action="store_true",
                    help="read the control alone, without the program")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax

    from bench import registry
    from bench.lib import graphs
    from bench.lib.peaks import UnsupportedDevice, peaks_for

    try:
        peaks_for(jax.devices()[0])
    except UnsupportedDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = registry.load_cell(ROOT, args.workload)
    driver = registry.load_driver(ROOT, cell.traffic["driver"])
    a = graphs.build(cell.config)
    program = None if args.control_only else driver.Program(cell.config, a)
    sink = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **READINGS[cell.traffic["driver"]](
                               driver, program, cell.config, cell.traffic,
                               a, seed, args.control_only)})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
