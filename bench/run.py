#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic mix,
limits and metrics are found by name (`bench/registry.py`). The run needs
a TPU whose `device_kind` is in `bench/lib/peaks.py`, and as many chips as
the cell asks for; otherwise it exits non-zero and prints no result. It
never falls back to the CPU.

Set-up (graph, host preparation, inputs and weights from `--seed`, warm-up
that compiles every program the window runs) counts as `setup_s`; then the
driver measures for `--seconds`. With `--trace 0` the result carries the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read
from a profiler trace of one part of the window. Every run compares what
its window produced with the plain reference and prints each number
compared beside its limit, last on standard error and last in the line.

JAX's persistent compilation cache lives in `<checkout>/.jax_cache`, so
only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _number(x):
    return float(x) if x is not None and math.isfinite(x) else None


def execute(root: str, workload: str, seed: int, seconds: float,
            trace: bool, peaks, devices, interpret=None,
            t_start: float = None) -> dict:
    """Drive one run of `workload`; returns the result line as a dict."""
    from bench import registry
    from bench.lib.common import Context
    from bench.lib.compile_counter import CompileCounter

    cell = registry.load_cell(root, workload)
    driver = registry.load_driver(root, cell.traffic["driver"])
    ctx = Context(workload=workload, config=cell.config,
                  traffic=cell.traffic, limits=cell.limits, seed=seed,
                  seconds=seconds, trace=trace,
                  t_start=T_START if t_start is None else t_start,
                  compiles=CompileCounter(), interpret=interpret)
    out = driver.run(ctx)

    record = dict(out.record, peaks=peaks._asdict())
    for phase, c in record["compiles"].items():
        log(f"{phase}: {c['compiled']} programs compiled, {c['loaded']} "
            f"loaded from the persistent cache, {c['seconds']:.3f} s of "
            "backend compile")
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = registry.load_metric(root, m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": out.failed == 0 and all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    reduced = record.get("trace")
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {c.name: {"value": _number(c.value),
                                 "limit": c.limit} for c in out.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    sys.path.insert(0, ROOT)
    from bench import registry

    chips = registry.load_cell(ROOT, args.workload).workload["chips"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    from bench.lib.peaks import UnsupportedDevice, peaks_for

    devices = jax.devices()
    try:
        peaks = peaks_for(devices[0])
    except UnsupportedDevice as e:
        log(str(e))
        return 2
    if len(devices) < chips:
        log(f"{args.workload} needs {chips} chips, found {len(devices)}")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"the program is missing from this checkout: {e}")
        return 2
    log(f"compile cache {enable_compile_cache()}; devices {devices}")

    result = execute(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace), peaks, devices)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
