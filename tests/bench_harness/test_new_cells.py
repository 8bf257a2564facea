"""The benchmark's GAT and four-chip serving cells, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

Whole runs of `rUSA.gat_serve` (driver `gat_serve`) and of
`kV2a.serve_shard4` (driver `gcn_serve_shard`, in a child process with
four virtual CPU devices, since this process's JAX holds one) at 512
vertices with the configurations' own widths: `correct` is true for the
program and false for the "high" control and for a planted fault. The
new readers on hand-built records, and the GAT work counts by hand.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import registry  # noqa: E402
from bench.lib import gat_work, graphs  # noqa: E402
from bench.lib.peaks import PEAKS  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
TINY = {"n_vertices": 512, "n_edges": 1024}
SEED = 2**31 + 11


def make_tiny_root(root: str) -> str:
    """The benchmark with each configuration cut to TINY: a device budget
    that gives several segments a pass and, for the GAT, a device tier
    that holds a part of them."""
    from repro.core import calc_mem, plan_memory_dense_features

    from bench.lib.common import program_csr

    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = dict(json.load(f), **TINY)
        a = graphs.build(config)
        width = config.get("plan_width", config["feature_dim"])
        est = plan_memory_dense_features(program_csr(a), a.n, width,
                                         float("inf"))
        csr = calc_mem(a.n, a.nnz)
        config["device_budget_bytes"] = int(est.m_b + est.m_c) + csr // 3
        if "cache_device_bytes" in config:
            config["cache_device_bytes"] = 2 * csr
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(config, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))


def execute(root, workload, seconds=0.5, trace_on=False):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "bench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cpu = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    return run.execute(root, workload, SEED, seconds, trace_on, V5E, cpu,
                       interpret=True, t_start=time.perf_counter())


def gat_control(root):
    """`ServingEngine.run_batch` whose outputs are the plain reference's
    at the control precision."""
    from repro.runtime import ServingEngine

    from bench.lib import gat_reference

    with open(os.path.join(root, "bench/configs/gat_ppi-rUSA.json")) as f:
        config = json.load(f)
    original = ServingEngine.run_batch

    def control(self):
        queue = list(self._queue)
        report = original(self)
        a = self._graphs[queue[0].graph]
        pattern = gat_reference.Pattern(graphs.Csr(
            a.indptr, a.indices, a.data, a.shape[0]))
        for r, q in zip(report.results, queue):
            r.output = np.asarray(gat_reference.forward(
                config, list(q.weights), pattern, q.features, "high"))
        return report

    return control


def row_zeroed(original):
    def fault(self):
        report = original(self)
        for r in report.results:
            r.output = r.output.copy()
            r.output[len(r.output) // 2] = 0.0
        return report

    return fault


def test_gat_cell_runs_and_refuses_the_control_and_a_fault(tiny_root,
                                                           monkeypatch):
    from repro.runtime import ServingEngine

    result = execute(tiny_root, "rUSA.gat_serve")
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    names = {m["name"] for m in registry.load_cell(
        tiny_root, "rUSA.gat_serve").end_to_end}
    assert set(result["metrics"]) == names == {
        "serve_s_per_req", "peak_hbm_gb", "setup_s"}
    for name, fault in (("control", gat_control(tiny_root)),
                        ("row_zeroed", row_zeroed(ServingEngine.run_batch))):
        with monkeypatch.context() as m:
            m.setattr(ServingEngine, "run_batch", fault)
            result = execute(tiny_root, "rUSA.gat_serve")
        assert not result["correct"], (name, result["checks"])


def test_gat_cell_traced_reports_its_host_and_cache_layers(tiny_root):
    """Off the chip the trace is empty, so a traced run reports the
    per-layer metrics read from spans and counters alone."""
    result = execute(tiny_root, "rUSA.gat_serve", trace_on=True)
    assert result["correct"], result["checks"]
    for name in ("host_prep_s", "compile_s", "bus_gb", "tier_hit_share"):
        assert f"{name}.gat_serve" in result["metrics"], name
    assert result["metrics"]["bus_gb.gat_serve"]["value"] > 0


SHARD_SCRIPT = """
import json, sys, types
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import test_new_cells as t
from repro.runtime import ServingEngine
from bench.lib import reference

root = t.make_tiny_root({tmp!r})
original = ServingEngine.run_batch

def control(self):
    queue = list(self._queue)
    report = original(self)
    a = self._graphs[queue[0].graph]
    rows = t.np.repeat(t.np.arange(a.n_rows), t.np.diff(a.indptr))
    edges = tuple(map(t.np.asarray, (rows, a.indices, a.data)))
    for r, q in zip(report.results, queue):
        r.output = t.np.asarray(reference.serve_forward(
            list(q.weights), edges, q.features, len(q.weights), "high"))
    return report

for name, run_batch in (("program", original), ("control", control),
                        ("row_zeroed", t.row_zeroed(original))):
    ServingEngine.run_batch = run_batch
    result = t.execute(root, "kV2a.serve_shard4", trace_on=name == "program")
    print("RESULT", name, json.dumps(result), flush=True)
"""


def test_shard_cell_on_four_virtual_devices(tmp_path):
    script = SHARD_SCRIPT.format(
        root=ROOT, src=os.path.join(ROOT, "src"),
        tests=os.path.dirname(os.path.abspath(__file__)),
        tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    results = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
               for line in done.stdout.splitlines()
               if line.startswith("RESULT ")}
    assert results["program"]["correct"], results["program"]["checks"]
    assert not results["control"]["correct"], results["control"]["checks"]
    assert not results["row_zeroed"]["correct"]
    # After the warm-up the bricks sit on several devices (at this size a
    # pass has too few segments for CRC owners to reach every chip), and
    # hits on them cross to the device that computes.
    held = [line for line in done.stderr.splitlines()
            if "brick bytes by device after the warm-up" in line]
    assert held
    by_device = eval(held[0].split("warm-up ", 1)[1])   # a dict literal
    assert len(by_device) >= 2 and min(by_device.values()) > 0
    assert results["program"]["metrics"]["ici_gb.serve_shard4"]["value"] > 0
    for name in ("host_prep_s", "compile_s", "bus_gb", "tier_hit_share"):
        assert f"{name}.serve_shard4" in results["program"]["metrics"], name


# ------------------------------------------------------------ readers ----

def _record(**extra):
    record = {"peaks": V5E._asdict(),
              "trace": {"window_s": 2.0, "busy_s": 1.5, "chips": 1,
                        "kernel_s": {"bcsr_spmm": 0.0, "gat_attn": 0.5}},
              "traced": {"model_flops": 4e12,
                         "attn_passes": [(1000, 5000, 4, 256)] * 2},
              "counters": {"units": 4, "ici_bytes": 6e9}}
    record.update(extra)
    return record


def test_new_readers_on_a_hand_built_record():
    metric = lambda name: registry.load_metric(ROOT, name)
    record = _record()
    least = 2 * gat_work.attn_min_seconds(1000, 5000, 4, 256, V5E)[0]
    assert metric("gat_attn_roofline.gat_serve")(record) == pytest.approx(
        100 * least / 0.5)
    assert metric("gat_attn_busy_share.gat_serve")(record) == 25.0
    assert metric("mfu.gat_serve")(record) == pytest.approx(
        100 * 4e12 / 2.0 / 197e12)
    assert metric("idle_share.gat_serve")(record) == 25.0
    assert metric("ici_gb.serve_shard4")(record) == 1.5
    assert metric("mfu.serve_shard4")(record) is None   # no chip count
    record["traced"]["chips"] = 4
    assert metric("mfu.serve_shard4")(record) == pytest.approx(
        100 * 4e12 / 2.0 / 197e12 / 4)
    # A record without the attention kernel or the counter reads nothing.
    bare = _record(traced={"model_flops": 1.0, "passes": []},
                   counters={"units": 4})
    bare["trace"]["kernel_s"] = {"bcsr_spmm": 0.5}
    for name in ("gat_attn_roofline.gat_serve",
                 "gat_attn_busy_share.gat_serve", "ici_gb.serve_shard4"):
        assert metric(name)(bare) is None, name


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_shard_trace_is_reduced_on_the_computing_chip():
    """Four chips: chip 2 runs the kernel, the others a copy each. The
    reduction is chip 2's, with the mesh's average busy time beside it."""
    from bench.lib import trace as trace_lib

    shard = registry.load_driver(ROOT, "gcn_serve_shard")
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    ops = {2: [_event("%kernel.1 = f32[8] custom-call(...)", 1_000, 4_000)]}
    planes = [types.SimpleNamespace(name="/host:CPU", lines=[
        line("main", [_event(trace_lib.WINDOW, 0, 10_000)])])] + [
        types.SimpleNamespace(name=f"/device:TPU:{i}", lines=[
            line("XLA Ops", ops.get(i, [_event("%copy.1 = f32[8] copy(...)",
                                               2_000, 1_000)])),
            line("XLA Modules", [])]) for i in range(4)]
    got = shard.reduce_on_computing_chip(
        types.SimpleNamespace(planes=planes), {"spmm": "custom-call"},
        "spmm")
    assert got["chips"] == 1 and got["mesh_chips"] == 4
    assert got["busy_s"] == pytest.approx(4e-6)
    assert got["kernel_s"]["spmm"] == pytest.approx(4e-6)
    assert got["mesh_busy_s"] == pytest.approx((4 + 3 * 1) / 4 * 1e-6)
    # The base readers then read that chip: 60% idle, 40% in the kernel.
    record = {"trace": got, "traced": {"model_flops": 0.0}}
    assert registry.load_metric(ROOT, "idle_share.serve_shard4")(
        record) == pytest.approx(60.0)
    empty = types.SimpleNamespace(planes=planes[:1])
    assert shard.reduce_on_computing_chip(empty, {"spmm": "x"}, "spmm") is None


def test_gat_work_counts_by_hand():
    # 10 vertices, 30 entries of A + I, 2 heads of 4, F_in 3.
    assert gat_work.attn_flops(30, 2, 4) == 2 * (8 + 7) * 30
    # Pattern 11*4 + 30*4; z and the output 2 * 10*2*4*4; scores 2*10*2*4.
    assert gat_work.attn_bytes(10, 30, 2, 4) == 44 + 120 + 640 + 160
    assert gat_work.gat_forward_flops(10, 30, [(3, 2, 4)]) == (
        2 * 10 * 3 * 8 + 4 * 10 * 8 + 900)
    with open(os.path.join(ROOT, "bench/configs/gat_ppi-rUSA.json")) as f:
        config = json.load(f)
    assert gat_work.layers(config) == [(256, 4, 256), (1024, 4, 256),
                                       (1024, 6, 121)]
    # The projections of one request at 478,800 vertices: 1.97 TFLOP.
    proj = sum(2 * 478800 * f_in * k * f
               for f_in, k, f in gat_work.layers(config))
    assert proj == pytest.approx(1.967e12, rel=1e-3)
