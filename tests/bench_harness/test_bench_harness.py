"""Tests of the benchmark's yardstick, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

Graph generators, work counts, the peaks table, the trace reduction (on
hand-made events and on a trace recorded on a TPU v5e), the registry that
finds a cell's parts by name, the refusal to run without a TPU, and whole
runs of each cell's driver at a tiny size: sound, and with the timed path
broken underneath, where `correct` has to come out false.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import registry  # noqa: E402
from bench.lib import graphs, trace, work  # noqa: E402
from bench.lib.peaks import PEAKS, UnsupportedDevice, peaks_for  # noqa: E402

V5E = PEAKS["TPU v5 lite"]


# ------------------------------------------------------------ graphs ----

@pytest.mark.parametrize("family,params", [
    ("uniform", {}), ("banded", {"band": 64}),
    ("banded", {"band": 64, "symmetric": True})])
def test_generator_counts_and_degrees(family, params):
    n, m = 5_000, 12_000
    a = graphs.generate(family, n, m, seed=3, **params)
    dense = np.zeros((n, n), bool)
    dense[a.rows(), a.indices] = True
    assert np.array_equal(dense, dense.T) == params.get("symmetric", False)
    assert a.n == n and a.indptr.shape == (n + 1,)
    # Parallel edges are dropped: a few at most at this density.
    assert m - 200 <= a.nnz <= m
    out_deg = np.diff(a.indptr)
    in_deg = np.bincount(a.indices, minlength=n)
    assert abs(out_deg.mean() - m / n) < 0.05
    # Poisson degrees: no hub anywhere, the ends of the id range included.
    assert in_deg.max() < 15 and out_deg.max() < 15
    assert in_deg[0] < 15 and in_deg[-1] < 15
    rows = a.rows()
    if family == "banded":
        span = n // params["band"]
        dist = np.minimum((a.indices - rows) % n, (rows - a.indices) % n)
        assert dist.max() <= span
        # Wrap-around: some edges cross the ends of the id range.
        assert np.any(np.abs(a.indices - rows) > n - span - 1)
    else:
        assert np.abs(a.indices - rows).max() > n // 2


def test_generator_is_fixed_by_its_seed():
    one = graphs.generate("uniform", 1000, 3000, seed=7)
    two = graphs.generate("uniform", 1000, 3000, seed=7)
    assert np.array_equal(one.indices, two.indices)
    assert not np.array_equal(
        one.indices, graphs.generate("uniform", 1000, 3000, seed=8).indices)


def test_normalize_matches_dense_formula():
    a = graphs.generate("banded", 60, 150, seed=1, band=8)
    dense = np.zeros((60, 60))
    dense[a.rows(), a.indices] = 1.0
    dense = np.maximum(dense, np.eye(60))
    dinv = 1.0 / np.sqrt(dense.sum(axis=1))
    want = dinv[:, None] * dense * dinv[None, :]
    got = graphs.normalize(a)
    out = np.zeros((60, 60))
    out[got.rows(), got.indices] = got.data
    assert np.allclose(out, want, rtol=1e-6)
    for r in range(60):   # columns sorted within each row
        cols = got.indices[got.indptr[r]:got.indptr[r + 1]]
        assert np.all(np.diff(cols) > 0)


def test_configured_graphs_match_their_stated_counts():
    for name in ("gcn_paper-kV2a", "gcn_paper-rUSA"):
        with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
            config = json.load(f)
        pub = config["published"]
        scale = config["n_vertices"] / pub["n_vertices"]
        assert config["n_edges"] == pytest.approx(pub["n_edges"] * scale,
                                                  rel=1e-3)
        # The same generator at 1e-4 of the configuration's scale.
        small = dict(config, n_vertices=config["n_vertices"] // 100,
                     n_edges=config["n_edges"] // 100)
        a = graphs.build(small)
        assert a.n == small["n_vertices"]
        # Â has every distinct edge plus the diagonal: parallel edges and
        # self-loops (within a band of 2n/64 + 1) take a few percent.
        full = small["n_edges"] + a.n
        assert 0.97 * full <= a.nnz <= full
        # rUSA is undirected: Â equals its transpose.
        at = sorted(zip(a.indices, a.rows(), a.data))
        assert (at == sorted(zip(a.rows(), a.indices, a.data))) == (
            config["graph"].get("symmetric", False))


# ------------------------------------------------------ work, peaks ----

def test_work_counts_by_hand():
    # 10 rows, 30 nonzeros, F = 4.
    assert work.spmm_flops(30, 4) == 240
    # CSR 11*4 + 30*8, H read 10*4*4, X written 10*4*4.
    assert work.spmm_bytes(10, 10, 30, 4) == 44 + 240 + 160 + 160
    # dims 4 -> 3 -> 2 on 10 nodes: 2*30*4 + 2*10*4*3 + 2*30*3 + 2*10*3*2.
    assert work.gcn_forward_flops(10, 30, (4, 3, 2)) == 240 + 240 + 180 + 120
    assert work.gcn_train_flops(10, 30, (4, 3, 2)) == 3 * 780
    seconds, bound = work.spmm_min_seconds(10, 10, 30, 4, V5E)
    assert bound == "memory" and seconds == pytest.approx(604 / 819e9)
    big, bound = work.spmm_min_seconds(8, 8, 10**6, 4096, V5E)
    assert bound == "compute" and big == pytest.approx(2 * 4096e6 / 197e12)


@pytest.mark.parametrize("platform,kind", [("cpu", "cpu"),
                                           ("tpu", "TPU v7 unknown"),
                                           ("gpu", "TPU v5 lite")])
def test_peaks_refuse_other_devices(platform, kind):
    with pytest.raises(UnsupportedDevice):
        peaks_for(types.SimpleNamespace(platform=platform, device_kind=kind))


def test_peaks_of_v5e():
    p = peaks_for(types.SimpleNamespace(platform="tpu",
                                        device_kind="TPU v5 lite"))
    assert (p.flops_per_s, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 819e9, 16e9)


# ------------------------------------------------------------- trace ----

def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _profile(host, device, modules):
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[line("main", host)]),
        types.SimpleNamespace(name="/device:TPU:0",
                              lines=[line("XLA Ops", device),
                                     line("XLA Modules", modules)]),
    ])


KERNEL = 'custom-call(...), custom_call_target="tpu_custom_call"'


def test_reduction_by_hand():
    host = [_event("bench.traced", 1_000, 9_000),
            _event("bench.epoch", 1_001, 8_999),
            _event("PjitFunction(bcsr_spmm_pallas)", 2_000, 500),
            _event("np.asarray(jax.Array)", 6_000, 2_000)]
    modules = [_event("jit_add(123)", 0, 1_500),
               _event("jit_bcsr_spmm_pallas(77)", 2_900, 2_200),
               _event("jit_add(123)", 9_000, 3_000)]
    device = [_event("%fusion.3 = f32[8] fusion(...)", 500, 1_000),
              _event("%while.2 = (s32[]) while(...)", 3_000, 2_000),
              _event(f"%closed_call.10 = f32[8] {KERNEL}", 3_000, 1_200),
              _event(f"%closed_call.10 = f32[8] {KERNEL}", 4_200, 800),
              _event("%copy.1 = f32[8] copy(...)", 9_000, 3_000)]
    got = trace.reduce(_profile(host, device, modules), {
        "bcsr_spmm": r'^jit_bcsr_spmm_pallas .*"tpu_custom_call"',
        "adds": r"^jit_add "})
    assert got["window_s"] == pytest.approx(9e-6)
    # Busy: [1000,1500] + [3000,5000] + [9000,10000] = 3500 ns.
    assert got["busy_s"] == pytest.approx(3.5e-6)
    assert got["kernel_s"]["bcsr_spmm"] == pytest.approx(2e-6)
    assert got["kernel_s"]["adds"] == pytest.approx(1.5e-6)
    # The while loop holds the two calls and is left out of the ranking.
    assert dict(got["device_ops"]) == pytest.approx({
        "jit_add/fusion": 0.5e-6, "jit_bcsr_spmm_pallas/closed_call": 2e-6,
        "jit_add/copy": 1e-6})
    # Gaps [1500,3000] (mid 2250: the dispatch), [5000,9000] (mid 7000:
    # the host sync), each inside the epoch span.
    assert dict(got["idle_gaps"]) == pytest.approx({
        "PjitFunction(bcsr_spmm_pallas) in bench.epoch": 1.5e-6,
        "np.asarray(jax.Array) in bench.epoch": 4e-6})


def test_reduction_without_window_or_device_is_empty():
    host = [_event("bench.epoch", 0, 100)]
    assert trace.reduce(_profile(host, [], []), {}) is None
    host = [_event("bench.traced", 0, 100)]
    assert trace.reduce(_profile(host, [], []), {}) is None


def test_reduction_of_a_recorded_tpu_trace():
    """Two streamed passes of a small graph, recorded on one TPU v5e: the
    reduction agrees with a brute-force count over a 100 ns grid."""
    from bench.lib.common import KERNELS

    pd = trace.load(os.path.join(DATA, "v5e_two_passes.xplane.pb"))
    got = trace.reduce(pd, KERNELS)
    assert got is not None and got["chips"] == 1
    window = [e for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events if e.name == trace.WINDOW]
    w0 = window[0].start_ns
    w1 = w0 + window[0].duration_ns
    device = [p for p in pd.planes if trace.DEVICE_PLANE.match(p.name)]
    lines = {ln.name: list(ln.events) for ln in device[0].lines}
    spans = lambda name: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines[name]]
    grid = np.arange(w0, w1, 100.0)
    busy = np.zeros(grid.shape, bool)
    kernel = 0.0
    modules = spans(trace.MODULES_LINE)
    for s, e, name in spans(trace.OPS_LINE):
        busy |= (grid >= s) & (grid < e)
        inside = [m for m in modules if m[0] <= s < m[1]]
        if (inside and inside[0][2].startswith("jit_bcsr_spmm_pallas(")
                and 'custom_call_target="tpu_custom_call"' in name):
            kernel += max(0.0, min(e, w1) - max(s, w0))
    assert kernel > 0
    assert got["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert got["busy_s"] == pytest.approx(busy.sum() * 100e-9, rel=2e-2)
    assert got["kernel_s"]["bcsr_spmm"] == pytest.approx(kernel * 1e-9)
    assert got["kernel_s"]["bcsr_spmm"] <= got["busy_s"] <= got["window_s"]
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert got["device_ops"][0][0] == "jit_bcsr_spmm_pallas/bcsr_spmm_pallas"


# ---------------------------------------------------------- registry ----

def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_cell_parts_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, limits and a per-layer metric added
    as new files, with new entries, are found without touching the
    harness."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-new", "source": "x",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.new", "config": "tiny-new",
                              "traffic": "new_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "new_s", "unit": "s",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny.new"]})
    spec["per_layer"].append({"name": "new_metric.x", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "kernel", "moves": "new_s",
                              "workloads": ["tiny.new"]})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    _write(os.path.join(root, "bench/configs/tiny-new.json"),
           json.dumps({"name": "tiny-new", "n_vertices": 7}))
    _write(os.path.join(root, "bench/traffic/new_mix.json"),
           json.dumps({"driver": "new_driver", "rate": 3}))
    _write(os.path.join(root, "bench/limits/tiny.new.json"),
           json.dumps({"gap": 0.5}))
    _write(os.path.join(root, "bench/metrics/new_metric.x.py"),
           "def read(record):\n    return record['counters']['x'] * 2\n")
    _write(os.path.join(root, "bench/drivers/new_driver.py"),
           "def run(ctx):\n    return ctx.traffic['rate']\n")

    cell = registry.load_cell(root, "tiny.new")
    assert cell.config == {"name": "tiny-new", "n_vertices": 7}
    assert cell.traffic["rate"] == 3 and cell.limits == {"gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == [
        "peak_hbm_gb", "setup_s", "new_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert registry.load_metric(root, "new_metric.x")(
        {"counters": {"x": 4}}) == 8
    driver = registry.load_driver(root, "new_driver")
    assert driver.run(types.SimpleNamespace(traffic=cell.traffic)) == 3
    # The existing cells still see only their own metrics.
    serve = registry.load_cell(root, "rUSA.serve")
    assert "new_metric.x" not in [m["name"] for m in serve.per_layer]
    assert all(m["moves"] in ("serve_s_per_req", "setup_s")
               for m in serve.per_layer)


def test_every_listed_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["per_layer"]:
        assert registry.load_metric(ROOT, m["name"])({}) is None, m["name"]


# ------------------------------------------------------------ refusal ----

def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rUSA.serve",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_without_a_tpu():
    done = _run_cli(ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_run_refuses_in_a_bare_copy(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = _run_cli(str(tmp_path))
    assert done.returncode != 0 and done.stdout.strip() == ""


# ------------------------------------------- whole runs at a tiny size ----

# The configurations' own widths on a graph of 512 vertices: the
# control's bfloat16 error grows with the width of each dot product.
TINY = {"n_vertices": 512, "n_edges": 1024}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The benchmark with each configuration cut to TINY, and a device
    budget that still gives several segments per pass."""
    from repro.core import calc_mem, plan_memory_dense_features

    from bench.lib.common import program_csr

    root = str(tmp_path_factory.mktemp("tiny"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = dict(json.load(f), **TINY)
        a = graphs.build(config)
        est = plan_memory_dense_features(program_csr(a), a.n,
                                         config["feature_dim"], float("inf"))
        config["device_budget_bytes"] = (int(est.m_b + est.m_c)
                                         + calc_mem(a.n, a.nnz) // 3)
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(config, f)
    return root


def _execute(root, workload, seconds=0.5, trace_on=False):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "bench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cpu = [types.SimpleNamespace(platform="cpu", device_kind="cpu")]
    return run.execute(root, workload, 2**31 + 11, seconds, trace_on, V5E,
                       cpu, interpret=True, t_start=time.perf_counter())


@pytest.mark.parametrize("workload", ["kV2a.train", "rUSA.serve"])
def test_sound_run_is_correct(tiny_root, workload):
    result = _execute(tiny_root, workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert list(result)[-1] == "checks"
    names = {m["name"] for m in registry.load_cell(tiny_root,
                                                   workload).end_to_end}
    assert set(result["metrics"]) == names
    assert all(v["value"] > 0 for k, v in result["metrics"].items()
               if k != "peak_hbm_gb")


def _reference_step(cfg, engine, a, h0, labels, optimizer="adamw",
                    precision="high", **hyper):
    """The plain reference, at `precision`, in the program's place."""
    from bench.lib import reference

    rows = np.repeat(np.arange(a.n_rows), np.diff(a.indptr))
    edges = tuple(map(np.asarray, (rows, a.indices, a.data)))
    n_layers = len(cfg.hidden_dims) + 1

    def step(params, opt):
        loss, grads = reference.loss_and_grads(params, edges, h0, labels,
                                               None, n_layers, precision)
        params, opt = reference.adamw(params, grads, opt, **hyper)
        return loss, params, opt

    return reference.adamw_init, step


def _state_unchanged(params, grads, state, **_):
    return params, state


def _half_batch_loss(cfg, params, a, h0, labels, engine=None):
    """Cross-entropy over the first half of the nodes only."""
    import jax
    import jax.numpy as jnp

    from repro.models.gcn import gcn_forward

    logits = gcn_forward(cfg, params, a, h0, engine)[: a.n_rows // 2]
    labels = labels[: a.n_rows // 2]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def test_train_control_and_faults_are_refused(tiny_root, monkeypatch):
    import repro.models.gcn
    import repro.train.loop
    import repro.train.optim

    cases = {
        "control": lambda m: m.setattr(
            repro.train.loop, "make_gcn_train_step", _reference_step),
        "state_unchanged": lambda m: m.setitem(
            repro.train.optim.OPTIMIZERS, "adamw",
            (repro.train.optim.adamw_init, _state_unchanged)),
        "half_batch": lambda m: m.setattr(
            repro.models.gcn, "gcn_loss", _half_batch_loss),
    }
    for name, plant in cases.items():
        with monkeypatch.context() as m:
            plant(m)
            result = _execute(tiny_root, "kV2a.train")
        assert not result["correct"], (name, result["checks"])


def test_serve_control_and_faults_are_refused(tiny_root, monkeypatch):
    from repro.runtime import ServingEngine

    from bench.lib import reference

    original = ServingEngine.run_batch

    def control(self):
        """Outputs of the plain reference at the control's precision."""
        queue = list(self._queue)
        report = original(self)
        a = self._graphs[queue[0].graph]
        rows = np.repeat(np.arange(a.n_rows), np.diff(a.indptr))
        edges = tuple(map(np.asarray, (rows, a.indices, a.data)))
        for r, q in zip(report.results, queue):
            r.output = np.asarray(reference.serve_forward(
                list(q.weights), edges, q.features, len(q.weights), "high"))
        return report

    def answer_altered(self):
        report = original(self)
        for r in report.results:
            r.output = r.output.copy()
            r.output[len(r.output) // 2] += 1.0
        return report

    def half_left_out(self):
        report = original(self)
        report.results = report.results[: max(1, len(report.results) // 2)]
        return report

    for name, fault in (("control", control),
                        ("answer_altered", answer_altered),
                        ("half_left_out", half_left_out)):
        with monkeypatch.context() as m:
            m.setattr(ServingEngine, "run_batch", fault)
            result = _execute(tiny_root, "rUSA.serve")
        assert not result["correct"], (name, result["checks"])
