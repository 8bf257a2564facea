"""Pipeline-plan IR: plan builders, the two interpreters, and the ISSUE 4
acceptance criterion — on the fig6 configurations, cost-interpreter
`ScheduleMetrics` match the pre-refactor monolithic schedulers (frozen in
tests/data/golden_pipeline.json: counts exactly, modeled times to a
relative 1e-12), and the execute interpreter's outputs agree exactly with
the reference computation.
"""
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (
    AiresConfig,
    AiresSpGEMM,
    CacheProbeOp,
    ComputeOp,
    CostInterpreter,
    ExecuteInterpreter,
    FeatureSpec,
    HostPreprocessOp,
    PhaseSpec,
    PipelinePlan,
    SCHEDULERS,
    TransferOp,
    plan_memory_dense_features,
)
from repro.core.pipeline import LANE_COMPUTE, LANE_DMA, LANE_GDS, AllocOp
from repro.io import TieredSegmentCache
from repro.io.tiers import (
    MemoryTier,
    PAPER_GPU_SYSTEM,
    Path,
)
from repro.sparse.formats import csr_fingerprint
from repro.sparse.ref_spgemm import spgemm_csr_dense

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_pipeline.json")
METRIC_FIELDS = [
    "makespan_s", "io_modeled_s", "compute_modeled_s", "host_preprocess_s",
    "bytes_by_path", "seconds_by_path", "total_transfer_bytes",
    "cache_hit_bytes", "merge_events", "merge_io_s", "segments", "oom",
]


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fig6_setup():
    from benchmarks.common import SCALE, budget_for, dataset, feature_spec

    if SCALE != 1e-3:
        pytest.skip("golden metrics were frozen at SCALE=1e-3 "
                    "(AIRES_BENCH_SCALE overrides the benchmark scale)")
    out = {}
    for name in ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a"]:
        a = dataset(name)
        feat = feature_spec(a)
        out[name] = (a, feat, budget_for(name, a, feat))
    return out


@pytest.fixture(scope="module")
def small_graph():
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    a = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["socLJ1"], 1e-4), seed=0))
    a.validate()
    return a


# ---- acceptance: cost interpreter == pre-refactor simulate ----------------

@pytest.mark.parametrize("sched", ["maxmemory", "ucg", "etc", "aires"])
@pytest.mark.parametrize("name", ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a"])
def test_cost_interpreter_matches_prerefactor_fig6(golden, fig6_setup,
                                                   assert_metrics_match,
                                                   name, sched):
    a, feat, budget = fig6_setup[name]
    res = SCHEDULERS[sched](PAPER_GPU_SYSTEM, device_budget=budget).run(
        a, feat, mode="simulate", dataset=name)
    assert_metrics_match(res.metrics, golden["fig6"][f"{name}/{sched}"],
                         METRIC_FIELDS, f"{name}/{sched}")


def test_cached_simulate_matches_prerefactor(golden, fig6_setup):
    """AIRES + shared segment cache: cold epoch fills, warm epoch hits —
    both float-equal to the pre-refactor monolith."""
    from benchmarks.common import budget_for, dataset, feature_spec

    a = dataset("kV2a")
    feat = feature_spec(a, 64)
    budget = budget_for("kV2a", a, feat)
    cache = TieredSegmentCache(device_budget_bytes=budget)
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget,
                                segment_cache=cache)
    for label in ("cold", "warm"):
        m = sched.run(a, feat, dataset="kV2a").metrics
        want = golden["cached_sim"][label]
        for field in METRIC_FIELDS:
            assert getattr(m, field) == want[field], (label, field)


# ---- one plan, two interpreters -------------------------------------------

@pytest.mark.parametrize("sched", ["maxmemory", "ucg", "etc", "aires"])
def test_execute_and_cost_interpret_same_plan_same_metrics(small_graph,
                                                           sched):
    """Simulate-vs-execute agreement is true by construction: interpreting
    one plan with both interpreters yields identical metrics, and the
    execute pass adds the exact output."""
    a = small_graph
    rng = np.random.default_rng(0)
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    # Above every scheduler's Table III feasibility floor (MaxMemory/UCG
    # need ≥84 % of required_bytes), still small enough to stream.
    from repro.core import FeatureSpec, required_bytes
    budget = int(1.1 * required_bytes(a, FeatureSpec.of(h)))
    kw = dict(bm=8, bk=8) if sched == "aires" else {}
    scheduler = SCHEDULERS[sched](PAPER_GPU_SYSTEM, device_budget=budget, **kw)

    plan = scheduler.build_plan(a, h, mode="execute")
    m_cost, x_cost = CostInterpreter(PAPER_GPU_SYSTEM).run(plan)
    m_exec, x_exec = ExecuteInterpreter(PAPER_GPU_SYSTEM).run(plan)
    assert x_cost is None
    assert x_exec is not None
    for field in METRIC_FIELDS:
        assert getattr(m_cost, field) == getattr(m_exec, field), field
    ref = spgemm_csr_dense(a, h)
    np.testing.assert_allclose(x_exec, ref, atol=1e-3, rtol=1e-3)


def test_scheduler_run_is_build_plus_interpret(small_graph):
    """run() must be nothing more than build_plan() + interpreter."""
    a = small_graph
    feat = FeatureSpec(a.n_rows, 32, 4, 0.0)
    est = plan_memory_dense_features(a, a.n_rows, 32, float("inf"))
    budget = int(est.m_b + est.m_c + 0.6 * a.nbytes())
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget)
    res = sched.run(a, feat)
    plan = sched.build_plan(a, feat)
    m, _ = CostInterpreter(PAPER_GPU_SYSTEM).run(plan)
    for field in METRIC_FIELDS:
        assert getattr(res.metrics, field) == getattr(m, field), field
    assert res.pipeline is not None
    assert res.pipeline.segments == res.metrics.segments


# ---- lane/overlap semantics of the makespan --------------------------------

def _plan(phases):
    p = PipelinePlan(scheduler="test")
    p.phases = phases
    return p


def test_lanes_phase_overlaps_independent_lanes():
    """Two transfers on different lanes overlap; same lane serializes."""
    spec = PAPER_GPU_SYSTEM
    plan = _plan([PhaseSpec("p")])
    plan.add(TransferOp(Path.GDS, MemoryTier.STORAGE, MemoryTier.DEVICE,
                        1 << 20), "p", LANE_GDS)
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 20), "p", LANE_DMA)
    m, _ = CostInterpreter(spec).run(plan)
    t_gds = spec.latency_s[Path.GDS] + (1 << 20) / spec.bw[Path.GDS]
    t_dma = spec.latency_s[Path.DMA] + (1 << 20) / spec.bw[Path.DMA]
    assert m.makespan_s == max(t_gds, t_dma)
    assert m.io_modeled_s == t_gds + t_dma

    serial = _plan([PhaseSpec("p")])
    for _ in range(2):
        serial.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                              1 << 20), "p", LANE_DMA)
    m2, _ = CostInterpreter(spec).run(serial)
    assert m2.makespan_s == pytest.approx(2 * t_dma)


def test_deps_gate_compute_behind_transfer():
    """A compute op with a transfer dep starts at the transfer's completion
    — the double-buffer recurrence in miniature."""
    spec = PAPER_GPU_SYSTEM
    plan = _plan([PhaseSpec("p")])
    ios, cmps = [], []
    for _ in range(3):
        i = plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                                1 << 20), "p", LANE_DMA)
        plan.add(ComputeOp(1e-4), "p", LANE_COMPUTE, deps=(i,))
    m, _ = CostInterpreter(spec).run(plan)
    t_dma = spec.latency_s[Path.DMA] + (1 << 20) / spec.bw[Path.DMA]
    # manual recurrence: io chain on its lane, compute waits on io + itself
    pipeline = io_free = 0.0
    for _ in range(3):
        io_done = io_free + t_dma
        pipeline = max(pipeline, io_done) + 1e-4
        io_free = io_done
    assert m.makespan_s == pytest.approx(pipeline)
    assert m.compute_modeled_s == pytest.approx(3e-4)


def test_serial_phase_sums_categories():
    spec = PAPER_GPU_SYSTEM
    plan = _plan([PhaseSpec("p", overlap="serial")])
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 20), "p")
    plan.add(HostPreprocessOp(2e-3), "p")
    plan.add(ComputeOp(5e-3), "p")
    m, _ = CostInterpreter(spec).run(plan)
    t_dma = spec.latency_s[Path.DMA] + (1 << 20) / spec.bw[Path.DMA]
    assert m.makespan_s == pytest.approx(t_dma + 2e-3 + 5e-3)
    assert m.host_preprocess_s == 2e-3


def test_alloc_op_oom_aborts_interpretation():
    spec = PAPER_GPU_SYSTEM
    plan = _plan([PhaseSpec("p", overlap="serial")])
    plan.add(AllocOp(MemoryTier.DEVICE, "huge",
                     spec.device_capacity + 1), "p")
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 20), "p")
    # analyze=False: the *runtime* OOM path is under test here — the static
    # analyzer (on by default under tests) refuses this plan up front, which
    # tests/test_analysis.py asserts separately.
    m, x = CostInterpreter(spec, analyze=False).run(plan)
    assert m.oom and x is None
    assert m.bytes_by_path == {}  # nothing charged after the failed alloc


def test_oom_plan_short_circuits():
    plan = PipelinePlan(scheduler="t", oom=True)
    m, x = CostInterpreter(PAPER_GPU_SYSTEM).run(plan)
    assert m.oom and x is None


# ---- cache probes: interpret vs estimate (peek) ----------------------------

def _probe_plan(key, nbytes):
    plan = _plan([PhaseSpec("p")])
    miss = TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE, nbytes,
                      tag="phaseII/seg")
    plan.add(CacheProbeOp(key, nbytes, miss, value=True), "p", LANE_DMA)
    return plan


def test_estimate_peeks_without_mutating_cache():
    from repro.io.segment_cache import SegmentKey

    cache = TieredSegmentCache(device_budget_bytes=1 << 20)
    key = SegmentKey("g", 0, "bricks", (1,))
    plan = _probe_plan(key, 4096)

    # estimate on a cold cache: miss modeled, nothing inserted
    est = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est.cache_hit_bytes == 0
    assert len(cache) == 0 and cache.stats.misses == 0

    # real interpretation inserts; estimate then sees a device hit — still
    # without touching LRU state or stats
    CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
    assert len(cache) == 1
    stats_before = (cache.stats.device_hits, cache.stats.misses)
    est2 = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est2.cache_hit_bytes == 4096
    assert est2.bytes_by_path.get("dma", 0) == 0
    assert (cache.stats.device_hits, cache.stats.misses) == stats_before


def test_estimate_models_host_tier_promotion():
    from repro.io.segment_cache import SegmentKey

    cache = TieredSegmentCache(device_budget_bytes=1)  # everything spills
    key = SegmentKey("g", 0, "bricks", (1,))
    plan = _probe_plan(key, 4096)
    CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
    assert cache.tier_of(key) is MemoryTier.HOST
    est = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est.cache_hit_bytes == 4096
    # the modeled promotion crosses the bus but is cheaper than a miss +
    # demotion; key point: the brick stays on the host tier (no mutation)
    assert est.bytes_by_path.get("dma", 0) == 4096
    assert cache.tier_of(key) is MemoryTier.HOST


def test_estimate_prices_remote_shard_hits_over_ici():
    """A peeked device hit owned by a remote shard must carry the ICI hop
    the real interpreter charges — estimate and execute agree on sharded
    caches too."""
    from repro.io import ShardedSegmentCache
    from repro.io.segment_cache import SegmentKey
    from repro.io.shard_cache import shard_of

    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    # find a key owned by a remote shard (local is 0)
    key = next(SegmentKey("g", i, "bricks", (1,)) for i in range(64)
               if shard_of(SegmentKey("g", i, "bricks", (1,)), 4) != 0)
    cache.put(key, "brick", 4096)
    plan = _probe_plan(key, 4096)
    est = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est.cache_hit_bytes == 4096
    assert est.bytes_by_path.get("ici", 0) == 4096

    # and the real probe charges the same ICI bytes
    m, _ = CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
    assert m.bytes_by_path.get("ici", 0) == 4096


def test_run_releases_payloads_but_stays_estimable(small_graph):
    """Execute-mode results must not pin the densified bricks (this is an
    out-of-core library); the returned plan still cost-interprets."""
    a = small_graph
    rng = np.random.default_rng(3)
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    est_mem = plan_memory_dense_features(a, a.n_rows, 16, float("inf"))
    budget = int(est_mem.m_b + est_mem.m_c + 0.6 * a.nbytes())
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget,
                                bm=8, bk=8)
    res = sched.run(a, h, mode="execute")
    assert res.x is not None
    for bound in res.pipeline.ops:
        op = bound.op
        assert getattr(op, "payload", None) is None
        assert getattr(op, "kernel", None) is None
        assert getattr(op, "pin", None) is None
        assert not hasattr(op, "value") or op.value is True
    assert res.pipeline.reference_kernel is None
    again = res.pipeline.estimate(PAPER_GPU_SYSTEM)
    assert again.makespan_s == res.metrics.makespan_s


# ---- the engine-side plan: stream_plan + estimate --------------------------

def test_stream_plan_estimate_prices_a_pass(small_graph):
    a = small_graph
    est_mem = plan_memory_dense_features(a, a.n_rows, 32, float("inf"))
    budget = int(est_mem.m_b + est_mem.m_c + 0.6 * a.nbytes())
    # Device tier large enough to retain the whole plan: warm hits are then
    # genuinely free (an undersized tier would model promote DMA instead).
    cache = TieredSegmentCache(device_budget_bytes=64 << 20)
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8,
                                  plan_features=32),
                      segment_cache=cache)
    plan = eng.stream_plan(a, (a.n_rows, 32), spec=PAPER_GPU_SYSTEM)
    assert plan.segments >= 2
    cold = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert cold.makespan_s > 0
    assert cold.bytes_by_path.get("dma", 0) == plan.wire_bytes()

    # run the pass for real; the warm estimate must now price ~free
    eng(a, jnp.asarray(np.zeros((a.n_rows, 32), np.float32)))
    warm = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert warm.cache_hit_bytes == plan.wire_bytes()
    assert warm.makespan_s < cold.makespan_s

    # estimating never disturbed the cache: a second real pass is all hits
    eng(a, jnp.asarray(np.zeros((a.n_rows, 32), np.float32)))
    assert eng.last_stream_stats.uploaded_bytes == 0


def test_stream_payloads_follow_plan_order(small_graph):
    a = small_graph
    est_mem = plan_memory_dense_features(a, a.n_rows, 16, float("inf"))
    budget = int(est_mem.m_b + est_mem.m_c + 0.6 * a.nbytes())
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    plan = eng.stream_plan(a, (a.n_rows, 16))
    payloads = plan.stream_payloads()
    assert [i for i, _ in payloads] == list(range(plan.segments))


# ---- fingerprint namespaces (the id()-reuse bugfix) ------------------------

def test_graph_namespace_is_content_addressed(small_graph):
    """Same structure → same namespace, regardless of object identity;
    different structure → different namespace. id(a) gave neither."""
    import copy

    a = small_graph
    b = copy.deepcopy(a)
    assert a is not b
    assert csr_fingerprint(a) == csr_fingerprint(b)
    assert (AiresSpGEMM.graph_cache_prefix(a)
            == AiresSpGEMM.graph_cache_prefix(b))

    c = copy.deepcopy(a)
    c.indptr = c.indptr.copy()
    # move one nonzero between rows: same nnz/shape, different structure.
    # (The memo rides along with deepcopy — correct for immutable CSRs;
    # this test builds a *new* structure, so drop it.)
    if hasattr(c, "_fingerprint"):
        del c._fingerprint
    row = int(np.argmax(np.diff(c.indptr)))
    c.indptr[row + 1] -= 1
    assert csr_fingerprint(a) != csr_fingerprint(c)


def test_reweighted_graph_gets_its_own_namespace(small_graph):
    """Cached bricks embed edge VALUES, so a re-weighted graph with the
    identical sparsity pattern must not hit the old graph's bricks."""
    import copy

    a = small_graph
    b = copy.deepcopy(a)
    del b._fingerprint
    b.data = b.data * 2.0
    assert csr_fingerprint(a) != csr_fingerprint(b)
    assert (AiresSpGEMM.graph_cache_prefix(a)
            != AiresSpGEMM.graph_cache_prefix(b))

    est = plan_memory_dense_features(a, a.n_rows, 16, float("inf"))
    budget = int(est.m_b + est.m_c + 0.6 * a.nbytes())
    cache = TieredSegmentCache(device_budget_bytes=64 << 20)
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
                      segment_cache=cache)
    h = np.ones((a.n_rows, 16), np.float32)
    xa = np.asarray(eng(a, jnp.asarray(h)))
    assert eng.last_stream_stats.uploaded_bytes > 0
    xb = np.asarray(eng(b, jnp.asarray(h)))
    assert eng.last_stream_stats.cache_hit_bytes == 0, \
        "re-weighted graph must miss the old graph's bricks"
    np.testing.assert_allclose(xb, 2.0 * xa, rtol=1e-5, atol=1e-5)


def test_simulate_cache_hits_across_equal_content_graphs():
    """The scenario the id() bug corrupted: a graph object is GC'd, an
    equal-content graph reappears at (possibly) the same id. Content
    namespaces make the cached segments legitimately reusable."""
    import copy

    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    a1 = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["rUSA"], 2e-5), seed=1))
    a2 = copy.deepcopy(a1)
    feat = FeatureSpec(a1.n_rows, 32, 4, 0.0)
    est = plan_memory_dense_features(a1, a1.n_rows, 32, float("inf"))
    budget = int(est.m_b + est.m_c + 0.6 * a1.nbytes())
    cache = TieredSegmentCache(device_budget_bytes=budget)
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget,
                                segment_cache=cache)
    cold = sched.run(a1, feat).metrics
    warm = sched.run(a2, feat).metrics   # different object, same content
    assert cold.cache_hit_bytes == 0
    assert warm.cache_hit_bytes == cold.bytes_by_path.get("dma", 0)


# ---- the real stream moves what the stream plan prices ---------------------

def test_execute_stream_counts_match_cost_model(small_graph):
    """The stream plan's wire bytes and cache keys appear identically in
    the cost reading and the real stream — one set of segments, no drift."""
    a = small_graph
    est_mem = plan_memory_dense_features(a, a.n_rows, 16, float("inf"))
    budget = int(est_mem.m_b + est_mem.m_c + 0.6 * a.nbytes())
    cache = TieredSegmentCache(device_budget_bytes=budget)
    probed = []
    get = cache.get
    cache.get = lambda key, **kw: probed.append(key) or get(key, **kw)
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
                      segment_cache=cache)
    h = np.zeros((a.n_rows, 16), np.float32)
    plan = eng.stream_plan(a, (a.n_rows, 16), spec=PAPER_GPU_SYSTEM)
    modeled = plan.estimate(PAPER_GPU_SYSTEM)
    eng(a, jnp.asarray(h))
    real = eng.last_stream_stats
    assert real.uploaded_bytes == modeled.bytes_by_path.get("dma", 0)
    assert real.segments == plan.segments
    assert probed == [b.op.key for b in plan.ops
                      if isinstance(b.op, CacheProbeOp)]
