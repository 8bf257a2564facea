"""Plan-rewrite pass framework (ISSUE 5): identity-pipeline behavior
preservation, per-pass properties, and the sharded-placement acceptance run.

Acceptance criteria covered here:
  * with an empty/identity `PassPipeline`, simulate metrics match
    tests/data/golden_pipeline.json (floats to a relative 1e-12) and
    execute outputs + BatchReports are bit-exact with PR-4 behavior
    (cache on/off, 1- and 4-shard);
  * coalescing conserves total bytes per path; placement never increases
    `ici_bytes`; EDF-with-tardy-demotion never increases deadline misses
    (hypothesis-driven when installed, deterministic sweep otherwise);
  * a 4-shard × 2-worker warm epoch streams strictly fewer ICI bytes with
    the placement pass enabled, with bit-identical outputs.
"""
import importlib.util
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
    EDFOrderingPass,
    FeatureSpec,
    PassPipeline,
    PhaseSpec,
    PipelinePlan,
    PlanValidationError,
    SCHEDULERS,
    ShardPlacementPass,
    TransferCoalescingPass,
    TransferOp,
    deadline_order,
    edf_sort,
    plan_memory_dense_features,
)
from repro.core.analysis import diff_path_totals, path_byte_totals
from repro.core.pipeline import LANE_COMPUTE, LANE_DMA
from repro.io import (
    CacheDirectory,
    ICI_RING,
    ICI_ALL_TO_ALL,
    ShardedSegmentCache,
    TieredSegmentCache,
)
from repro.io.segment_cache import SegmentKey
from repro.io.shard_cache import shard_of
from repro.io.tiers import MemoryTier, PAPER_GPU_SYSTEM, Path
from repro.runtime import EngineConfig, InferenceRequest, ServingEngine
from repro.sparse.ref_spgemm import spgemm_csr_dense

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None
GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_pipeline.json")
METRIC_FIELDS = [
    "makespan_s", "io_modeled_s", "compute_modeled_s", "host_preprocess_s",
    "bytes_by_path", "seconds_by_path", "total_transfer_bytes",
    "cache_hit_bytes", "merge_events", "merge_io_s", "segments", "oom",
]


@pytest.fixture(scope="module")
def small_graph():
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    a = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["socLJ1"], 1e-4), seed=0))
    a.validate()
    return a


def _budget(a, width=64, a_frac=0.6):
    est = plan_memory_dense_features(a, a.n_rows, width, float("inf"))
    return int(est.m_b + est.m_c + a_frac * a.nbytes())


# ---- satellite bugfix: plan validation -------------------------------------


def _tiny_plan():
    p = PipelinePlan(scheduler="t")
    p.phases = [PhaseSpec("p")]
    return p


def test_validate_rejects_dangling_dep():
    p = _tiny_plan()
    p.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE, 8),
          "p", LANE_DMA, deps=(3,))
    with pytest.raises(PlanValidationError, match="dangling"):
        p.validate()
    q = _tiny_plan()
    q.add(ComputeOp(1e-6), "p", LANE_COMPUTE, deps=(-1,))
    with pytest.raises(PlanValidationError, match="dangling"):
        q.validate()


def test_validate_rejects_cycles_and_forward_refs():
    p = _tiny_plan()
    i0 = p.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE, 8),
               "p", LANE_DMA, deps=(1,))   # forward edge of a 2-cycle
    p.add(ComputeOp(1e-6), "p", LANE_COMPUTE, deps=(i0,))
    with pytest.raises(PlanValidationError, match="topological"):
        p.validate()
    q = _tiny_plan()
    q.add(ComputeOp(1e-6), "p", LANE_COMPUTE, deps=(0,))  # self-cycle
    with pytest.raises(PlanValidationError, match="cycle"):
        q.validate()


def test_validate_rejects_op_bearing_plan_without_phases():
    """The `if declared and ...` loophole is closed: an op-bearing plan
    with an empty phase list used to pass validation, and every op then
    sat in an undeclared phase whose span never entered the makespan."""
    p = PipelinePlan(scheduler="t")
    p.add(ComputeOp(1e-6), "p", LANE_COMPUTE)
    with pytest.raises(PlanValidationError, match="declares no phases"):
        p.validate()
    # Empty plans stay valid — builders return one (oom=True) for
    # infeasible budgets before declaring any phase.
    PipelinePlan(scheduler="t").validate()
    PipelinePlan(scheduler="t", oom=True).validate()


def test_validate_rejects_undeclared_and_duplicate_phases():
    p = _tiny_plan()
    p.add(ComputeOp(1e-6), "nope", LANE_COMPUTE)
    with pytest.raises(PlanValidationError, match="undeclared"):
        p.validate()
    q = PipelinePlan(scheduler="t")
    q.phases = [PhaseSpec("p"), PhaseSpec("p")]
    with pytest.raises(PlanValidationError, match="duplicate"):
        q.validate()


def test_interpreter_refuses_malformed_plan():
    """The silent mis-order is gone: interpreting a plan with a dangling
    dep raises instead of reading completion time 0.0."""
    p = _tiny_plan()
    p.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE, 8),
          "p", LANE_DMA, deps=(7,))
    with pytest.raises(PlanValidationError):
        CostInterpreter(PAPER_GPU_SYSTEM).run(p)


def test_valid_builder_plans_pass_validation(small_graph):
    a = small_graph
    h = FeatureSpec(a.n_rows, 32, 4, 0.0)
    for name in SCHEDULERS:
        plan = SCHEDULERS[name](PAPER_GPU_SYSTEM,
                                device_budget=_budget(a)).build_plan(a, h)
        assert plan.validate() is plan


# ---- acceptance: identity pipeline is behavior-preserving ------------------


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


@pytest.mark.parametrize("sched", ["maxmemory", "ucg", "etc", "aires"])
@pytest.mark.parametrize("name", ["rUSA", "kV2a", "kU1a", "socLJ1", "kP1a"])
def test_identity_pipeline_simulate_matches_golden(golden, fig6_setup,
                                                   assert_metrics_match,
                                                   name, sched):
    """run() = build → (identity rewrite) → interpret must match the
    pre-refactor goldens on every fig6 configuration."""
    a, feat, budget = fig6_setup[name]
    res = SCHEDULERS[sched](PAPER_GPU_SYSTEM, device_budget=budget,
                            passes=PassPipeline([])).run(
        a, feat, mode="simulate", dataset=name)
    assert res.pass_reports == []
    assert_metrics_match(res.metrics, golden["fig6"][f"{name}/{sched}"],
                         METRIC_FIELDS, f"{name}/{sched}")


def test_identity_pipeline_execute_bit_exact(small_graph):
    a = small_graph
    rng = np.random.default_rng(5)
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    kw = dict(device_budget=_budget(a, width=16), bm=8, bk=8)
    x0 = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, **kw).run(
        a, h, mode="execute").x
    x1 = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, passes=PassPipeline([]),
                             **kw).run(a, h, mode="execute").x
    np.testing.assert_array_equal(x0, x1)


def _report_fields(rep):
    return {
        "uploaded_bytes": rep.uploaded_bytes,
        "cache_hit_bytes": rep.cache_hit_bytes,
        "promoted_bytes": rep.promoted_bytes,
        "segments_streamed": rep.segments_streamed,
        "aggregation_passes": rep.aggregation_passes,
        "ici_bytes": rep.ici_bytes,
        "directory_hit_bytes": rep.directory_hit_bytes,
        "duplicate_avoided_bytes": rep.duplicate_avoided_bytes,
    }


def test_identity_pipeline_engine_reports_bitexact(golden, small_graph):
    """The PR-4 golden BatchReport scenarios — cache on, cache off, and
    4-shard × 2 workers — reproduce bit-exactly with an (empty) engine
    PassPipeline configured."""
    a = small_graph
    rng = np.random.default_rng(1)
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    budget = _budget(a)
    engine_golden = golden["engine"]

    for label, kw, nworkers in [("cache_on", {}, 1),
                                ("cache_off", {"cache_enabled": False}, 1),
                                ("shard4", {"cache_shards": 4}, 2)]:
        directory = CacheDirectory() if nworkers > 1 else None
        workers = [
            ServingEngine(EngineConfig(device_budget_bytes=budget,
                                       max_batch_features=64,
                                       worker_id=wid, plan_passes=(), **kw),
                          directory=directory)
            for wid in range(nworkers)
        ]
        for eng in workers:
            eng.register_graph("lj", a)
        reports = []
        for _epoch in range(2):
            for eng in workers:
                eng.submit(InferenceRequest("lj", h, w))
                reports.append(eng.run_batch())
        for i, (got, want) in enumerate(zip(reports, engine_golden[label])):
            assert _report_fields(got) == want, (label, i)


# ---- transfer coalescing ---------------------------------------------------


def _random_plan(rng):
    """A random (valid) multi-lane, multi-phase plan of small transfers,
    computes and host ops — the coalescing property-test input."""
    plan = PipelinePlan(scheduler="prop")
    plan.phases = [PhaseSpec("a"), PhaseSpec("b", overlap="serial")]
    paths = [Path.DMA, Path.GDS, Path.STORAGE_HOST]
    lanes = [LANE_DMA, "gds", ""]
    last = None
    for _ in range(int(rng.integers(2, 40))):
        kind = rng.integers(0, 4)
        phase = "a" if rng.integers(0, 2) else "b"
        if kind < 2:
            p = paths[int(rng.integers(0, len(paths)))]
            deps = (last,) if (last is not None and rng.integers(0, 3) == 0) \
                else ()
            last = plan.add(
                TransferOp(p, MemoryTier.HOST, MemoryTier.DEVICE,
                           int(rng.integers(1, 1 << 20)),
                           merge=bool(rng.integers(0, 2))),
                phase, lanes[int(rng.integers(0, len(lanes)))], deps=deps)
        elif kind == 2:
            deps = (last,) if last is not None else ()
            last = plan.add(ComputeOp(float(rng.random()) * 1e-4),
                            phase, LANE_COMPUTE, deps=deps)
        else:
            from repro.core import HostPreprocessOp
            last = plan.add(HostPreprocessOp(1e-6), phase, "host")
    return plan


def _assert_coalescing_invariants(plan, min_bytes):
    # strict=True: the shared analyzer enforces per-path byte conservation
    # inside apply() — the same diff CI's scripts/lint_plans.py runs, so
    # this test and the lint gate cannot drift.
    pipeline = PassPipeline([TransferCoalescingPass(min_bytes=min_bytes)],
                            spec=PAPER_GPU_SYSTEM, strict=True)
    before = plan.estimate(PAPER_GPU_SYSTEM)
    out, reports = pipeline.apply(plan)
    out.validate()
    after = out.estimate(PAPER_GPU_SYSTEM)
    # bytes per path conserved exactly (analyzer diff helper: {} = no delta)
    assert diff_path_totals(path_byte_totals(plan),
                            path_byte_totals(out)) == {}
    # fewer (or equal) transfer ops, never more setup latency
    n_before = sum(isinstance(b.op, TransferOp) for b in plan.ops)
    n_after = sum(isinstance(b.op, TransferOp) for b in out.ops)
    assert n_after <= n_before
    assert after.io_modeled_s <= before.io_modeled_s + 1e-15
    assert reports and reports[0].pass_name == "transfer-coalescing"
    assert not any(f.severity == "error" for f in reports[0].findings)


def test_coalescing_conserves_bytes_per_path_property():
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(st.integers(0, 2**32 - 1), st.sampled_from([1 << 12, 1 << 20]))
        def prop(seed, min_bytes):
            _assert_coalescing_invariants(
                _random_plan(np.random.default_rng(seed)), min_bytes)

        prop()
    else:
        for seed in range(40):
            for min_bytes in (1 << 12, 1 << 20):
                _assert_coalescing_invariants(
                    _random_plan(np.random.default_rng(seed)), min_bytes)


def test_coalescing_merges_small_serial_transfers():
    """Three small same-path serial transfers become one DMA: same bytes,
    two setup latencies saved."""
    plan = PipelinePlan(scheduler="t")
    plan.phases = [PhaseSpec("p", overlap="serial")]
    for _ in range(3):
        plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                            1 << 10), "p")
    out, _ = PassPipeline([TransferCoalescingPass(min_bytes=1 << 12)]).apply(
        plan)
    assert len(out.ops) == 1
    assert out.ops[0].op.nbytes == 3 * (1 << 10)
    spec = PAPER_GPU_SYSTEM
    m, _ = CostInterpreter(spec).run(out)
    assert m.makespan_s == pytest.approx(
        spec.latency_s[Path.DMA] + 3 * (1 << 10) / spec.bw[Path.DMA])


def test_coalescing_respects_threshold_and_lane_order():
    plan = PipelinePlan(scheduler="t")
    plan.phases = [PhaseSpec("p")]
    # big op between two small ones on the same lane closes the run
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 10), "p", LANE_DMA)
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 24), "p", LANE_DMA)
    plan.add(TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                        1 << 10), "p", LANE_DMA)
    out, _ = PassPipeline([TransferCoalescingPass(min_bytes=1 << 12)]).apply(
        plan)
    assert len(out.ops) == 3, "interleaved big transfer must break the run"


def test_coalescing_remaps_compute_deps(small_graph):
    """AIRES stream phase: computes dep on their segment's transfer; after
    coalescing they dep on the merged DMA — plan still validates and
    total bytes are unchanged."""
    a = small_graph
    h = FeatureSpec(a.n_rows, 16, 4, 0.0)
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=_budget(a))
    plan = sched.build_plan(a, h)
    before = plan.estimate(PAPER_GPU_SYSTEM)
    out, _ = PassPipeline([TransferCoalescingPass(min_bytes=1 << 30)]).apply(
        plan)
    out.validate()
    after = out.estimate(PAPER_GPU_SYSTEM)
    assert after.bytes_by_path == before.bytes_by_path
    assert diff_path_totals(path_byte_totals(plan),
                            path_byte_totals(out)) == {}
    n_cmp = sum(isinstance(b.op, ComputeOp) for b in out.ops)
    assert n_cmp == plan.segments


def test_coalesced_stream_executes_bit_exact(small_graph):
    """The real streamer path: a cache-off engine with coalescing uploads
    the same bytes and produces the identical output (coalescing reprices
    the plan; the stream still uploads one segment per issue)."""
    a = small_graph
    rng = np.random.default_rng(7)
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    budget = _budget(a, width=16)

    plain = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    x0 = np.asarray(plain(a, jnp.asarray(h)))
    s0 = plain.last_stream_stats
    assert s0.segments >= 2, "need >=2 segments for coalescing to act"

    co = AiresSpGEMM(
        AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
        plan_passes=PassPipeline([TransferCoalescingPass(min_bytes=1 << 30)]))
    x1 = np.asarray(co(a, jnp.asarray(h)))
    s1 = co.last_stream_stats
    np.testing.assert_array_equal(x0, x1)
    assert s1.uploaded_bytes == s0.uploaded_bytes
    # The rewrite merged the plan's uploads; the stream did not.
    plan = co.stream_plan(a, h.shape)
    assert sum(isinstance(b.op, TransferOp) for b in plan.ops) < s0.segments
    assert s1.segments == s0.segments


# ---- shard-aware placement -------------------------------------------------


def _probe_plan(keys, nbytes):
    plan = PipelinePlan(scheduler="t")
    plan.phases = [PhaseSpec("p")]
    for k in keys:
        miss = TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                          nbytes, tag="phaseII/seg")
        plan.add(CacheProbeOp(k, nbytes, miss, value=True), "p", LANE_DMA)
    return plan


def _placement_never_increases_ici(seed):
    rng = np.random.default_rng(seed)
    n_shards = int(rng.integers(2, 6))
    nbytes = int(rng.integers(1, 4096))
    n_keys = int(rng.integers(1, 24))
    budget = int(rng.integers(n_shards, n_shards * n_keys * 4096 + 1))
    topology = ICI_RING if rng.integers(0, 2) else ICI_ALL_TO_ALL
    keys = [SegmentKey(f"g{seed}", i, "bricks", (i,)) for i in range(n_keys)]

    def warm_ici(passes):
        cache = ShardedSegmentCache(device_budget_bytes=budget,
                                    n_shards=n_shards, topology=topology)
        sched_passes = (PassPipeline([ShardPlacementPass()])
                        if passes else PassPipeline([]))
        plan = _probe_plan(keys, nbytes)
        plan, _ = sched_passes.apply(plan, segment_cache=cache)
        # cold fill then warm reread, both interpreted for real
        CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
        m, _ = CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
        return m.bytes_by_path.get("ici", 0)

    assert warm_ici(True) <= warm_ici(False)


def test_placement_never_increases_ici_property():
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(st.integers(0, 2**32 - 1))
        def prop(seed):
            _placement_never_increases_ici(seed)

        prop()
    else:
        for seed in range(60):
            _placement_never_increases_ici(seed)


def test_placement_pins_bricks_to_local_shard():
    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    keys = [SegmentKey("g", i, "bricks", (i,)) for i in range(8)]
    remote = [k for k in keys if shard_of(k, 4) != 0]
    assert remote, "CRC should scatter at least one key off shard 0"
    plan = _probe_plan(keys, 256)
    plan, _ = PassPipeline([ShardPlacementPass()]).apply(
        plan, segment_cache=cache)
    probes = [b.op for b in plan.ops if isinstance(b.op, CacheProbeOp)]
    for op in probes:
        if shard_of(op.key, 4) != 0:
            assert op.place_shard == 0, "remote key must be pinned locally"
        else:
            assert op.place_shard is None
    # interpreting the rewritten plan records the placements in the owner
    # map: warm hits are local, zero ICI
    CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
    assert all(cache.owner_of(k) == 0 for k in keys)
    m, _ = CostInterpreter(PAPER_GPU_SYSTEM, segment_cache=cache).run(plan)
    assert m.bytes_by_path.get("ici", 0) == 0
    assert m.cache_hit_bytes == 8 * 256


def test_placement_prefers_device_tiers_and_falls_back_near():
    """The tier-aware decision rules: local device first; a brick the
    owner can keep device-resident stays there (a remote device hit's ICI
    is cheaper than converting it into a local PCIe promotion); overflow
    goes to the nearest shard with device room at no more hops than the
    owner. 512 B device + 512 B host per shard, 400 B bricks, ring."""
    n = 8
    cache = ShardedSegmentCache(device_budget_bytes=n * 512,
                                host_budget_bytes=n * 512, n_shards=n,
                                topology=ICI_RING)
    assert cache.shard_headroom(0) == 512
    assert cache.shard_host_headroom(0) == 512
    # four keys sharing one far CRC owner (>= 2 hops from shard 0)
    pool = [SegmentKey("g", i, "bricks", (i,)) for i in range(512)]
    owners = {}
    for k in pool:
        owners.setdefault(shard_of(k, n), []).append(k)
    owner = next(s for s in owners
                 if cache.ici_hops(s) >= 2 and len(owners[s]) >= 4)
    plan = _probe_plan(owners[owner][:4], 400)
    plan, _ = PassPipeline([ShardPlacementPass()]).apply(
        plan, segment_cache=cache)
    placed = [b.op.place_shard for b in plan.ops
              if isinstance(b.op, CacheProbeOp)]
    assert placed[0] == 0, "first brick takes the local device headroom"
    assert placed[1] is None, \
        "the owner still has device room — keep the cheap remote-device hit"
    for p in placed[2:]:
        assert p is not None and p != 0, \
            "local and owner device tiers are full"
        assert cache.ici_hops(p) <= cache.ici_hops(owner)
    # deterministic nearest-first fill: both 1-hop neighbors of shard 0
    assert {placed[2], placed[3]} == {1, 7}


def test_placement_uses_local_host_only_under_global_device_pressure():
    """No shard's device tier can hold the brick → it will be a host-tier
    hit wherever it lands, so the pass pins it locally (promotion without
    the ICI add-on). With an unbounded host this is always capacity-safe."""
    n = 4
    cache = ShardedSegmentCache(device_budget_bytes=n * 64, n_shards=n)
    key = next(k for k in (SegmentKey("g", i, "bricks", (i,))
                           for i in range(64)) if shard_of(k, n) != 0)
    plan = _probe_plan([key], 4096)      # 4096 B >> 64 B per-shard device
    plan, _ = PassPipeline([ShardPlacementPass()]).apply(
        plan, segment_cache=cache)
    assert plan.ops[0].op.place_shard == 0


def test_placement_leaves_resident_bricks_alone():
    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    key = next(SegmentKey("g", i, "bricks", (i,)) for i in range(64)
               if shard_of(SegmentKey("g", i, "bricks", (i,)), 4) != 0)
    cache.put(key, "brick", 256)       # resident at its CRC owner
    plan = _probe_plan([key], 256)
    plan, _ = PassPipeline([ShardPlacementPass()]).apply(
        plan, segment_cache=cache)
    op = plan.ops[0].op
    assert op.place_shard is None, "warm bricks must not be migrated"


def test_placement_estimate_prices_rewritten_plan():
    """peek_cost honors the placement override: a cold estimate of the
    rewritten plan predicts no shard-place ICI for locally pinned keys."""
    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    keys = [SegmentKey("g", i, "bricks", (i,)) for i in range(8)]
    plan = _probe_plan(keys, 256)
    est_before = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est_before.bytes_by_path.get("ici", 0) > 0
    plan, _ = PassPipeline([ShardPlacementPass()]).apply(
        plan, segment_cache=cache)
    est_after = plan.estimate(PAPER_GPU_SYSTEM, segment_cache=cache)
    assert est_after.bytes_by_path.get("ici", 0) == 0
    assert len(cache) == 0, "estimating must not touch the cache"


def test_warm_epoch_ici_strictly_lower_with_placement(small_graph):
    """Scheduler-level acceptance (the fig9 --shards arm in miniature):
    warm-epoch ici_bytes strictly lower with the pass, simulate metrics
    otherwise coherent."""
    a = small_graph
    budget = _budget(a)
    feat = np.zeros((a.n_rows, 16), np.float32)

    def warm(passes):
        cache = ShardedSegmentCache(device_budget_bytes=budget, n_shards=4)
        sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget,
                                    segment_cache=cache, passes=passes)
        sched.run(a, feat)
        return sched.run(a, feat).metrics

    w0 = warm(None)
    w1 = warm(PassPipeline([ShardPlacementPass()], spec=PAPER_GPU_SYSTEM))
    assert w0.bytes_by_path.get("ici", 0) > 0, \
        "without placement, warm hits must ride ICI"
    assert (w1.bytes_by_path.get("ici", 0)
            < w0.bytes_by_path.get("ici", 0))
    assert w1.cache_hit_bytes == w0.cache_hit_bytes


# ---- the 4-shard × 2-worker engine acceptance run --------------------------


def test_sharded_two_worker_placement_acceptance(small_graph):
    """ISSUE 5 acceptance: 4 cache shards × 2 replicated workers, warm
    epoch — the placement pass strictly reduces BatchReport.ici_bytes and
    every numerical output stays bit-identical to the pass-free run."""
    rng = np.random.default_rng(11)
    a = small_graph
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    budget = _budget(a)

    def run_epochs(plan_passes):
        directory = CacheDirectory()
        workers = [
            ServingEngine(
                EngineConfig(device_budget_bytes=budget, cache_shards=4,
                             worker_id=wid, plan_passes=plan_passes),
                directory=directory)
            for wid in (0, 1)
        ]
        for eng in workers:
            eng.register_graph("lj", a)
        epochs = []
        for _ in range(2):
            for eng in workers:
                eng.submit(InferenceRequest("lj", h, w))
                epochs.append(eng.run_batch())
        return epochs

    base = run_epochs(None)
    placed = run_epochs([ShardPlacementPass()])

    # bit-identical outputs, epoch by epoch, worker by worker
    for b, p in zip(base, placed):
        np.testing.assert_array_equal(b.results[0].output,
                                      p.results[0].output)
    # warm epoch (last two reports, one per worker): strictly lower ICI
    base_warm = sum(r.ici_bytes for r in base[2:])
    placed_warm = sum(r.ici_bytes for r in placed[2:])
    assert base_warm > 0, "pass-free warm epoch must cross shards"
    assert placed_warm < base_warm
    # and nothing got re-uploaded either way
    for r in base[2:] + placed[2:]:
        assert r.uploaded_bytes == 0


# ---- EDF / deadline-aware ordering -----------------------------------------


def _misses(items, order):
    t = 0.0
    missed = 0
    for cost, dl in order:
        t += cost
        if dl is not None and t > dl:
            missed += 1
    return missed


def _max_lateness(order):
    t = 0.0
    worst = 0.0
    for cost, dl in order:
        t += cost
        if dl is not None:
            worst = max(worst, t - dl)
    return worst


def _check_deadline_order(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 12))
    items = [(float(rng.random() * 10),
              None if rng.integers(0, 4) == 0 else float(rng.random() * 20))
             for _ in range(n)]
    cost_of = lambda it: it[0]
    deadline_of = lambda it: it[1]
    ordered = deadline_order(items, cost_of, deadline_of)
    assert sorted(map(id, ordered)) == sorted(map(id, items)), "permutation"
    # Moore–Hodgson optimality: never more misses than submission order
    assert _misses(items, ordered) <= _misses(items, items)
    # pure EDF: optimal max lateness (Jackson's rule)
    edf = edf_sort(items, deadline_of)
    assert _max_lateness(edf) <= _max_lateness(items) + 1e-12


def test_deadline_order_never_increases_misses_property():
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=100, deadline=None)
        @given(st.integers(0, 2**32 - 1))
        def prop(seed):
            _check_deadline_order(seed)

        prop()
    else:
        for seed in range(200):
            _check_deadline_order(seed)


def test_deadline_order_demotes_tardy_job():
    """The Moore–Hodgson move pure EDF misses: dropping the long job saves
    the two short ones (EDF alone would miss two deadlines here)."""
    items = [("long", 10.0, 10.0), ("s1", 2.0, 11.0), ("s2", 2.0, 13.0)]
    ordered = deadline_order(items, lambda it: it[1], lambda it: it[2])
    assert [it[0] for it in ordered] == ["s1", "s2", "long"]
    assert _misses(None, [(c, d) for _, c, d in ordered]) == 1
    # pure EDF keeps the long job first and misses both short deadlines
    edf = edf_sort(items, lambda it: it[2])
    assert [it[0] for it in edf] == ["long", "s1", "s2"]


def test_deadline_free_requests_keep_fifo_order():
    items = [(i, None) for i in range(5)]
    ordered = deadline_order(items, lambda it: 1.0, lambda it: it[1])
    assert ordered == items


def test_engine_edf_orders_earliest_deadline_first(small_graph):
    """Two graphs, the later-registered one holding the earlier deadline:
    with the EDF pass its group completes first (smaller actual_s); the
    outputs match the dense reference either way."""
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    rng = np.random.default_rng(3)
    g1 = small_graph
    g2 = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["rUSA"], 2e-5), seed=1))
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=max(_budget(g1), _budget(g2)),
        plan_passes=[EDFOrderingPass()]))
    eng.register_graph("first", g1)
    eng.register_graph("second", g2)
    h1 = rng.standard_normal((g1.n_rows, 16)).astype(np.float32)
    h2 = rng.standard_normal((g2.n_rows, 16)).astype(np.float32)
    rid_late = eng.submit(InferenceRequest("first", h1, deadline_s=120.0))
    rid_urgent = eng.submit(InferenceRequest("second", h2, deadline_s=30.0))
    rep = eng.run_batch()
    lat = {l.request_id: l for l in rep.request_latency}
    assert lat[int(rid_urgent)].actual_s < lat[int(rid_late)].actual_s, \
        "the earlier deadline must be served first"
    outs = {r.request_id: r.output for r in rep.results}
    np.testing.assert_allclose(outs[int(rid_late)],
                               spgemm_csr_dense(g1, h1), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(outs[int(rid_urgent)],
                               spgemm_csr_dense(g2, h2), atol=1e-3, rtol=1e-3)


def test_edf_compares_deadlines_on_one_clock(small_graph):
    """Relative deadlines are converted to remaining-time at ordering:
    a request submitted earlier with a nominally larger deadline_s can
    have LESS time remaining than a fresh request — it must run first
    (ordering by the raw relative field would invert them)."""
    import time as _time

    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    rng = np.random.default_rng(4)
    g1 = small_graph
    g2 = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["rUSA"], 2e-5), seed=1))
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=max(_budget(g1), _budget(g2)),
        plan_passes=[EDFOrderingPass()]))
    eng.register_graph("old", g1)
    eng.register_graph("fresh", g2)
    h1 = rng.standard_normal((g1.n_rows, 16)).astype(np.float32)
    h2 = rng.standard_normal((g2.n_rows, 16)).astype(np.float32)
    # submitted first, deadline_s 60.0 -> ~59.6 s remaining at batch time
    rid_old = eng.submit(InferenceRequest("old", h1, deadline_s=60.0))
    _time.sleep(0.4)
    # submitted later, deadline_s 59.9 -> ~59.9 s remaining (MORE time)
    rid_fresh = eng.submit(InferenceRequest("fresh", h2, deadline_s=59.9))
    rep = eng.run_batch()
    lat = {l.request_id: l for l in rep.request_latency}
    assert lat[int(rid_old)].actual_s < lat[int(rid_fresh)].actual_s, \
        "less time remaining must mean served first, regardless of the " \
        "raw relative deadline_s fields"


# ---- per-request latency predictions (satellite) ---------------------------


def test_submit_receipt_carries_prediction(small_graph):
    a = small_graph
    eng = ServingEngine(EngineConfig(device_budget_bytes=_budget(a),
                                     max_queue_cost_s=1e9))
    eng.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    receipt = eng.submit(InferenceRequest("g", h))
    assert isinstance(receipt, int)          # backward-compatible id
    assert receipt.estimated_cost_s > 0
    assert receipt.estimated_cost_s == pytest.approx(
        eng.estimate_request_cost(InferenceRequest("g", h)))


def test_batch_report_records_predicted_vs_actual(small_graph):
    a = small_graph
    eng = ServingEngine(EngineConfig(device_budget_bytes=_budget(a)))
    eng.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    w = [np.zeros((16, 8), np.float32)]
    rid0 = eng.submit(InferenceRequest("g", h))
    rid1 = eng.submit(InferenceRequest("g", h, w))
    rep = eng.run_batch()
    assert [l.request_id for l in rep.request_latency] == [rid0, rid1]
    for l in rep.request_latency:
        assert l.predicted_s > 0, "run_batch must fill unpriced predictions"
        assert l.actual_s >= l.processing_s > 0, \
            "batch-relative latency includes the group-relative one"
        assert l.error_s == l.processing_s - l.predicted_s, \
            "calibration error compares group-relative processing time"
    # the single-pass request is predicted cheaper than the 1-layer one?
    # both are one aggregation pass at width 16 — equal predictions.
    assert (rep.request_latency[0].predicted_s
            == pytest.approx(rep.request_latency[1].predicted_s))


# ---- multi-hop ICI topology ------------------------------------------------


def test_ici_topology_hops():
    assert ICI_ALL_TO_ALL.hops(0, 5, 8) == 1
    assert ICI_ALL_TO_ALL.hops(2, 2, 8) == 0
    assert ICI_RING.hops(0, 1, 8) == 1
    assert ICI_RING.hops(0, 4, 8) == 4
    assert ICI_RING.hops(0, 5, 8) == 3     # wraps the short way
    assert ICI_RING.hops(7, 0, 8) == 1
    with pytest.raises(ValueError):
        from repro.io import ICITopology
        ICITopology("mesh3d")


def test_ring_topology_charges_hop_scaled_ici():
    """A 3-hop remote put/get must charge 3× the bytes on the ICI path and
    3 per-hop latencies — the all-to-all flat link stays 1×."""
    from repro.io import TieredMemorySystem

    n = 8
    key = next(SegmentKey("g", i, "bricks", (i,)) for i in range(256)
               if ICI_RING.hops(shard_of(SegmentKey("g", i, "bricks", (i,)),
                                         n), 0, n) == 3)
    for topology, hops in ((ICI_ALL_TO_ALL, 1), (ICI_RING, 3)):
        tms = TieredMemorySystem(PAPER_GPU_SYSTEM)
        cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=n,
                                    tms=tms, topology=topology)
        cache.put(key, "v", 1000)
        assert tms.bytes_by_path()[Path.ICI] == 1000 * hops
        spec = PAPER_GPU_SYSTEM
        want = spec.latency_s[Path.ICI] * hops + 1000 / spec.bw[Path.ICI]
        assert tms.seconds_by_path()[Path.ICI] == pytest.approx(want)
        cache.get(key, nbytes=1000)
        assert tms.bytes_by_path()[Path.ICI] == 2 * 1000 * hops
        assert cache.stats.ici_bytes == 2 * 1000 * hops


def test_pass_reports_expose_cost_deltas(small_graph):
    """ScheduleResult.pass_reports carries one before/after reading per
    pass, and coalescing's delta is non-positive on a serial baseline."""
    a = small_graph
    h = FeatureSpec(a.n_rows, 16, 4, 0.0)
    pipeline = PassPipeline([TransferCoalescingPass(min_bytes=1 << 30),
                             ShardPlacementPass()], spec=PAPER_GPU_SYSTEM)
    res = SCHEDULERS["maxmemory"](PAPER_GPU_SYSTEM,
                                  device_budget=4 * _budget(a),
                                  passes=pipeline).run(a, h)
    assert [r.pass_name for r in res.pass_reports] == [
        "transfer-coalescing", "shard-placement"]
    assert res.pass_reports[0].makespan_delta_s <= 0
    assert res.pass_reports[0].bytes_delta("dma") == 0
    # placement is a no-op without a sharded cache
    assert res.pass_reports[1].makespan_delta_s == 0
