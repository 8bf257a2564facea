"""Static plan analyzer (ISSUE 8): clean plans stay clean, planted bugs fire
their exact rule codes, and strict pass pipelines refuse broken rewrites.

Acceptance criteria covered here:
  * all four schedulers' built plans (cache-off, tiered-cache and sharded
    variants) analyze with zero findings;
  * all three production passes analyze clean under
    `PassPipeline(strict=True)`, with (empty) findings attached to the
    `PassReport`s;
  * adversarial plans — a planted tier oversubscription, an unordered
    same-`SegmentKey` probe pair, and a byte-dropping mutation of
    `TransferCoalescingPass` — fire exactly `mem/oversubscription`,
    `race/segment-key` and `bytes/path-delta`;
  * property (hypothesis when installed): a plan whose alloc replay
    analyzes clean interprets without `OutOfMemory` at the analyzed
    capacities, and vice versa.
"""
import dataclasses
import importlib.util

import numpy as np
import pytest

from repro.core import (
    AiresConfig,
    AiresSpGEMM,
    AllocOp,
    CacheProbeOp,
    ComputeOp,
    CostInterpreter,
    EDFOrderingPass,
    FeatureSpec,
    PassPipeline,
    PhaseSpec,
    PipelinePlan,
    PlanAnalysisError,
    RULES,
    SCHEDULERS,
    ShardPlacementPass,
    TransferCoalescingPass,
    TransferOp,
    analyze_plan,
    diff_path_totals,
    path_byte_totals,
    plan_memory_dense_features,
)
from repro.core.pipeline import (
    HostPreprocessOp, LANE_COMPUTE, LANE_DMA, LANE_GDS,
)
from repro.io import ShardedSegmentCache, TieredSegmentCache
from repro.io.segment_cache import SegmentKey
from repro.io.tiers import MemoryTier, PAPER_GPU_SYSTEM, Path

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None
SPEC = PAPER_GPU_SYSTEM


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


def _plan(*phases):
    p = PipelinePlan(scheduler="t")
    p.phases = [ph if isinstance(ph, PhaseSpec) else PhaseSpec(ph)
                for ph in phases]
    return p


def _transfer(nbytes=1 << 10, path=Path.DMA, src=MemoryTier.HOST,
              dst=MemoryTier.DEVICE, **kw):
    return TransferOp(path, src, dst, nbytes, **kw)


def _probe(key, nbytes=1 << 10, **kw):
    return CacheProbeOp(key, nbytes, _transfer(nbytes, tag="phaseII/seg"),
                        **kw)


def _key(i=0, fp=""):
    return SegmentKey("g", i, "bricks", (i,), fingerprint=fp)


# ---- clean plans stay clean ------------------------------------------------


def test_all_scheduler_plans_analyze_clean(small_graph):
    a = small_graph
    feat = FeatureSpec(a.n_rows, 64, 4, 0.0)
    budget = _budget(a)
    for name, cls in SCHEDULERS.items():
        plan = cls(SPEC, device_budget=budget).build_plan(a, feat)
        report = analyze_plan(plan, spec=SPEC)
        assert report.findings == [], \
            f"{name}: {[str(f) for f in report.findings]}"


def test_cached_and_sharded_scheduler_plans_analyze_clean(small_graph):
    a = small_graph
    feat = FeatureSpec(a.n_rows, 64, 4, 0.0)
    budget = _budget(a)
    for cache in (TieredSegmentCache(device_budget_bytes=budget),
                  ShardedSegmentCache(device_budget_bytes=budget,
                                      n_shards=4)):
        sched = SCHEDULERS["aires"](SPEC, device_budget=budget,
                                    segment_cache=cache)
        plan = sched.build_plan(a, feat)
        report = analyze_plan(plan, spec=SPEC, segment_cache=cache)
        assert report.findings == []


def test_oom_plan_analyzes_empty():
    """Builder-declared infeasibility is not a finding: the interpreters
    never touch the op list either."""
    plan = PipelinePlan(scheduler="t", oom=True)
    assert analyze_plan(plan, spec=SPEC).findings == []


def test_production_passes_analyze_clean_strict(small_graph):
    """All three production passes under strict mode, against a sharded
    cache: no raise, and every PassReport carries empty findings."""
    a = small_graph
    feat = FeatureSpec(a.n_rows, 64, 4, 0.0)
    budget = _budget(a)
    cache = ShardedSegmentCache(device_budget_bytes=budget, n_shards=4)
    sched = SCHEDULERS["aires"](SPEC, device_budget=budget,
                                segment_cache=cache)
    plan = sched.build_plan(a, feat)
    pipeline = PassPipeline(
        [ShardPlacementPass(), TransferCoalescingPass(min_bytes=1 << 12),
         EDFOrderingPass()],
        spec=SPEC, strict=True)
    out, reports = pipeline.apply(plan, segment_cache=cache)
    out.validate()
    assert len(reports) == 3
    assert all(r.findings == () for r in reports)
    assert diff_path_totals(path_byte_totals(plan),
                            path_byte_totals(out)) == {}


def test_released_scheduler_plan_has_no_dangling_pins(small_graph):
    a = small_graph
    feat = FeatureSpec(a.n_rows, 64, 4, 0.0)
    budget = _budget(a)
    cache = TieredSegmentCache(device_budget_bytes=budget)
    res = SCHEDULERS["aires"](SPEC, device_budget=budget,
                              segment_cache=cache).run(a, feat)
    report = analyze_plan(res.pipeline, spec=SPEC, released=True)
    assert report.findings == []


# ---- planted bugs fire their exact rule codes ------------------------------


def test_oversubscription_rule_fires():
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(AllocOp(MemoryTier.DEVICE, "huge", SPEC.device_capacity + 1),
             "p")
    plan.add(_transfer(), "p")
    report = analyze_plan(plan, spec=SPEC)
    assert [f.rule for f in report.errors] == ["mem/oversubscription"]
    assert report.errors[0].ops == (0,)
    # ... and the interpreter refuses the plan up front under analyze=True.
    with pytest.raises(PlanAnalysisError):
        CostInterpreter(SPEC, analyze=True).run(plan)
    # Point-in-time: two allocs that only jointly oversubscribe flag the
    # second, and a same-name realloc *replaces* (TieredMemorySystem
    # semantics) so it stays clean.
    plan2 = _plan(PhaseSpec("p", overlap="serial"))
    half = SPEC.device_capacity // 2 + 1
    plan2.add(AllocOp(MemoryTier.DEVICE, "a", half), "p")
    i = plan2.add(AllocOp(MemoryTier.DEVICE, "b", half), "p")
    r2 = analyze_plan(plan2, spec=SPEC)
    assert [f.rule for f in r2.errors] == ["mem/oversubscription"]
    assert r2.errors[0].ops == (i,)
    plan3 = _plan(PhaseSpec("p", overlap="serial"))
    plan3.add(AllocOp(MemoryTier.DEVICE, "a", half), "p")
    plan3.add(AllocOp(MemoryTier.DEVICE, "a", half), "p")  # realloc
    plan3.add(_transfer(), "p")
    assert analyze_plan(plan3, spec=SPEC).findings == []


def test_without_spec_budget_rules_skip():
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(AllocOp(MemoryTier.DEVICE, "huge", SPEC.device_capacity + 1),
             "p")
    plan.add(_transfer(), "p")
    assert analyze_plan(plan).findings == []


def test_race_unordered_same_segment_key():
    key = _key()
    # Different lanes, no deps: unordered — the race fires.
    plan = _plan("p")
    i = plan.add(_probe(key), "p", LANE_DMA)
    j = plan.add(_probe(key), "p", LANE_GDS)
    report = analyze_plan(plan)
    assert [f.rule for f in report.errors] == ["race/segment-key"]
    assert report.errors[0].ops == (i, j)
    # Same lane: lane serialization orders them — clean.
    ordered = _plan("p")
    ordered.add(_probe(key), "p", LANE_DMA)
    ordered.add(_probe(key), "p", LANE_DMA)
    assert analyze_plan(ordered).by_rule("race/segment-key") == []
    # Cross-lane with an explicit dep — clean.
    dep = _plan("p")
    i = dep.add(_probe(key), "p", LANE_DMA)
    dep.add(_probe(key), "p", LANE_GDS, deps=(i,))
    assert analyze_plan(dep).by_rule("race/segment-key") == []
    # Different phases: declared phase order is a barrier — clean.
    phased = _plan("p", "q")
    phased.add(_probe(key), "p", LANE_DMA)
    phased.add(_probe(key), "q", LANE_GDS)
    assert analyze_plan(phased).by_rule("race/segment-key") == []
    # A serial phase is a total order — clean.
    serial = _plan(PhaseSpec("p", overlap="serial"))
    serial.add(_probe(key), "p")
    serial.add(_probe(key), "p")
    assert analyze_plan(serial).by_rule("race/segment-key") == []


def test_race_unordered_alloc_slot():
    plan = _plan("p")
    plan.add(AllocOp(MemoryTier.DEVICE, "H", 64), "p", LANE_DMA)
    plan.add(AllocOp(MemoryTier.DEVICE, "H", 32), "p", LANE_GDS)
    report = analyze_plan(plan)
    assert [f.rule for f in report.errors] == ["race/alloc-name"]
    # Distinct names on unordered lanes are distinct resources — clean.
    ok = _plan("p")
    ok.add(AllocOp(MemoryTier.DEVICE, "H", 64), "p", LANE_DMA)
    ok.add(AllocOp(MemoryTier.DEVICE, "C", 32), "p", LANE_GDS)
    assert analyze_plan(ok).by_rule("race/alloc-name") == []


def test_race_pin_and_unconsumed_payload_warn():
    key_a, key_b = _key(0), _key(1)
    plan = _plan("p")
    plan.add(_probe(key_a, pin=object()), "p", LANE_DMA)
    plan.add(_probe(key_b, pin=object()), "p", LANE_GDS)
    report = analyze_plan(plan)
    assert [f.rule for f in report.warnings] == ["race/pin"]
    assert report.ok  # warnings never fail interpretation

    stream = _plan("stream")
    stream.add(_probe(key_a, payload=(0, "ell")), "stream", LANE_DMA)
    report = analyze_plan(stream)
    assert [f.rule for f in report.warnings] == ["race/unconsumed-payload"]
    consumed = _plan("stream")
    i = consumed.add(_probe(key_a, payload=(0, "ell")), "stream", LANE_DMA)
    consumed.add(ComputeOp(1e-6), "stream", LANE_COMPUTE, deps=(i,))
    assert analyze_plan(consumed).findings == []


def test_byte_dropping_rewrite_raises_under_strict():
    class ByteDroppingPass(TransferCoalescingPass):
        """Adversarial mutation: coalesce, then halve the merged bytes."""

        name = "byte-dropper"

        def __call__(self, plan, ctx=None):
            plan = super().__call__(plan, ctx)
            for bound in plan.ops:
                if isinstance(bound.op, TransferOp):
                    bound.op.nbytes //= 2
            return plan

    def build():
        plan = _plan(PhaseSpec("p", overlap="serial"))
        for _ in range(3):
            plan.add(_transfer(1 << 10), "p")
        return plan

    with pytest.raises(PlanAnalysisError) as err:
        PassPipeline([ByteDroppingPass(min_bytes=1 << 12)],
                     strict=True).apply(build())
    assert "bytes/path-delta" in str(err.value)
    # The same rewrite sails through a non-strict pipeline — strict is
    # exactly what stands between a buggy pass and wrong output.
    out, _ = PassPipeline([ByteDroppingPass(min_bytes=1 << 12)]).apply(
        build())
    assert path_byte_totals(out) == {"dma": (3 << 10) // 2}
    # An opted-out pass (conserves_bytes=False) may change bytes.
    class ReroutingPass(ByteDroppingPass):
        conserves_bytes = False

    out, reports = PassPipeline([ReroutingPass(min_bytes=1 << 12)],
                                strict=True).apply(build())
    assert reports[-1].findings == ()


def test_strict_pipeline_attaches_findings_to_reports():
    """Warning-severity findings ride the PassReport without raising."""
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(_transfer(0, tag="empty"), "p")
    plan.add(_transfer(1 << 20), "p")
    out, reports = PassPipeline(
        [TransferCoalescingPass(min_bytes=1 << 10)], spec=SPEC,
        strict=True).apply(plan)
    assert len(reports) == 1
    assert [f.rule for f in reports[0].findings] == \
        ["lint/zero-byte-transfer"]
    assert reports[0].before is not None  # cost tracking still on


# ---- semantic lints --------------------------------------------------------


def test_lint_negative_and_zero_bytes():
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(_transfer(-4, tag="neg"), "p")
    plan.add(_transfer(0, tag="zero"), "p")
    report = analyze_plan(plan)
    assert [f.rule for f in report.errors] == ["lint/negative-bytes"]
    assert [f.rule for f in report.warnings] == ["lint/zero-byte-transfer"]


def test_lint_miss_dst_tier():
    plan = _plan("p")
    miss = _transfer(64, dst=MemoryTier.HOST)
    plan.add(CacheProbeOp(_key(), 64, miss), "p", LANE_DMA)
    report = analyze_plan(plan)
    assert [f.rule for f in report.errors] == ["lint/miss-dst-tier"]


def test_lint_alloc_unreferenced():
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(AllocOp(MemoryTier.HOST, "staging", 1 << 10), "p")
    plan.add(ComputeOp(1e-6), "p")  # touches DEVICE only
    report = analyze_plan(plan)
    assert [f.rule for f in report.warnings] == ["lint/alloc-unreferenced"]
    # A host preprocess op is host-tier work — the alloc is referenced.
    plan.add(HostPreprocessOp(1e-6), "p")
    assert analyze_plan(plan).findings == []


def test_lint_bad_placement():
    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    plan = _plan("p")
    plan.add(_probe(_key(), place_shard=7), "p", LANE_DMA)
    report = analyze_plan(plan, segment_cache=cache)
    assert [f.rule for f in report.errors] == ["lint/bad-placement"]
    # Without a cache, only negative shards are provably wrong.
    neg = _plan("p")
    neg.add(_probe(_key(), place_shard=-1), "p", LANE_DMA)
    assert [f.rule for f in analyze_plan(neg).errors] == \
        ["lint/bad-placement"]
    assert analyze_plan(plan).findings == []


def test_lint_duplicate_key_conflicting_fingerprints():
    plan = _plan("p")
    i = plan.add(_probe(_key(0, fp="aaaa")), "p", LANE_DMA)
    j = plan.add(_probe(_key(0, fp="bbbb")), "p", LANE_DMA)
    report = analyze_plan(plan)
    assert [f.rule for f in report.errors] == ["lint/duplicate-key-conflict"]
    assert report.errors[0].ops == (i, j)
    # Same fingerprint twice is a re-probe, not a conflict.
    ok = _plan("p")
    ok.add(_probe(_key(0, fp="aaaa")), "p", LANE_DMA)
    ok.add(_probe(_key(0, fp="aaaa")), "p", LANE_DMA)
    assert analyze_plan(ok).by_rule("lint/duplicate-key-conflict") == []


def test_lint_shard_imbalance():
    cache = ShardedSegmentCache(device_budget_bytes=1 << 20, n_shards=4)
    # 32 probes (the 8-per-shard floor) all owned by shard 0: its bytes
    # are 4x the per-shard mean — a property of the owner map, not size.
    plan = _plan("p")
    for i in range(32):
        plan.add(_probe(_key(i), place_shard=0), "p", LANE_DMA)
    report = analyze_plan(plan, segment_cache=cache)
    assert [f.rule for f in report.warnings] == ["lint/shard-imbalance"]
    # Evenly spread owners stay clean at the same probe count...
    even = _plan("p")
    for i in range(32):
        even.add(_probe(_key(i), place_shard=i % 4), "p", LANE_DMA)
    assert analyze_plan(even, segment_cache=cache).findings == []
    # ...and below the probe-count gate the same skew is granularity, not
    # an owner-map bug (one big segment trips 2x by pigeonhole).
    small = _plan("p")
    for i in range(31):
        small.add(_probe(_key(i), place_shard=0), "p", LANE_DMA)
    assert analyze_plan(small, segment_cache=cache).findings == []


def test_lint_dangling_pin_after_release():
    plan = _plan("p")
    i = plan.add(_probe(_key(), pin=object(), payload=(0, "ell")), "p",
                 LANE_DMA)
    plan.add(ComputeOp(1e-6), "p", LANE_COMPUTE, deps=(i,))
    # Pre-release, pins are expected: the released contract is opt-in.
    assert analyze_plan(plan).findings == []
    assert analyze_plan(plan, released=True).by_rule("lint/dangling-pin")
    plan.release_payloads()
    assert analyze_plan(plan, released=True).findings == []


def test_every_finding_rule_is_cataloged():
    """Rule codes are stable API: every code the analyzer can emit is in
    RULES, so the README table and CI lint output can't drift."""
    emitted = {
        "mem/oversubscription", "race/segment-key", "race/alloc-name",
        "race/pin", "race/unconsumed-payload", "bytes/path-delta",
        "lint/negative-bytes", "lint/zero-byte-transfer",
        "lint/miss-dst-tier", "lint/alloc-unreferenced",
        "lint/bad-placement", "lint/dangling-pin",
        "lint/duplicate-key-conflict", "lint/shard-imbalance",
    }
    assert emitted == set(RULES)


# ---- interpreters under analyze=True ---------------------------------------


def test_interpreter_analyze_default_on_under_tests():
    """tests/conftest.py flips the module default on: a broken plan dies
    in analysis, not at the runtime alloc."""
    plan = _plan(PhaseSpec("p", overlap="serial"))
    plan.add(AllocOp(MemoryTier.DEVICE, "huge", SPEC.device_capacity + 1),
             "p")
    with pytest.raises(PlanAnalysisError):
        CostInterpreter(SPEC).run(plan)
    m, _ = CostInterpreter(SPEC, analyze=False).run(plan)
    assert m.oom
    # estimate() never analyzes: admission control prices plans constantly.
    assert plan.estimate(SPEC).oom


def test_spgemm_stream_plan_analyzes_clean(small_graph):
    a = small_graph
    budget = _budget(a, width=8)
    cache = TieredSegmentCache(device_budget_bytes=budget)
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
                      segment_cache=cache)
    plan = eng.stream_plan(a, (a.n_rows, 8), spec=SPEC)
    assert analyze_plan(plan, spec=SPEC, segment_cache=cache).findings == []


# ---- property: clean alloc replay <=> no runtime OutOfMemory ---------------


def _random_alloc_plan(rng, spec):
    plan = _plan(PhaseSpec("p", overlap="serial"))
    names = ["H", "C", "A", "S"]
    tiers = [MemoryTier.DEVICE, MemoryTier.HOST]
    caps = {MemoryTier.DEVICE: spec.device_capacity,
            MemoryTier.HOST: spec.host_capacity}
    for _ in range(int(rng.integers(1, 12))):
        tier = tiers[int(rng.integers(0, len(tiers)))]
        plan.add(AllocOp(tier, names[int(rng.integers(0, len(names)))],
                         int(rng.integers(0, caps[tier] // 2 + 2))), "p")
    plan.add(_transfer(1 << 10), "p")
    return plan


def _assert_liveness_matches_interpreter(seed):
    spec = dataclasses.replace(SPEC, device_capacity=1 << 12,
                               host_capacity=1 << 13)
    plan = _random_alloc_plan(np.random.default_rng(seed), spec)
    clean = not analyze_plan(plan, spec=spec).by_rule("mem/oversubscription")
    m, _ = CostInterpreter(spec, analyze=False).run(plan)
    assert clean == (not m.oom)


def test_clean_liveness_implies_no_runtime_oom_property():
    if HAVE_HYPOTHESIS:
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=60, deadline=None)
        @given(st.integers(0, 2**32 - 1))
        def prop(seed):
            _assert_liveness_matches_interpreter(seed)

        prop()
    else:
        for seed in range(80):
            _assert_liveness_matches_interpreter(seed)
