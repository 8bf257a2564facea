"""Serving-engine behaviour: multi-graph batching, epoch-over-epoch cache
reuse, the cache-off ablation, and the simulate↔execute byte cross-check.

The headline assertions mirror ISSUE 2's acceptance criteria:
  * batched multi-graph inference is exact vs the dense reference chain;
  * on the quickstart graph, epoch 2 uploads ≤ 50 % of epoch 1's wire bytes
    with the cache on (in fact: zero), and strictly fewer bytes generally;
  * cache_enabled=False reproduces the PR-1 AiresSpGEMM behavior exactly —
    same outputs, same uploaded_bytes, no epoch-2 improvement;
  * AiresScheduler(mode="simulate") Phase II DMA in `bytes_by_path` agrees
    with AiresSpGEMM execute-mode `uploaded_bytes` once both plan with the
    same per-segment budget — the model is locked to reality.
"""
import json
import os
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (
    AiresConfig, AiresSpGEMM, SCHEDULERS, plan_memory_dense_features,
)
from repro.io import CacheDirectory, ShardedSegmentCache, TieredSegmentCache
from repro.io.tiers import PAPER_GPU_SYSTEM
from repro.runtime import (
    AdmissionError, EngineConfig, InferenceRequest, ServingEngine,
)
from repro.sparse.ref_spgemm import spgemm_csr_dense


@pytest.fixture(scope="module")
def quickstart_graph():
    """The examples/quickstart.py graph (socLJ1 scaled for CPU)."""
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    a = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["socLJ1"], 1e-4), seed=0))
    a.validate()
    return a


@pytest.fixture(scope="module")
def road_graph():
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    return normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["rUSA"], 2e-5), seed=1))


def _budget(a, width=64, a_frac=0.6):
    """Feasible for the serving engine's pinned plan width, but small enough
    to force ≥2 streamed segments."""
    est = plan_memory_dense_features(a, a.n_rows, width, float("inf"))
    return int(est.m_b + est.m_c + a_frac * a.nbytes())


def _engine(a, **overrides):
    kw = dict(device_budget_bytes=_budget(a), max_batch_features=64)
    kw.update(overrides)
    return ServingEngine(EngineConfig(**kw))


def _reference_chain(a, h, weights):
    h = np.asarray(h, dtype=np.float32)
    if not weights:
        return spgemm_csr_dense(a, h)
    for layer, w in enumerate(weights):
        x = spgemm_csr_dense(a, h)
        h = x @ np.asarray(w, dtype=np.float32)
        if layer < len(weights) - 1:
            h = np.maximum(h, 0.0)
    return h


# ---- multi-graph batching correctness ------------------------------------

def test_multi_graph_batch_matches_dense_reference(quickstart_graph,
                                                   road_graph):
    rng = np.random.default_rng(0)
    g1, g2 = quickstart_graph, road_graph
    eng = _engine(g1, device_budget_bytes=max(_budget(g1), _budget(g2)))
    eng.register_graph("lj", g1)
    eng.register_graph("road", g2)

    cases = [
        ("lj", rng.standard_normal((g1.n_rows, 16)).astype(np.float32),
         [rng.standard_normal((16, 8)).astype(np.float32),
          rng.standard_normal((8, 4)).astype(np.float32)]),
        ("lj", rng.standard_normal((g1.n_rows, 24)).astype(np.float32), []),
        ("road", rng.standard_normal((g2.n_rows, 32)).astype(np.float32),
         [rng.standard_normal((32, 8)).astype(np.float32)]),
    ]
    rids = [eng.submit(InferenceRequest(g, h, ws)) for g, h, ws in cases]
    report = eng.run_batch()
    assert len(report.results) == len(cases)
    # the two same-width-round "lj" requests share one streamed pass
    assert report.aggregation_passes < sum(max(len(ws), 1)
                                           for _, _, ws in cases)
    outs = {r.request_id: r.output for r in report.results}
    graphs = {"lj": g1, "road": g2}
    for rid, (gname, h, ws) in zip(rids, cases):
        np.testing.assert_allclose(
            outs[rid], _reference_chain(graphs[gname], h, ws),
            atol=1e-3, rtol=1e-3)


def test_submit_validates_graph_and_shape(quickstart_graph):
    eng = _engine(quickstart_graph)
    eng.register_graph("g", quickstart_graph)
    with pytest.raises(KeyError):
        eng.submit(InferenceRequest("nope", np.zeros((4, 4), np.float32)))
    with pytest.raises(ValueError):
        eng.submit(InferenceRequest("g", np.zeros((3, 4), np.float32)))
    with pytest.raises(ValueError):
        eng.register_graph("g", quickstart_graph)


def test_infer_does_not_drain_other_queued_requests(quickstart_graph):
    rng = np.random.default_rng(7)
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    h_queued = rng.standard_normal((a.n_rows, 8)).astype(np.float32)
    rid = eng.submit(InferenceRequest("g", h_queued))
    h_now = rng.standard_normal((a.n_rows, 8)).astype(np.float32)
    out_now = eng.infer("g", h_now)
    np.testing.assert_allclose(out_now, _reference_chain(a, h_now, []),
                               atol=1e-4)
    # the queued request survived infer() and still runs
    report = eng.run_batch()
    assert [r.request_id for r in report.results] == [rid]
    np.testing.assert_allclose(report.results[0].output,
                               _reference_chain(a, h_queued, []), atol=1e-4)


def test_evict_graph_returns_orphans_and_drops_cache(quickstart_graph):
    rng = np.random.default_rng(8)
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    eng.infer("g", rng.standard_normal((a.n_rows, 8)).astype(np.float32))
    assert len(eng.cache) > 0
    rid = eng.submit(InferenceRequest(
        "g", rng.standard_normal((a.n_rows, 8)).astype(np.float32)))
    orphans = eng.evict_graph("g")
    assert [r.request_id for r in orphans] == [rid]
    assert len(eng.cache) == 0, "eviction must drop every cached namespace"
    assert eng.run_batch().results == []  # queue is clean, nothing dropped


def test_promoted_bytes_surface_in_stream_stats(quickstart_graph):
    """A warm epoch served by host-tier promotions must not read as free:
    StreamStats.promoted_bytes carries the re-crossing bytes."""
    rng = np.random.default_rng(9)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    budget = _budget(a, width=16)
    tiny = TieredSegmentCache(device_budget_bytes=1)  # everything spills
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
                      segment_cache=tiny)
    eng(a, jnp.asarray(h))
    cold = eng.last_stream_stats
    eng(a, jnp.asarray(h))
    warm = eng.last_stream_stats
    assert cold.promoted_bytes == 0
    assert warm.uploaded_bytes == 0
    assert warm.promoted_bytes == warm.cache_hit_bytes == cold.uploaded_bytes


# ---- the acceptance criterion: epoch 2 uploads ≤ 50 % --------------------

def test_second_epoch_uploads_drop_on_quickstart_graph(quickstart_graph):
    rng = np.random.default_rng(1)
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("lj", a)
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]

    reports = []
    for _ in range(2):
        eng.submit(InferenceRequest("lj", h, w))
        reports.append(eng.run_batch())
    first, second = reports
    assert first.uploaded_bytes > 0
    assert second.uploaded_bytes < first.uploaded_bytes
    assert second.uploaded_bytes <= first.uploaded_bytes // 2, (
        "epoch 2 must upload at most half of epoch 1's wire bytes")
    assert second.cache_hit_bytes == first.uploaded_bytes
    # same answer both times
    np.testing.assert_allclose(first.results[0].output,
                               second.results[0].output, atol=1e-6)


def test_epoch2_exact_under_cache_demotion_pressure(quickstart_graph):
    """A device tier too small for the whole plan forces demote/promote
    round-trips mid-stream; outputs must stay exact."""
    rng = np.random.default_rng(2)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)

    probe = _engine(a)
    probe.register_graph("lj", a)
    ref = probe.infer("lj", h)
    wire_total = (probe.cache_stats().hit_bytes
                  + probe.cache_stats().miss_bytes)

    eng = _engine(a, cache_device_bytes=max(1, wire_total // 3))
    eng.register_graph("lj", a)
    out1 = eng.infer("lj", h)
    out2 = eng.infer("lj", h)
    np.testing.assert_allclose(out1, ref, atol=1e-6)
    np.testing.assert_allclose(out2, ref, atol=1e-6)
    stats = eng.cache_stats()
    assert stats.demoted_bytes > 0, "pressure test must actually demote"
    assert stats.host_hits > 0, "epoch 2 should be served by promotions"


@pytest.mark.parametrize("shards", [1, 2])
def test_half_pass_tier_demotes_bricks_without_copies(quickstart_graph,
                                                      shards, tmp_path):
    """A device tier of about half a pass demotes every pass's bricks, and
    promotes them back the next, without a device->host copy: the outputs
    are a cache-off engine's bit for bit, and so are a warm-started
    successor's from the demoted bricks' checkpoint."""
    rng = np.random.default_rng(31)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    ref = _engine(a, cache_enabled=False)
    ref.register_graph("lj", a)
    want = ref.infer("lj", h, w)
    half = dict(cache_device_bytes=max(shards, _wire_total(a, h) // 2),
                cache_shards=shards)

    eng = _engine(a, **half)
    eng.register_graph("lj", a)
    for _ in range(2):
        eng.submit(InferenceRequest("lj", h, w))
        report = eng.run_batch()
        np.testing.assert_array_equal(report.results[0].output, want)
        assert report.demoted_bytes > 0
        assert report.demote_copied_bytes == 0
    stats = eng.cache_stats()
    assert stats.host_hits > 0, "the second pass must promote"
    assert stats.demoted_bytes > 0 and stats.demote_copied_bytes == 0
    eng.checkpoint_cache(str(tmp_path))

    fresh = _engine(a, **half)
    fresh.register_graph("lj", a)
    assert fresh.warm_start(str(tmp_path)).bricks > 0
    np.testing.assert_array_equal(fresh.infer("lj", h, w), want)


# ---- cache-off ablation reproduces PR-1 ----------------------------------

def test_cache_off_reproduces_pr1_engine_exactly(quickstart_graph):
    rng = np.random.default_rng(3)
    a = quickstart_graph
    f = 32
    h = rng.standard_normal((a.n_rows, f)).astype(np.float32)
    budget = _budget(a, width=f)

    # PR-1 path: bare AiresSpGEMM, no cache, plan at the actual width.
    pr1 = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    x_pr1 = np.asarray(pr1(a, jnp.asarray(h)))
    pr1_bytes = pr1.last_stream_stats.uploaded_bytes

    # Serving engine, cache off, pinned width == actual width.
    eng = ServingEngine(EngineConfig(device_budget_bytes=budget,
                                     cache_enabled=False,
                                     max_batch_features=f))
    eng.register_graph("lj", a)
    assert eng.cache is None and eng.cache_stats() is None
    reports = []
    for _ in range(2):
        eng.submit(InferenceRequest("lj", h))
        reports.append(eng.run_batch())
    np.testing.assert_array_equal(reports[0].results[0].output, x_pr1)
    for rep in reports:
        assert rep.uploaded_bytes == pr1_bytes
        assert rep.cache_hit_bytes == 0
    assert reports[1].uploaded_bytes == reports[0].uploaded_bytes, (
        "without the cache, every epoch re-streams every byte — PR-1")


# ---- simulate ↔ execute cross-check (locks the model to reality) ---------

def test_simulate_bytes_by_path_matches_execute_uploaded_bytes(
        quickstart_graph):
    """Same graph, same per-segment budget, same wire format: the modeled
    Phase II DMA bytes must equal the real streamed upload bytes."""
    rng = np.random.default_rng(4)
    a = quickstart_graph
    f = 32
    h = rng.standard_normal((a.n_rows, f)).astype(np.float32)
    budget = _budget(a, width=f)

    engine = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    engine(a, jnp.asarray(h))
    real = engine.last_stream_stats

    # Same budget on both sides: the unified Eq. 5 planner gives the
    # scheduler and the engine identical MemoryEstimates for dense features,
    # hence identical RoBW partitions — the pre-unification equal-m_a
    # scaffolding is gone.
    sched_budget = budget
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=sched_budget,
                                wire_format="bricks", bm=8, bk=8)
    res = sched.run(a, h, mode="simulate")
    assert not res.metrics.oom
    assert res.metrics.segments == real.segments
    modeled_dma = res.metrics.bytes_by_path.get("dma", 0)
    assert modeled_dma == pytest.approx(real.uploaded_bytes, rel=0.02), (
        "simulate-mode DMA bytes diverged from executed upload bytes")

    # ...and the agreement holds warm: with a shared cache large enough to
    # hold the whole plan device-side, both sides drop their epoch-2 wire
    # traffic to zero. (An undersized device tier would instead show the
    # demote/promote DMA churn in bytes_by_path — also honest, not tested
    # here.)
    cache = TieredSegmentCache(device_budget_bytes=4 * modeled_dma)
    cached_sched = SCHEDULERS["aires"](
        PAPER_GPU_SYSTEM, device_budget=sched_budget,
        wire_format="bricks", bm=8, bk=8, segment_cache=cache)
    cold = cached_sched.run(a, h, mode="simulate").metrics
    warm = cached_sched.run(a, h, mode="simulate").metrics
    assert cold.bytes_by_path.get("dma", 0) == modeled_dma
    assert warm.bytes_by_path.get("dma", 0) == 0
    assert warm.cache_hit_bytes == modeled_dma

    cached_engine = AiresSpGEMM(
        AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
        segment_cache=TieredSegmentCache(device_budget_bytes=4 * modeled_dma))
    cached_engine(a, jnp.asarray(h))
    cached_engine(a, jnp.asarray(h))
    assert cached_engine.last_stream_stats.uploaded_bytes == 0
    assert (cached_engine.last_stream_stats.cache_hit_bytes
            == real.uploaded_bytes)


# ---- sharded serving (ISSUE 3 tentpole) ----------------------------------

def _wire_total(a, h):
    """Total wire bytes of one streamed pass at h's width (probe run)."""
    probe = _engine(a)
    probe.register_graph("lj", a)
    probe.infer("lj", h)
    return probe.cache_stats().hit_bytes + probe.cache_stats().miss_bytes


def test_sharded_two_worker_warm_epoch_acceptance(quickstart_graph):
    """The ISSUE acceptance scenario: 4 cache shards, two replicated
    workers sharing a CacheDirectory, device tier too small for the plan.
    Warm epoch: zero wire uploads, promoted/remote bytes ride ICI, and the
    directory spares at least one duplicate demotion copy."""
    rng = np.random.default_rng(11)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    ref = _reference_chain(a, h, w)
    wire_total = _wire_total(a, h)

    directory = CacheDirectory()
    workers = [
        ServingEngine(
            EngineConfig(device_budget_bytes=_budget(a),
                         cache_device_bytes=max(4, wire_total // 2),
                         cache_shards=4, worker_id=wid),
            directory=directory)
        for wid in (0, 1)
    ]
    for eng in workers:
        assert isinstance(eng.cache, ShardedSegmentCache)
        assert eng.cache.n_shards == 4
        eng.register_graph("lj", a)

    cold, warm = [], []
    for epoch_reports in (cold, warm):
        for eng in workers:
            eng.submit(InferenceRequest("lj", h, w))
            epoch_reports.append(eng.run_batch())
    for rep in cold + warm:
        np.testing.assert_allclose(rep.results[0].output, ref,
                                   atol=1e-3, rtol=1e-3)

    assert cold[0].uploaded_bytes > 0
    # Worker 1's cold epoch already benefits from worker 0's demotions: its
    # own demotions find the directory populated.
    assert sum(r.duplicate_avoided_bytes for r in cold + warm) > 0, \
        "directory must spare at least one duplicate demotion copy"
    for rep in warm:
        assert rep.uploaded_bytes == 0, \
            "warm epoch must not re-stream any wire bytes"
        assert rep.cache_hit_bytes == wire_total
        assert rep.ici_bytes > 0, \
            "remote-shard traffic must ride the ICI path"
    stats = workers[0].cache_stats()
    assert stats.remote_hits > 0 and stats.ici_bytes > 0


def test_evict_graph_unpublishes_directory_holdings(quickstart_graph):
    """Regression (ISSUE 7 satellite): `evict_graph` dropped the local
    cache but left the evicting worker's CacheDirectory records behind —
    peers could be routed a peer-promote for host copies the worker no
    longer backs. Eviction now drops exactly that worker's holdings under
    the graph prefix; a peer's own records survive."""
    from repro.core import AiresSpGEMM
    from repro.io import prefix_matches

    rng = np.random.default_rng(13)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    wire_total = _wire_total(a, h)

    directory = CacheDirectory()
    workers = [
        ServingEngine(
            EngineConfig(device_budget_bytes=_budget(a),
                         cache_device_bytes=max(4, wire_total // 2),
                         cache_shards=4, worker_id=wid),
            directory=directory)
        for wid in (0, 1)
    ]
    for eng in workers:
        eng.register_graph("lj", a)
        eng.submit(InferenceRequest("lj", h))
        eng.run_batch()

    prefix = AiresSpGEMM.graph_cache_prefix(a)
    held_by_0 = [k for k in directory._entries
                 if prefix_matches(k.graph_id, prefix)
                 and directory.holder(k) == 0]
    assert held_by_0, "demotion pressure must have published host copies"

    workers[0].evict_graph("lj")
    for key in held_by_0:
        assert directory.holder(key) is None, (
            "evicting worker's directory records must be unpublished")
    leftovers = [k for k in directory._entries
                 if prefix_matches(k.graph_id, prefix)]
    assert all(directory.holder(k) == 1 for k in leftovers), (
        "only the peer's own holdings may survive worker 0's evict")
    # Worker 1 keeps serving correctly: the bricks it deduplicated against
    # worker 0's now-gone host copies re-upload (no dangling peer-promote),
    # and the answer is still exact.
    workers[1].submit(InferenceRequest("lj", h))
    rep = workers[1].run_batch()
    assert rep.directory_hit_bytes == 0, (
        "no peer-promote may be served from the evicted worker's records")
    np.testing.assert_allclose(rep.results[0].output,
                               _reference_chain(a, h, []), atol=1e-3,
                               rtol=1e-3)


def test_one_shard_directory_off_matches_pr2_bitexactly(quickstart_graph):
    """A 1-shard ShardedSegmentCache with no directory must reproduce the
    PR-2 TieredSegmentCache BatchReport byte accounting bit-exactly —
    including under demotion pressure."""
    rng = np.random.default_rng(12)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    wire_total = _wire_total(a, h)
    pressure = max(4, wire_total // 3)

    reports = {}
    for flavor in ("tiered", "sharded1"):
        eng = _engine(a, cache_device_bytes=pressure)
        if flavor == "sharded1":
            # swap in the 1-shard sharded tier before any graph binds to it
            eng.cache = ShardedSegmentCache(
                device_budget_bytes=pressure, n_shards=1)
        eng.register_graph("lj", a)
        reps = []
        for _ in range(2):
            eng.submit(InferenceRequest("lj", h))
            reps.append(eng.run_batch())
        reports[flavor] = reps
    for pr2, one in zip(reports["tiered"], reports["sharded1"]):
        assert one.uploaded_bytes == pr2.uploaded_bytes
        assert one.cache_hit_bytes == pr2.cache_hit_bytes
        assert one.promoted_bytes == pr2.promoted_bytes
        assert one.bus_bytes == pr2.bus_bytes
        assert one.segments_streamed == pr2.segments_streamed
        assert one.aggregation_passes == pr2.aggregation_passes
        assert one.ici_bytes == 0
        assert one.directory_hit_bytes == pr2.directory_hit_bytes == 0
        assert one.duplicate_avoided_bytes == 0
        np.testing.assert_array_equal(pr2.results[0].output,
                                      one.results[0].output)


def test_engine_rejects_contradictory_sharding_config():
    budget = 1 << 20
    # cache features demanded while the cache is off -> error, not silence
    with pytest.raises(ValueError, match="cache_enabled=False"):
        ServingEngine(EngineConfig(device_budget_bytes=budget,
                                   cache_enabled=False),
                      directory=CacheDirectory())
    # two replicas on one directory must carry distinct worker ids
    directory = CacheDirectory()
    ServingEngine(EngineConfig(device_budget_bytes=budget, worker_id=0),
                  directory=directory)
    with pytest.raises(ValueError, match="worker_id"):
        ServingEngine(EngineConfig(device_budget_bytes=budget, worker_id=0),
                      directory=directory)


def test_serving_engine_over_real_mesh(quickstart_graph):
    """ServingEngine(mesh=...) builds the sharded cache from a real device
    mesh; exercised with >1 devices in the CI sharded job."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from repro.launch.mesh import make_cache_mesh

    rng = np.random.default_rng(13)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 16)).astype(np.float32)
    mesh = make_cache_mesh(4)
    eng = ServingEngine(EngineConfig(device_budget_bytes=_budget(a)),
                        mesh=mesh)
    assert isinstance(eng.cache, ShardedSegmentCache)
    assert eng.cache.devices is not None
    eng.register_graph("lj", a)
    out1 = eng.infer("lj", h)
    out2 = eng.infer("lj", h)
    ref = _reference_chain(a, h, [])
    np.testing.assert_allclose(out1, ref, atol=1e-4)
    np.testing.assert_allclose(out2, ref, atol=1e-4)
    stats = eng.cache_stats()
    assert stats.remote_hits > 0, \
        "second pass must hit bricks owned by remote chips"


# ---- execute interpreter bit-exact with the PR-3 BatchReports --------------

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


def test_batch_reports_bitexact_with_prerefactor_golden(quickstart_graph):
    """ISSUE 4 acceptance: the execute-interpreter serving path reproduces
    the pre-refactor (PR 3) BatchReport byte accounting exactly — cache on,
    cache off, and 4-shard × 2 workers, two epochs each (frozen in
    tests/data/golden_pipeline.json)."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "golden_pipeline.json")) as f:
        golden = json.load(f)["engine"]
    a = quickstart_graph
    rng = np.random.default_rng(1)
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    budget = _budget(a)

    for label, kw, nworkers in [("cache_on", {}, 1),
                                ("cache_off", {"cache_enabled": False}, 1),
                                ("shard4", {"cache_shards": 4}, 2)]:
        directory = CacheDirectory() if nworkers > 1 else None
        workers = [
            ServingEngine(EngineConfig(device_budget_bytes=budget,
                                       max_batch_features=64,
                                       worker_id=wid, **kw),
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
        for i, (got, want) in enumerate(zip(reports, golden[label])):
            assert _report_fields(got) == want, (label, i)


# ---- admission control (ISSUE 4 satellite) ---------------------------------

def test_submit_estimates_request_cost(quickstart_graph):
    a = quickstart_graph
    eng = _engine(a, max_queue_cost_s=1e9)
    eng.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    one = eng.estimate_request_cost(InferenceRequest("g", h))
    two = eng.estimate_request_cost(InferenceRequest(
        "g", h, weights=[np.zeros((16, 16), np.float32)] * 2))
    assert one > 0
    # a 2-layer request costs two streamed passes
    assert two == pytest.approx(2 * one)
    rid = eng.submit(InferenceRequest("g", h))
    assert eng._queue[0].request_id == rid
    assert eng._queue[0].estimated_cost_s == pytest.approx(one)
    assert eng.queued_cost_s() == pytest.approx(one)


def test_submit_skips_pricing_without_admission_policy(quickstart_graph):
    """No deadline and no queue cap → submit() must not pay for plan
    preparation (the pre-admission submit latency)."""
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    eng.submit(InferenceRequest("g", np.zeros((a.n_rows, 16), np.float32)))
    assert eng._queue[0].estimated_cost_s == 0.0
    assert eng._pass_costs == {}, "no estimate should have been memoized"


def test_infeasible_deadline_rejected_at_submit(quickstart_graph):
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    with pytest.raises(AdmissionError) as exc:
        eng.submit(InferenceRequest("g", h, deadline_s=1e-15))
    assert exc.value.decision.reason == "deadline-infeasible"
    assert eng.run_batch().rejected[0].reason == "deadline-infeasible"
    # a realistic deadline is admitted and served
    rid = eng.submit(InferenceRequest("g", h, deadline_s=60.0))
    rep = eng.run_batch()
    assert [r.request_id for r in rep.results] == [rid]
    assert rep.rejected == [] and rep.expired == []


def test_queue_cost_cap_rejects_overflow(quickstart_graph):
    a = quickstart_graph
    probe = _engine(a)
    probe.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    unit = probe.estimate_request_cost(InferenceRequest("g", h))

    eng = _engine(a, max_queue_cost_s=1.5 * unit)
    eng.register_graph("g", a)
    eng.submit(InferenceRequest("g", h))
    with pytest.raises(AdmissionError) as exc:
        eng.submit(InferenceRequest("g", h))
    assert exc.value.decision.reason == "queue-full"
    rep = eng.run_batch()
    assert len(rep.results) == 1
    assert [d.reason for d in rep.rejected] == ["queue-full"]
    # the drain freed the queue budget: the next submit is admitted
    eng.submit(InferenceRequest("g", h))
    assert len(eng.run_batch().results) == 1


def test_expired_requests_dropped_not_run(quickstart_graph):
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    h = np.zeros((a.n_rows, 16), np.float32)
    rid_expired = eng.submit(InferenceRequest("g", h, deadline_s=0.03))
    rid_live = eng.submit(InferenceRequest("g", h))
    time.sleep(0.08)
    rep = eng.run_batch()
    assert [r.request_id for r in rep.results] == [rid_live]
    assert [d.request_id for d in rep.expired] == [rid_expired]
    assert rep.expired[0].reason == "deadline-expired"


# ---- warm start from checkpointed bricks (ISSUE 4 satellite) ---------------

def test_warm_start_restores_cache_and_charges_tms(quickstart_graph,
                                                   tmp_path):
    a = quickstart_graph
    rng = np.random.default_rng(21)
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]

    donor = _engine(a)
    donor.register_graph("lj", a)
    donor.submit(InferenceRequest("lj", h, w))
    cold = donor.run_batch()
    assert cold.uploaded_bytes > 0
    donor.checkpoint_cache(str(tmp_path))

    # A *fresh* engine (fresh cache, fresh process in production — keys are
    # content-addressed so they survive): warm-start, then first batch.
    fresh = _engine(a)
    fresh.register_graph("lj", a)
    ws = fresh.warm_start(str(tmp_path))
    assert ws.bricks > 0
    assert ws.wire_bytes == cold.uploaded_bytes
    assert ws.modeled_seconds > 0
    # honesty: the warm-start load shows up on the engine's tms paths
    by_path = {p.value: b for p, b in fresh.tms.bytes_by_path().items()}
    assert by_path.get("sio", 0) >= ws.wire_bytes   # storage → host
    assert by_path.get("dma", 0) >= ws.wire_bytes   # host → device

    fresh.submit(InferenceRequest("lj", h, w))
    first = fresh.run_batch()
    assert first.uploaded_bytes == 0, \
        "warm-started first epoch must not re-stream wire bytes"
    assert first.cache_hit_bytes == cold.uploaded_bytes
    np.testing.assert_array_equal(first.results[0].output,
                                  cold.results[0].output)


def test_warm_start_requires_cache(quickstart_graph, tmp_path):
    eng = _engine(quickstart_graph, cache_enabled=False)
    with pytest.raises(ValueError, match="cache_enabled"):
        eng.warm_start(str(tmp_path))
    with pytest.raises(ValueError, match="cache_enabled"):
        eng.checkpoint_cache(str(tmp_path))


def test_warm_start_empty_directory_is_noop(quickstart_graph, tmp_path):
    eng = _engine(quickstart_graph)
    eng.register_graph("g", quickstart_graph)
    ws = eng.warm_start(str(tmp_path))
    assert (ws.bricks, ws.wire_bytes) == (0, 0)


def test_checkpoint_cache_coexists_with_training_checkpoints(
        quickstart_graph, tmp_path):
    """Brick checkpoints live in their own subdirectory: pointing
    checkpoint_cache at a directory holding training checkpoints must
    neither prune them nor let warm_start misread them."""
    import os

    from repro.checkpoint import Checkpointer

    a = quickstart_graph
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(100, params={"layer0": {"w": np.ones((2, 2))}},
              opt_state={"m": np.zeros(2)})

    eng = _engine(a)
    eng.register_graph("g", a)
    eng.infer("g", np.zeros((a.n_rows, 16), np.float32))
    eng.checkpoint_cache(str(tmp_path))  # default step=0 < training step

    # the training checkpoint survived the brick save's keep_last=1 prune
    assert os.path.isdir(tmp_path / "step_100")
    restored, step = ckpt.restore({"params": {"layer0": {"w": None}},
                                  "opt_state": {"m": None}})
    assert step == 100
    np.testing.assert_array_equal(restored["params"]["layer0"]["w"],
                                  np.ones((2, 2)))

    fresh = _engine(a)
    fresh.register_graph("g", a)
    assert fresh.warm_start(str(tmp_path)).bricks > 0


def test_load_segment_bricks_ignores_foreign_checkpoints(tmp_path):
    """A directory that only holds a training checkpoint yields no bricks
    (not a crash on its nested param keys)."""
    from repro.checkpoint import Checkpointer, load_segment_bricks

    Checkpointer(str(tmp_path)).save(
        3, params={"layer0": {"w": np.ones((2, 2))}}, opt_state={})
    assert load_segment_bricks(str(tmp_path)) == []


# ---- gcn_epoch passthrough -----------------------------------------------

def test_gcn_epoch_simulate_accepts_segment_cache(quickstart_graph):
    from repro.core import FeatureSpec, gcn_epoch, required_bytes

    a = quickstart_graph
    feat = FeatureSpec(a.n_rows, 64, 4, sparsity_pct=99.0)
    budget = int(0.9 * required_bytes(a, feat))
    cache = TieredSegmentCache(device_budget_bytes=budget)
    weights = [np.zeros((64, 64))] * 2
    base = gcn_epoch(a, feat, weights, "aires", PAPER_GPU_SYSTEM, budget)
    assert sum(m.cache_hit_bytes for m in base.per_layer) == 0
    # Same-width layers share a plan, so even the cold epoch's second layer
    # hits; the warm epoch hits everywhere.
    cold = gcn_epoch(a, feat, weights, "aires", PAPER_GPU_SYSTEM, budget,
                     segment_cache=cache)
    warm = gcn_epoch(a, feat, weights, "aires", PAPER_GPU_SYSTEM, budget,
                     segment_cache=cache)
    assert warm.epoch_makespan_s < base.epoch_makespan_s
    assert warm.epoch_makespan_s <= cold.epoch_makespan_s
    assert sum(m.cache_hit_bytes for m in warm.per_layer) > 0


# ---- admission/report-accounting bugfixes (ISSUE 6 satellites) -----------

def test_infer_after_queue_expiry_raises_admission_error(quickstart_graph):
    """infer() whose own request expires before the internal batch runs
    must raise an AdmissionError naming the expiry — not leak a bare
    StopIteration out of a result search."""
    a = quickstart_graph
    calls = {"n": 0}

    def clock():
        # First read stamps submit(); every later read (run_batch's
        # prepare_queue) lands far past the 60 s relative deadline.
        calls["n"] += 1
        return 0.0 if calls["n"] == 1 else 1e9

    eng = _engine(a, clock=clock)
    eng.register_graph("g", a)
    h = np.random.default_rng(0).standard_normal(
        (a.n_rows, 8)).astype(np.float32)
    with pytest.raises(AdmissionError) as ei:
        eng.infer("g", h, deadline_s=60.0)
    assert ei.value.decision.reason == "deadline-expired"
    assert eng._queue == [] and eng._rejected == []


def test_infer_preserves_foreign_admission_verdicts(quickstart_graph):
    """Rejection verdicts from *other* callers' submits must survive an
    interleaved infer() and surface in the next real BatchReport instead
    of vanishing into the private report infer() discards."""
    rng = np.random.default_rng(1)
    a = quickstart_graph
    probe = _engine(a)
    probe.register_graph("g", a)
    h = [rng.standard_normal((a.n_rows, 8)).astype(np.float32)
         for _ in range(3)]
    est = probe.estimate_request_cost(InferenceRequest("g", h[0]))
    # Room for one queued request (est <= cap) but not two (2*est > cap).
    eng = _engine(a, max_queue_cost_s=1.5 * est)
    eng.register_graph("g", a)
    rid = eng.submit(InferenceRequest("g", h[0]))
    with pytest.raises(AdmissionError):
        eng.submit(InferenceRequest("g", h[1]))      # queue-full verdict
    out = eng.infer("g", h[2])                       # interleaved caller
    np.testing.assert_allclose(out, _reference_chain(a, h[2], []), atol=1e-4)
    report = eng.run_batch()
    assert [r.request_id for r in report.results] == [rid]
    assert [v.reason for v in report.rejected] == ["queue-full"]


def test_run_batch_leaves_caller_requests_unmutated(quickstart_graph):
    """Queue preparation prices/stamps engine-side copies; the caller's
    own InferenceRequest objects stay untouched."""
    rng = np.random.default_rng(2)
    a = quickstart_graph
    eng = _engine(a)
    eng.register_graph("g", a)
    submitted = InferenceRequest(
        "g", rng.standard_normal((a.n_rows, 8)).astype(np.float32),
        deadline_s=120.0)
    eng.submit(submitted)
    direct = InferenceRequest(
        "g", rng.standard_normal((a.n_rows, 8)).astype(np.float32))
    eng._queue.append(direct)                        # e.g. an orphan re-queue
    report = eng.run_batch()
    assert len(report.results) == 2
    assert submitted.estimated_cost_s == 0.0
    assert submitted.submitted_s == -1.0
    assert submitted.request_id == -1
    assert direct.estimated_cost_s == 0.0
    assert direct.submitted_s == -1.0


def test_direct_requeue_deadline_not_instantly_expired(quickstart_graph):
    """A deadline-bearing request that reaches the queue without passing
    submit() (submitted_s still the -1.0 sentinel) is stamped on first
    sight, not expired against the monotonic epoch."""
    rng = np.random.default_rng(3)
    a = quickstart_graph
    eng = _engine(a, clock=lambda: 1e6)   # epoch far beyond any deadline
    eng.register_graph("g", a)
    eng._queue.append(InferenceRequest(
        "g", rng.standard_normal((a.n_rows, 8)).astype(np.float32),
        deadline_s=60.0))
    report = eng.run_batch()
    assert report.expired == []
    assert len(report.results) == 1


# ---- partition-aware sharding (connectivity-clustered owner maps) --------

@pytest.fixture(scope="module")
def sbm_graph():
    from repro.data import generate_sbm_graph, normalized_adjacency

    a = normalized_adjacency(generate_sbm_graph(
        512, 4096, n_blocks=4, p_in=0.95, seed=0))
    a.validate()
    return a


def _partitioned_engine(a, clusters=8, **overrides):
    from repro.io.tiers import ICI_RING

    kw = dict(device_budget_bytes=_budget(a, width=32),
              cache_device_bytes=_budget(a, width=32),
              cache_shards=4, ici_topology=ICI_RING,
              partition_shards=clusters, max_batch_features=32)
    kw.update(overrides)
    eng = ServingEngine(EngineConfig(**kw))
    eng.register_graph("g", a)
    return eng


def _workload(a, seed=5, width=32, hidden=16):
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((a.n_rows, width)).astype(np.float32)
    w = [rng.standard_normal((width, hidden)).astype(np.float32)]
    return h, w


def test_partition_shards_end_to_end_outputs_bitexact(sbm_graph):
    """partition_shards only moves brick ownership — every epoch's output
    must be bit-identical to the CRC-owner default."""
    a = sbm_graph
    h, w = _workload(a)
    crc = _partitioned_engine(a, clusters=0)
    part = _partitioned_engine(a, clusters=8)
    spg = part._engines["g"]
    assert spg.partition is not None and spg.partition.n_clusters == 8
    assert part.cache._owner_maps, \
        "register_graph must install the owner map eagerly"
    for _ in range(2):
        crc.submit(InferenceRequest("g", h, w))
        part.submit(InferenceRequest("g", h, w))
        ref = crc.run_batch().results[0].output
        got = part.run_batch().results[0].output
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_partition_off_without_sharded_cache(quickstart_graph):
    """partition_shards on an unsharded cache is a no-op: CRC owners are
    already correct and an all-zeros owner map would only add overhead."""
    eng = _engine(quickstart_graph, partition_shards=8)
    eng.register_graph("g", quickstart_graph)
    assert eng._engines["g"].partition is None


def test_partition_owner_map_survives_warm_start(sbm_graph, tmp_path):
    a = sbm_graph
    h, w = _workload(a)
    donor = _partitioned_engine(a)
    donor.submit(InferenceRequest("g", h, w))
    cold = donor.run_batch()
    donor.checkpoint_cache(str(tmp_path))

    fresh = _partitioned_engine(a)
    assert fresh.cache._owner_maps, \
        "owner map must be installed before warm_start puts route bricks"
    ws = fresh.warm_start(str(tmp_path))
    assert ws.bricks > 0
    # Every restored brick sits on the shard its owner map dictates.
    for s, shard in enumerate(fresh.cache.shards):
        for key in list(shard._device) + list(shard._host):
            assert fresh.cache.owner_of(key) == s
    fresh.submit(InferenceRequest("g", h, w))
    first = fresh.run_batch()
    assert first.uploaded_bytes == 0, \
        "warm-started partitioned epoch must not re-stream wire bytes"
    np.testing.assert_array_equal(np.asarray(first.results[0].output),
                                  np.asarray(cold.results[0].output))


def test_update_graph_keeps_partition_owner_maps(sbm_graph):
    a = sbm_graph
    h, w = _workload(a)
    eng = _partitioned_engine(a)
    eng.submit(InferenceRequest("g", h, w))
    eng.run_batch()
    part_before = eng._engines["g"].partition
    rep = eng.update_graph("g", inserts=[(5, 300, 0.5), (6, 301, 0.25)])
    assert rep.plans_updated >= 1
    part_after = eng._engines["g"].partition
    assert part_after is not None, "partition must survive edge deltas"
    np.testing.assert_array_equal(part_after.cluster_to_shard,
                                  part_before.cluster_to_shard)
    assert eng.cache._owner_maps, \
        "owner maps must be re-installed for the migrated plan"
    # Exactness on the updated graph, vs a CRC engine serving it fresh.
    ref_eng = _partitioned_engine(eng._graphs["g"], clusters=0)
    eng.submit(InferenceRequest("g", h, w))
    ref_eng.submit(InferenceRequest("g", h, w))
    got = eng.run_batch().results[0].output
    ref = ref_eng.run_batch().results[0].output
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_install_schedule_swaps_partition(sbm_graph):
    from repro.core import TunedSchedule
    from repro.core.autotune import DEFAULT_MIN_BYTES, DEFAULT_PASS_ORDER

    a = sbm_graph
    eng = _partitioned_engine(a, clusters=0)
    assert eng._engines["g"].partition is None

    def tuned(clusters):
        return TunedSchedule(
            graph="g", min_bytes=DEFAULT_MIN_BYTES,
            pass_order=DEFAULT_PASS_ORDER, ell_buckets=None,
            predicted_makespan_s=1.0, default_makespan_s=1.0,
            partition_clusters=clusters)

    eng.install_schedule(tuned(8))
    spg = eng._engines["g"]
    assert spg.partition is not None and spg.partition.n_clusters == 8
    assert not spg._prepared, "cluster change must drop prepared plans"
    h, w = _workload(a)
    eng.submit(InferenceRequest("g", h, w))
    out_part = eng.run_batch().results[0].output
    eng.install_schedule(tuned(None))
    assert eng._engines["g"].partition is None
    eng.submit(InferenceRequest("g", h, w))
    out_crc = eng.run_batch().results[0].output
    np.testing.assert_array_equal(np.asarray(out_part), np.asarray(out_crc))
