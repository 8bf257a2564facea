"""The program's profiler spans (`repro.trace`) and the demotion counter.

A streamed pass traced by `jax.profiler` on the CPU: the `aires.*` spans
nest as the layers do (the `col_tile` sync inside the kernel dispatch
inside the pass; a demotion inside the cache store that caused it), every
name emitted is in `SPANS` and every name in `SPANS` is emitted by a
serving batch with a GAT request and a training step; without the GAT
request, every name but its own. `demoted_bytes` carries the cache's
demotions from `StreamStats` up to `BatchReport`, and a demoted brick
copies nothing device->host.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AiresConfig, AiresSpGEMM, plan_memory_dense_features
from repro.io import TieredSegmentCache
from repro.sparse import csr_from_dense
from repro.trace import PREFIX, SPANS

N, F = 64, 16


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(5)
    dense = ((rng.random((N, N)) < 0.3)
             * rng.standard_normal((N, N))).astype(np.float32)
    return csr_from_dense(dense), dense


def _budget(a):
    """Streams `a` at width F in two segments, in both directions."""
    est = plan_memory_dense_features(a, a.n_rows, F, float("inf"))
    return int(est.m_b + est.m_c + 0.6 * a.nbytes())


def _engine(a):
    """An engine whose cache's device tier holds one of its two bricks."""
    eng = AiresSpGEMM(AiresConfig(device_budget_bytes=_budget(a), bm=8,
                                  bk=8))
    ells = eng._prepare(a, (N, F), transpose=False).ells
    assert len(ells) == 2
    eng.segment_cache = TieredSegmentCache(
        device_budget_bytes=max(e.nbytes() for e in ells))
    return eng


def _traced(directory, fn):
    """Host events of `fn`'s run under the profiler: (start, end, name)
    of each `aires.*` span, and the number of host threads that had one."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(directory))
    try:
        jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    events, threads = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in line.events
                    if e.name.startswith(PREFIX)]
            threads += bool(mine)
            events.extend(mine)
    return events, threads


def _stats(directory, name):
    """The stats of each host event `name` in the trace under directory."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return [dict(e.stats) for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name == name]


def _inside(events, inner, *outer, every=True) -> bool:
    """Some `inner` span was emitted, and each (or, with `every` False,
    one) lies within a span named in `outer`."""
    outers = [(s, e) for s, e, n in events if n in outer]
    inners = [(s, e) for s, e, n in events if n == inner]
    within = [any(o0 <= s and e <= o1 for o0, o1 in outers)
              for s, e in inners]
    return bool(inners) and (all(within) if every else any(within))


def test_streamed_pass_spans_nest(tmp_path, graph):
    a, _ = graph
    eng = _engine(a)
    h = jnp.asarray(np.random.default_rng(0).standard_normal((N, F)),
                    jnp.float32)
    events, threads = _traced(tmp_path, lambda: [eng(a, h), eng(a, h)])
    assert threads == 1
    assert {n for _, _, n in events} <= set(SPANS)
    for inner, outer in [
            ("aires.kernel.sync", "aires.kernel"),
            ("aires.kernel", "aires.pass"),
            ("aires.upload", "aires.pass"),
            ("aires.cache.probe", "aires.pass"),
            ("aires.cache.store", "aires.pass"),
            ("aires.pass.wait", "aires.pass"),
            ("aires.assemble", "aires.pass"),
            ("aires.cache.promote", "aires.cache.probe")]:
        assert _inside(events, inner, outer), (inner, outer)
    # The first pass's second store evicts the first brick; the second pass
    # promotes each brick back inside its probe, which evicts the other.
    assert _inside(events, "aires.cache.demote", "aires.cache.store",
                   every=False)
    assert _inside(events, "aires.cache.demote", "aires.cache.store",
                   "aires.cache.probe")
    assert sum(n == "aires.pass" for _, _, n in events) == 2


GAT_SPANS = {"aires.attn", "aires.engine.project"}


def _gat_request(rng):
    from repro.configs.gat_ppi import SMOKE
    from repro.models.gat import gat_init
    from repro.runtime import InferenceRequest

    # SMOKE's layout, each layer streaming at most F columns.
    cfg = dataclasses.replace(SMOKE, feature_dim=F, head_dims=(8, 8, 5))
    params = [{k: np.asarray(v) for k, v in p.items()}
              for p in gat_init(cfg, jax.random.PRNGKey(3))]
    return InferenceRequest("g", rng.standard_normal((N, F)).astype(
        np.float32), params, model=cfg)


def _serving_and_step(a, rng, gat: bool):
    """A two-request serving batch (one of them a GAT where `gat`) whose
    tier holds one brick, then a GCN training step."""
    from repro.models.gcn import GCNConfig, gcn_init
    from repro.runtime import EngineConfig, InferenceRequest, ServingEngine
    from repro.train.loop import make_gcn_train_step

    eng = _engine(a)
    serving = ServingEngine(EngineConfig(
        device_budget_bytes=_budget(a), max_batch_features=F,
        cache_device_bytes=eng.segment_cache.device_budget_bytes))
    serving.register_graph("g", a)
    serving.submit(InferenceRequest(
        "g", rng.standard_normal((N, F)).astype(np.float32),
        [rng.standard_normal((F, F)).astype(np.float32)] * 2))
    serving.submit(_gat_request(rng) if gat else InferenceRequest(
        "g", rng.standard_normal((N, F)).astype(np.float32),
        [rng.standard_normal((F, F)).astype(np.float32)] * 2))
    cfg = GCNConfig(feature_dim=F, hidden_dims=(F,), n_classes=4,
                    out_of_core=True)
    params = gcn_init(cfg, jax.random.PRNGKey(0))
    init_opt, step = make_gcn_train_step(
        cfg, AiresSpGEMM(AiresConfig(device_budget_bytes=_budget(a), bm=8,
                                     bk=8)),
        a, jnp.asarray(rng.standard_normal((N, F)), jnp.float32),
        jnp.asarray(rng.integers(0, 4, N)))

    def run():
        report = serving.run_batch()
        assert len(report.results) == 2
        return step(params, init_opt(params))[0]

    return run


def test_every_span_is_listed_and_emitted(tmp_path, graph):
    """A two-request serving batch, one GCN and one GAT request, whose
    tier holds one brick, then a GCN training step, emit exactly the names
    in `SPANS`; the GAT request's `aires.attn` carries its grid steps,
    bricks and heads."""
    a, _ = graph
    events, _ = _traced(tmp_path, _serving_and_step(
        a, np.random.default_rng(1), gat=True))
    assert {n for _, _, n in events} == set(SPANS)
    assert _inside(events, "aires.train.update", "aires.train.step")
    assert _inside(events, "aires.pass", "aires.train.step", every=False)
    for name in ("aires.engine.inputs", "aires.engine.project",
                 "aires.engine.combine", "aires.engine.readback"):
        assert _inside(events, name, "aires.engine.group"), name
    assert _inside(events, "aires.attn", "aires.pass")
    assert _inside(events, "aires.kernel.sync", "aires.attn", "aires.kernel")
    assert _inside(events, "aires.prep.robw", "aires.prep")
    assert _inside(events, "aires.prep.densify", "aires.prep")
    attn = _stats(tmp_path, "aires.attn")
    # Two segments a pass, three GAT layers.
    assert len(attn) == 6
    for st in attn:
        assert st["grid_steps"] >= 1 and st["bricks"] >= 1
    assert sorted({st["heads"] for st in attn}) == [2, 3]


def test_gcn_request_opens_the_spans_it_did(tmp_path, graph):
    """With no GAT request, the same batch and step emit every name but
    the GAT's own."""
    a, _ = graph
    events, _ = _traced(tmp_path, _serving_and_step(
        a, np.random.default_rng(1), gat=False))
    assert {n for _, _, n in events} == set(SPANS) - GAT_SPANS


def test_demoted_bytes_follow_the_cache(graph):
    """Each stream's `demoted_bytes` is the cache's own count over it, and
    a batch's is the sum of its streams'."""
    from repro.runtime import EngineConfig, InferenceRequest, ServingEngine

    a, _ = graph
    eng = _engine(a)
    cache = eng.segment_cache
    h = jnp.asarray(np.random.default_rng(2).standard_normal((N, F)),
                    jnp.float32)
    for _ in range(3):
        before = cache.stats.demoted_bytes
        eng(a, h)
        assert eng.last_stream_stats.demoted_bytes == (
            cache.stats.demoted_bytes - before)
    assert sum(s.demoted_bytes for s in eng.forward_stats_log) == (
        cache.stats.demoted_bytes) > 0

    serving = ServingEngine(EngineConfig(
        device_budget_bytes=_budget(a), max_batch_features=F,
        cache_device_bytes=cache.device_budget_bytes))
    serving.register_graph("g", a)
    rng = np.random.default_rng(3)
    total = 0
    for _ in range(2):
        serving.submit(InferenceRequest(
            "g", rng.standard_normal((N, F)).astype(np.float32),
            [rng.standard_normal((F, F)).astype(np.float32)] * 2))
        report = serving.run_batch()
        total += report.demoted_bytes
        assert report.demoted_bytes > 0
    assert total == serving.cache_stats().demoted_bytes


def test_demotions_copy_nothing(tmp_path, graph):
    """The streamed bricks keep the host arrays they were uploaded from, so
    every `aires.cache.demote` span says `copied` 0 of its bytes, and so
    do `demote_copied_bytes` in the stream's stats and the cache's."""
    a, _ = graph
    eng = _engine(a)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((N, F)),
                    jnp.float32)
    _traced(tmp_path, lambda: [eng(a, h), eng(a, h)])
    demotions = _stats(tmp_path, "aires.cache.demote")
    assert len(demotions) == 3
    for st in demotions:
        assert st["bytes"] > 0 and st["copied"] == 0
    assert sum(s.demoted_bytes for s in eng.forward_stats_log) == (
        eng.segment_cache.stats.demoted_bytes) > 0
    assert eng.segment_cache.stats.demote_copied_bytes == 0
    assert all(s.demote_copied_bytes == 0 for s in eng.forward_stats_log)
