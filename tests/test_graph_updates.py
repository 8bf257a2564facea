"""Evolving graphs: edge deltas, incremental re-tiling and segment-level
cache keys (ISSUE 7).

The headline assertions mirror the ISSUE's acceptance criteria:
  * `apply_edge_updates` is exact vs a dense oracle and strict about
    malformed updates (bounds, duplicates, delete-of-absent);
  * CSR arrays are frozen at construction — mutate-in-place fails loudly
    instead of silently serving a stale fingerprint memo;
  * `robw_delta_partition` re-partitions only touched row blocks; reused
    segments keep their boundaries, bricks and fingerprints verbatim,
    and delta-updated bricks are bit-identical to a from-scratch
    `densify_segment` of the same rows (property-tested, hypothesis-
    optional);
  * `ServingEngine.update_graph` invalidates exactly the touched segment
    keys: the post-update epoch uploads precisely `retiled_bytes`, the
    epoch after uploads zero, and outputs track the updated graph;
  * `ContinuousServer.update_graph` applies a delta between steps without
    draining the queue.
"""
import importlib.util

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (
    AiresConfig, AiresSpGEMM, densify_segment, plan_memory_dense_features,
    robw_delta_partition, robw_partition,
)
from repro.io import SegmentKey, TieredSegmentCache
from repro.runtime import (
    ContinuousServer, EngineConfig, InferenceRequest, ServingEngine,
    VirtualClock,
)
from repro.sparse import (
    EdgeDelta, apply_edge_updates, csr_fingerprint, csr_from_dense,
    csr_to_dense, graph_cache_prefix, segment_fingerprint,
)
from repro.sparse.ref_spgemm import spgemm_csr_dense

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None


@pytest.fixture(scope="module")
def quickstart_graph():
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    a = normalized_adjacency(generate_graph(
        scaled_spec(SUITESPARSE_SPECS["socLJ1"], 1e-4), seed=0))
    a.validate()
    return a


def _budget(a, width=64, a_frac=0.15):
    """Small enough that the plan holds several segments — deltas must be
    able to leave most of them untouched. Sized by the larger matrix
    dimension so both orientations (forward H: n_cols×F, backward dX:
    n_rows×F) stay feasible for rectangular property-test matrices."""
    est = plan_memory_dense_features(a, max(a.shape), width, float("inf"))
    return int(est.m_b + est.m_c + a_frac * a.nbytes())


def _random_sparse(rng):
    """Mirrors tests/test_robw_property.py's case distribution."""
    n = int(rng.integers(8, 65))
    m = int(rng.integers(8, 65))
    density = float(rng.uniform(0.01, 0.4))
    dense = ((rng.random((n, m)) < density)
             * rng.standard_normal((n, m))).astype(np.float32)
    return csr_from_dense(dense), dense


def _random_delta(rng, a, dense, max_edges=6):
    """Draw a valid (inserts, deletes) pair against `dense`'s occupancy."""
    n, m = dense.shape
    inserts, deletes, used = [], [], set()
    for _ in range(int(rng.integers(1, max_edges))):
        r, c = int(rng.integers(n)), int(rng.integers(m))
        if (r, c) in used:
            continue
        used.add((r, c))
        if dense[r, c] != 0 and rng.random() < 0.5:
            deletes.append((r, c))
        else:
            inserts.append((r, c, float(rng.standard_normal())))
    return inserts, deletes


# ---- apply_edge_updates: dense-oracle exactness --------------------------

def test_apply_edge_updates_matches_dense_oracle():
    rng = np.random.default_rng(0)
    a, dense = _random_sparse(rng)
    inserts, deletes = _random_delta(rng, a, dense, max_edges=10)
    new, delta = apply_edge_updates(a, inserts=inserts, deletes=deletes)

    ref = dense.copy()
    n_ins = n_upd = 0
    for r, c, v in inserts:
        if ref[r, c] != 0:
            n_upd += 1
        else:
            n_ins += 1
        ref[r, c] = v
    for r, c in deletes:
        ref[r, c] = 0.0
    np.testing.assert_array_equal(csr_to_dense(new), ref)
    new.validate()
    assert delta.n_inserted == n_ins
    assert delta.n_updated == n_upd
    assert delta.n_deleted == len(deletes)
    assert delta.n_changed == n_ins + n_upd + len(deletes)
    touched = sorted({r for r, _, _ in inserts} | {r for r, _ in deletes})
    assert delta.touched_rows.tolist() == touched
    touched_c = sorted({c for _, c, _ in inserts} | {c for _, c in deletes})
    assert delta.touched_cols.tolist() == touched_c


def test_apply_edge_updates_splices_untouched_rows_verbatim():
    """Untouched rows must be bit-exact — that is what keeps their segment
    fingerprints (and cached bricks) valid."""
    rng = np.random.default_rng(1)
    a, _ = _random_sparse(rng)
    r = a.n_rows // 2
    new, delta = apply_edge_updates(a, inserts=[(r, 0, 2.5)])
    assert delta.touched_rows.tolist() == [r]
    for row in range(a.n_rows):
        if row == r:
            continue
        lo_o, hi_o = int(a.indptr[row]), int(a.indptr[row + 1])
        lo_n, hi_n = int(new.indptr[row]), int(new.indptr[row + 1])
        np.testing.assert_array_equal(a.indices[lo_o:hi_o],
                                      new.indices[lo_n:hi_n])
        np.testing.assert_array_equal(a.data[lo_o:hi_o],
                                      new.data[lo_n:hi_n])


def test_apply_edge_updates_strictness():
    a = csr_from_dense(np.array([[1.0, 0.0], [0.0, 2.0]], np.float32))
    with pytest.raises(IndexError):
        apply_edge_updates(a, inserts=[(2, 0, 1.0)])
    with pytest.raises(IndexError):
        apply_edge_updates(a, deletes=[(0, 5)])
    with pytest.raises(ValueError, match="duplicate insert"):
        apply_edge_updates(a, inserts=[(0, 1, 1.0), (0, 1, 2.0)])
    with pytest.raises(ValueError, match="duplicate delete"):
        apply_edge_updates(a, deletes=[(0, 0), (0, 0)])
    with pytest.raises(ValueError, match="both inserted and deleted"):
        apply_edge_updates(a, inserts=[(0, 0, 3.0)], deletes=[(0, 0)])
    with pytest.raises(KeyError):
        apply_edge_updates(a, deletes=[(0, 1)])


def test_empty_update_is_identity():
    a = csr_from_dense(np.eye(4, dtype=np.float32))
    new, delta = apply_edge_updates(a)
    assert new is a
    assert delta.n_changed == 0
    assert delta.touched_rows.size == 0 and delta.touched_cols.size == 0


def test_updated_graph_keeps_cache_lineage():
    """graph_cache_prefix must survive chained deltas (CSR.graph_key) so
    untouched segment keys keep hitting; a fresh equal-content CSR without
    the lineage gets the ancestor-free prefix."""
    a = csr_from_dense(np.eye(6, dtype=np.float32))
    prefix = graph_cache_prefix(a)
    assert prefix == (f"g{csr_fingerprint(a)}:{a.nnz}"
                      f":{a.shape[0]}x{a.shape[1]}")
    b, _ = apply_edge_updates(a, inserts=[(0, 3, 1.0)])
    c, _ = apply_edge_updates(b, deletes=[(0, 3)])
    assert b.graph_key == prefix and c.graph_key == prefix
    assert graph_cache_prefix(b) == prefix
    assert graph_cache_prefix(c) == prefix
    assert csr_fingerprint(b) != csr_fingerprint(a)
    # same content as `a`, but rebuilt without lineage → same prefix again
    fresh = csr_from_dense(csr_to_dense(c))
    assert graph_cache_prefix(fresh) == prefix


# ---- the stale-fingerprint bugfix: frozen CSR arrays ---------------------

def test_csr_arrays_are_frozen_against_inplace_mutation():
    """Regression (ISSUE 7 satellite): `csr_fingerprint` memoizes on the
    instance, so in-place mutation used to serve stale fingerprints — and
    stale cached bricks. Arrays are now frozen at construction: the
    mutation itself raises instead of corrupting silently."""
    a = csr_from_dense(np.array([[1.0, 2.0], [0.0, 3.0]], np.float32))
    fp = csr_fingerprint(a)
    with pytest.raises(ValueError, match="read-only"):
        a.data[0] = 99.0
    with pytest.raises(ValueError, match="read-only"):
        a.indices[0] = 1
    with pytest.raises(ValueError, match="read-only"):
        a.indptr[0] = 1
    assert csr_fingerprint(a) == fp        # memo never went stale
    np.testing.assert_array_equal(csr_to_dense(a),
                                  [[1.0, 2.0], [0.0, 3.0]])


def test_edge_delta_index_arrays_are_frozen():
    a = csr_from_dense(np.eye(4, dtype=np.float32))
    _, delta = apply_edge_updates(a, inserts=[(1, 2, 1.0)])
    with pytest.raises(ValueError, match="read-only"):
        delta.touched_rows[0] = 3


# ---- robw_delta_partition ------------------------------------------------

def check_delta_partition(a, dense, budget, rng):
    inserts, deletes = _random_delta(rng, a, dense)
    new, delta = apply_edge_updates(a, inserts=inserts, deletes=deletes)
    old_plan = robw_partition(a, budget)
    new_plan, reuse = robw_delta_partition(new, old_plan,
                                           delta.touched_rows)
    segs = new_plan.segments
    # 1. Complete cover, in order, no overlap.
    assert segs[0].row_start == 0 and segs[-1].row_end == new.n_rows
    for s1, s2 in zip(segs, segs[1:]):
        assert s1.row_end == s2.row_start
    # 2. Budget respected unless a single row alone exceeds it.
    for seg in segs:
        if seg.n_rows > 1:
            assert seg.nbytes <= budget
    # 3. Reused segments are verbatim copies of untouched old segments,
    #    and no touched row falls inside a reused segment.
    touched = set(delta.touched_rows.tolist())
    for seg, src in zip(segs, reuse):
        if src is None:
            continue
        old_seg = old_plan.segments[src]
        assert (seg.row_start, seg.row_end) == (old_seg.row_start,
                                                old_seg.row_end)
        assert not touched & set(range(seg.row_start, seg.row_end))
        assert segment_fingerprint(new, seg.row_start, seg.row_end) == \
            segment_fingerprint(a, seg.row_start, seg.row_end)
    # 4. Bricks: every segment — reused or re-tiled — densifies to exactly
    #    densify_segment of the *new* matrix's rows (bit-identical), so a
    #    delta plan's bricks are interchangeable with a from-scratch
    #    re-tile of the same rows.
    for seg, src in zip(segs, reuse):
        fresh = densify_segment(new, seg, bm=8, bk=8)
        if src is not None:
            old_brick = densify_segment(a, old_plan.segments[src],
                                        bm=8, bk=8)
            np.testing.assert_array_equal(old_brick.blocks, fresh.blocks)
            np.testing.assert_array_equal(old_brick.col_tile,
                                          fresh.col_tile)


def test_delta_partition_rejects_out_of_range_rows():
    a = csr_from_dense(np.eye(8, dtype=np.float32))
    plan = robw_partition(a, 64)
    with pytest.raises(IndexError):
        robw_delta_partition(a, plan, [8])
    with pytest.raises(IndexError):
        robw_delta_partition(a, plan, [-1])


def test_delta_partition_no_touched_rows_is_plan_copy():
    a = csr_from_dense(np.eye(16, dtype=np.float32))
    plan = robw_partition(a, 48)
    new_plan, reuse = robw_delta_partition(a, plan, [])
    assert reuse == list(range(len(plan.segments)))
    assert [(s.row_start, s.row_end) for s in new_plan.segments] == \
        [(s.row_start, s.row_end) for s in plan.segments]


# ---- property: delta bricks ≡ from-scratch, cache hits survive -----------

def check_delta_update_end_to_end(seed):
    """After a random delta: SpGEMM output is exact on the new graph,
    untouched segment keys are preserved (their cache entries keep
    hitting), and changed segments carry fresh fingerprints."""
    rng = np.random.default_rng(seed)
    a, dense = _random_sparse(rng)
    h = rng.standard_normal((a.shape[1], 8)).astype(np.float32)
    budget = _budget(a, width=8, a_frac=0.3)
    cache = TieredSegmentCache(device_budget_bytes=1 << 24)
    spg = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8),
                      segment_cache=cache)
    np.testing.assert_allclose(np.asarray(spg(a, jnp.asarray(h))),
                               dense @ h, atol=1e-4, rtol=1e-4)
    (old_key,) = list(spg._prepared)
    old_keys = [e.key for e in spg._segment_entries(spg._prepared[old_key])]

    inserts, deletes = _random_delta(rng, a, dense)
    new, delta = apply_edge_updates(a, inserts=inserts, deletes=deletes)
    stats = spg.apply_edge_update(a, new, delta)
    assert stats.plans_updated == 1
    assert stats.segments_retiled >= 1

    (new_key,) = list(spg._prepared)
    prep = spg._prepared[new_key]
    new_keys = [e.key for e in spg._segment_entries(prep)]
    # Untouched keys survive verbatim (same namespace, id, fingerprint):
    # those are exactly the cache entries that keep hitting.
    surviving = set(old_keys) & set(new_keys)
    assert len(surviving) == stats.segments_reused
    assert set(stats.stale_keys) == set(old_keys) - set(new_keys)
    # Every brick — reused or re-tiled — matches a from-scratch densify of
    # the updated matrix, and every fingerprint matches its rows' content.
    for seg, ell, fp in zip(prep.plan.segments, prep.ells, prep.fps):
        fresh = densify_segment(new, seg, bm=8, bk=8)
        np.testing.assert_array_equal(ell.blocks, fresh.blocks)
        np.testing.assert_array_equal(ell.col_tile, fresh.col_tile)
        assert fp == segment_fingerprint(new, seg.row_start, seg.row_end)
    # The updated engine computes the updated graph exactly.
    ref = csr_to_dense(new) @ h
    np.testing.assert_allclose(np.asarray(spg(new, jnp.asarray(h))),
                               ref, atol=1e-4, rtol=1e-4)


if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 10_000))
    def test_delta_partition_properties(seed):
        rng = np.random.default_rng(seed)
        a, dense = _random_sparse(rng)
        budget = int(rng.integers(64, 4097))
        check_delta_partition(a, dense, budget, rng)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_delta_update_end_to_end(seed):
        check_delta_update_end_to_end(seed)
else:
    @pytest.mark.parametrize("seed", range(25))
    def test_delta_partition_properties(seed):
        rng = np.random.default_rng(seed)
        a, dense = _random_sparse(rng)
        budget = int(rng.integers(64, 4097))
        check_delta_partition(a, dense, budget, rng)

    @pytest.mark.parametrize("seed", range(10))
    def test_delta_update_end_to_end(seed):
        check_delta_update_end_to_end(seed)


def test_delta_update_migrates_backward_plan_too():
    """A prepared transposed (backward) plan re-tiles by touched *columns*
    and stays exact under jax.grad after the delta."""
    import jax

    rng = np.random.default_rng(7)
    a, dense = _random_sparse(rng)
    h = rng.standard_normal((a.shape[1], 8)).astype(np.float32)
    budget = _budget(a, width=8, a_frac=0.3)
    spg = AiresSpGEMM(AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    # d/dH sum(A @ H) = Aᵀ @ 1 broadcast across feature columns
    def grad_ref(d):
        return np.repeat(d.sum(axis=0)[:, None], 8, axis=1)

    g = jax.grad(lambda h_: jnp.sum(spg(a, h_)))(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(g), grad_ref(dense),
                               atol=1e-4, rtol=1e-4)
    assert len(spg._prepared) == 2           # forward + backward plans

    inserts, deletes = _random_delta(rng, a, dense)
    new, delta = apply_edge_updates(a, inserts=inserts, deletes=deletes)
    stats = spg.apply_edge_update(a, new, delta)
    assert stats.plans_updated == 2
    g2 = jax.grad(lambda h_: jnp.sum(spg(new, h_)))(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(g2), grad_ref(csr_to_dense(new)),
                               atol=1e-4, rtol=1e-4)
    # Transposed bricks match a from-scratch densify of the new transpose.
    for key, prep in spg._prepared.items():
        if not key[-1]:
            continue
        a_t = spg.transpose_of(new)
        for seg, ell in zip(prep.plan.segments, prep.ells):
            fresh = densify_segment(a_t, seg, bm=8, bk=8)
            np.testing.assert_array_equal(ell.blocks, fresh.blocks)


# ---- ServingEngine.update_graph: upload exactly the delta ----------------

def test_update_graph_uploads_only_retiled_bytes(quickstart_graph):
    """The ISSUE acceptance scenario: after a small edge delta the next
    epoch re-streams exactly `retiled_bytes` (untouched bricks keep
    hitting), and the epoch after uploads zero."""
    rng = np.random.default_rng(3)
    a = quickstart_graph
    h = rng.standard_normal((a.n_rows, 32)).astype(np.float32)
    w = [rng.standard_normal((32, 16)).astype(np.float32)]
    eng = ServingEngine(EngineConfig(device_budget_bytes=_budget(a),
                                     max_batch_features=64))
    eng.register_graph("g", a)

    def epoch():
        eng.submit(InferenceRequest("g", h, w))
        return eng.run_batch()

    cold, warm = epoch(), epoch()
    assert cold.uploaded_bytes > 0 and warm.uploaded_bytes == 0

    rep = eng.update_graph("g", inserts=[(5, 100, 0.5)])
    assert rep.delta.n_changed == 1
    assert rep.plans_updated >= 1
    assert rep.segments_retiled >= 1
    assert rep.segments_reused >= 1, "delta must not re-tile the graph"
    assert rep.segments_reused > rep.segments_retiled
    assert rep.stale_keys >= 1
    assert rep.cache_entries_dropped >= 1

    after = epoch()
    assert after.uploaded_bytes == rep.retiled_bytes, (
        "post-update epoch must re-stream exactly the re-tiled bricks")
    assert after.cache_hit_bytes > 0, "untouched bricks must keep hitting"
    assert epoch().uploaded_bytes == 0

    new = eng._graphs["g"]
    ref = spgemm_csr_dense(new, h) @ w[0]
    np.testing.assert_allclose(after.results[0].output, ref, atol=1e-4,
                               rtol=1e-4)


def test_update_graph_requires_registration(quickstart_graph):
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=_budget(quickstart_graph)))
    with pytest.raises(KeyError):
        eng.update_graph("nope", inserts=[(0, 0, 1.0)])


# ---- ContinuousServer: deltas between steps, queue intact ----------------

def test_continuous_server_update_between_steps(quickstart_graph):
    """A delta lands between steps without draining the queue: the request
    admitted before the update is served against the updated graph."""
    rng = np.random.default_rng(8)
    a = quickstart_graph
    eng = ServingEngine(EngineConfig(device_budget_bytes=_budget(a),
                                     max_batch_features=64,
                                     clock=VirtualClock()))
    eng.register_graph("g", a)
    server = ContinuousServer(eng)

    h1, h2 = (rng.standard_normal((a.n_rows, 40)).astype(np.float32)
              for _ in range(2))
    r1 = int(server.submit(InferenceRequest("g", h1)))
    r2 = int(server.submit(InferenceRequest("g", h2)))
    s1 = server.step()                      # serves r1 against the old graph
    assert [e.request_id for e in s1.events] == [r1]
    np.testing.assert_allclose(s1.results[0].output,
                               spgemm_csr_dense(a, h1), atol=1e-4)

    rep = server.update_graph("g", inserts=[(3, 50, 0.25)])
    assert rep.segments_reused >= 1
    assert server.pending == 1              # queue survived the delta

    s2 = server.step()                      # r2 now sees the updated graph
    assert [e.request_id for e in s2.events] == [r2]
    new = eng._graphs["g"]
    np.testing.assert_allclose(s2.results[0].output,
                               spgemm_csr_dense(new, h2), atol=1e-4)
    assert server.step() is None
