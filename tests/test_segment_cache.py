"""Property tests for the tiered segment cache (io/segment_cache.py).

Invariants, each driven by hypothesis when installed and by a deterministic
seeded sweep otherwise (the conftest/test_robw_property pattern — fallback,
never skip):
  * LRU order: device eviction is strictly least-recently-used, and a get()
    refreshes recency.
  * capacity: neither tier ever exceeds its byte budget, under any op mix.
  * demote/promote round-trip: a brick that falls to the host tier and is
    promoted back is bit-identical.
  * byte accounting: hit_bytes + miss_bytes equals exactly the wire bytes
    requested through get() — the invariant the serving metrics rely on.
"""
import dataclasses
import importlib.util

import numpy as np
import pytest

from repro.io import SegmentKey, TieredSegmentCache
from repro.io.tiers import MemoryTier, PAPER_GPU_SYSTEM, TieredMemorySystem

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None


def _key(i, graph="g0"):
    return SegmentKey(graph, i, "bricks", (i, 8, 8))


# ---- deterministic unit behaviour ----------------------------------------

def test_lru_eviction_demotes_in_order():
    cache = TieredSegmentCache(device_budget_bytes=3)
    for i in range(3):
        cache.put(_key(i), f"seg{i}", 1)
    cache.put(_key(3), "seg3", 1)           # evicts k0 (oldest)
    assert cache.tier_of(_key(0)) == MemoryTier.HOST
    assert cache.tier_of(_key(3)) == MemoryTier.DEVICE
    cache.get(_key(1), nbytes=1)            # refresh k1
    cache.put(_key(4), "seg4", 1)           # now k2 is LRU, not k1
    assert cache.tier_of(_key(2)) == MemoryTier.HOST
    assert cache.tier_of(_key(1)) == MemoryTier.DEVICE
    assert cache.stats.demoted_bytes == 2


def test_host_tier_hit_promotes_back_to_device():
    cache = TieredSegmentCache(device_budget_bytes=2)
    cache.put(_key(0), "a", 1)
    cache.put(_key(1), "b", 1)
    cache.put(_key(2), "c", 1)              # k0 demoted
    assert cache.tier_of(_key(0)) == MemoryTier.HOST
    assert cache.get(_key(0), nbytes=1) == "a"
    assert cache.tier_of(_key(0)) == MemoryTier.DEVICE
    assert cache.stats.host_hits == 1
    assert cache.stats.promoted_bytes == 1


def test_host_budget_drops_overflow_for_good():
    cache = TieredSegmentCache(device_budget_bytes=1, host_budget_bytes=1)
    cache.put(_key(0), "a", 1)
    cache.put(_key(1), "b", 1)              # k0 -> host
    cache.put(_key(2), "c", 1)              # k1 -> host, k0 dropped
    assert _key(0) not in cache
    assert cache.stats.evicted_bytes == 1
    assert cache.get(_key(0), nbytes=1) is None


def test_oversized_entry_spills_straight_to_host():
    cache = TieredSegmentCache(device_budget_bytes=4)
    cache.put(_key(0), "big", 9)
    assert cache.tier_of(_key(0)) == MemoryTier.HOST
    assert cache.device_used_bytes == 0
    assert cache.get(_key(0), nbytes=9) == "big"  # served, promoted-or-held
    assert cache.device_used_bytes <= 4


def test_transfers_charged_through_tiered_memory_system():
    tms = TieredMemorySystem(PAPER_GPU_SYSTEM)
    cache = TieredSegmentCache(device_budget_bytes=2, tms=tms)
    cache.put(_key(0), "a", 1)
    cache.put(_key(1), "b", 1)
    cache.put(_key(2), "c", 1)              # one demotion
    _, cost = cache.get_with_cost(_key(0), nbytes=1)  # promotion (+ a
    #                                                     demotion: full)
    tags = [t.tag for t in tms.transfers]
    assert tags == ["cache/demote", "cache/promote", "cache/demote"]
    assert cost > 0.0
    n_before = len(tms.transfers)
    _, cost = cache.get_with_cost(_key(0), nbytes=1)  # device hit: free
    assert cost == 0.0
    assert len(tms.transfers) == n_before


def test_invalidate_graph_drops_both_tiers():
    cache = TieredSegmentCache(device_budget_bytes=2)
    cache.put(_key(0, "gA"), "a", 1, pin="graph-object-A")
    cache.put(_key(1, "gA"), "b", 1)
    cache.put(_key(2, "gB"), "c", 1)        # demotes k0
    assert cache.invalidate_graph("gA") == 2
    assert len(cache) == 1
    assert cache.tier_of(_key(2, "gB")) is not None


def test_invalidate_prefix_is_delimiter_aware():
    """Regression (ISSUE 7 satellite): raw-string prefix matching let
    invalidating `g12` take out an innocent `g123` bystander. Matching is
    now `:`-boundary aware — only the graph itself and its namespace
    extensions fall."""
    cache = TieredSegmentCache(device_budget_bytes=8)
    cache.put(_key(0, "g12"), "a", 1)
    cache.put(_key(1, "g12:fwd:w64"), "b", 1)
    cache.put(_key(2, "g123"), "c", 1)
    cache.put(_key(3, "g123:fwd:w64"), "d", 1)
    assert cache.invalidate_prefix("g12") == 2
    assert cache.tier_of(_key(0, "g12")) is None
    assert cache.tier_of(_key(1, "g12:fwd:w64")) is None
    assert cache.tier_of(_key(2, "g123")) is not None, \
        "sibling graph sharing leading characters must survive"
    assert cache.tier_of(_key(3, "g123:fwd:w64")) is not None


def test_prefix_matches_semantics():
    from repro.io import prefix_matches

    assert prefix_matches("g12", "g12")
    assert prefix_matches("g12:fwd:w64", "g12")
    assert not prefix_matches("g123", "g12")
    assert not prefix_matches("g123:fwd", "g12")
    assert not prefix_matches("g1", "g12")
    assert prefix_matches(1234, "x", exact=1234)   # non-string graph ids
    assert not prefix_matches(1234, "12")


def test_invalidate_keys_drops_exact_keys_both_tiers():
    """The delta-update path: exactly the stale keys fall, nothing else —
    including a host-tier (demoted) entry."""
    cache = TieredSegmentCache(device_budget_bytes=2)
    cache.put(_key(0), "a", 1)
    cache.put(_key(1), "b", 1)
    cache.put(_key(2), "c", 1)              # k0 demoted to host
    assert cache.tier_of(_key(0)) == MemoryTier.HOST
    assert cache.invalidate_keys([_key(0), _key(2), _key(9)]) == 2
    assert cache.tier_of(_key(0)) is None
    assert cache.tier_of(_key(2)) is None
    assert cache.tier_of(_key(1)) == MemoryTier.DEVICE


def test_invalidate_keys_unpublishes_directory_holdings():
    from repro.io import CacheDirectory

    directory = CacheDirectory()
    directory.claim_worker("w0")
    cache = TieredSegmentCache(device_budget_bytes=1, directory=directory,
                               worker_id="w0")
    cache.put(_key(0), "a", 1)
    cache.put(_key(1), "b", 1)              # k0 demoted → published
    assert directory.holder(_key(0)) == "w0"
    cache.invalidate_keys([_key(0)])
    assert directory.holder(_key(0)) is None


def test_directory_drop_reaches_any_holder():
    """`drop` removes a record regardless of holder (unlike the
    holder-checked `unpublish`) — a graph delta makes peers' copies stale
    too."""
    from repro.io import CacheDirectory

    directory = CacheDirectory()
    directory.publish(_key(0), "peer", "v", 4)
    directory.unpublish(_key(0), "me")      # holder-checked: no-op
    assert directory.holder(_key(0)) == "peer"
    assert directory.drop(_key(0)) is True
    assert directory.holder(_key(0)) is None
    assert directory.drop(_key(0)) is False


def test_directory_drop_prefix_delimiter_aware_and_holder_filtered():
    from repro.io import CacheDirectory

    directory = CacheDirectory()
    directory.publish(_key(0, "g12:fwd"), "w0", "a", 1)
    directory.publish(_key(1, "g12:bwd"), "w1", "b", 1)
    directory.publish(_key(2, "g123:fwd"), "w0", "c", 1)
    assert directory.drop_prefix("g12", worker_id="w0") == 1
    assert directory.holder(_key(1, "g12:bwd")) == "w1"
    assert directory.holder(_key(2, "g123:fwd")) == "w0"
    assert directory.drop_prefix("g12") == 1    # any holder
    assert len(directory) == 1


def test_fingerprint_distinguishes_segment_generations():
    """Same (graph, segment, format, shape) but different content
    fingerprints are different cache keys — the stale generation cannot
    shadow the fresh one."""
    cache = TieredSegmentCache(device_budget_bytes=4)
    stale = SegmentKey("g0", 0, "bricks", (1, 8, 8), fingerprint="s8n4caaaa")
    fresh = SegmentKey("g0", 0, "bricks", (1, 8, 8), fingerprint="s8n5cbbbb")
    cache.put(stale, "old", 1)
    assert cache.get(fresh, nbytes=1) is None
    cache.put(fresh, "new", 1)
    assert cache.get(fresh, nbytes=1) == "new"
    assert cache.get(stale, nbytes=1) == "old"


# ---- demotion of a brick that keeps its host arrays ----------------------

def _brick(seed=0, row_blocks=2, width=3):
    """A float32 / int32 BlockELL and its `AiresSpGEMM.device_payload`."""
    from repro.core import AiresSpGEMM
    from repro.sparse.formats import BlockELL

    rng = np.random.default_rng(seed)
    ell = BlockELL(
        blocks=rng.standard_normal((row_blocks, width, 8, 8)).astype(
            np.float32),
        col_tile=rng.integers(-1, 9, (row_blocks, width)).astype(np.int32),
        n_tiles=rng.integers(0, width + 1, row_blocks).astype(np.int32),
        bm=8, bk=8, n_rows=8 * row_blocks, n_cols=72)
    return ell, AiresSpGEMM.device_payload(ell)


def _assert_same_leaves(got, want):
    """Same tree, and each leaf the same bytes, dtype and shape."""
    import jax

    assert type(got) is type(want)
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        if hasattr(w, "dtype"):
            assert np.asarray(g).dtype == np.asarray(w).dtype
            assert np.asarray(g).shape == np.asarray(w).shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            assert g is w


@pytest.mark.parametrize("kind", ["brick", "array", "array_and_meta"])
def test_demotion_copies_only_values_without_host_form(kind, monkeypatch):
    """A `device_payload` brick moves down a tier as its `ell`'s own arrays
    (copying nothing, again after a promotion); a plain device array is
    still copied, and `demote_copied_bytes` and the span's `copied` count
    it."""
    import jax.numpy as jnp

    from repro.io import segment_cache

    opened = []
    real_span = segment_cache.span
    monkeypatch.setattr(segment_cache, "span", lambda name, **attrs: (
        opened.append((name, attrs)), real_span(name, **attrs))[1])
    ell, brick = _brick()
    value = {"brick": brick,
             "array": brick.blocks,
             "array_and_meta": (jnp.asarray(ell.blocks), "meta")}[kind]
    nbytes = ell.nbytes()
    copied = 0 if kind == "brick" else ell.blocks.nbytes
    cache = TieredSegmentCache(device_budget_bytes=nbytes)
    cache.put(_key(0), value, nbytes)
    for _ in range(2):
        before = dataclasses.replace(cache.stats)
        cache.put(_key(1), "other", nbytes)     # k0 down a tier
        assert cache.tier_of(_key(0)) == MemoryTier.HOST
        assert cache.stats.demoted_bytes - before.demoted_bytes == nbytes
        assert cache.stats.demote_copied_bytes - (
            before.demote_copied_bytes) == copied
        assert opened[-1] == ("cache.demote",
                              {"bytes": nbytes, "copied": copied})
        host = cache._host[_key(0)].value
        if kind == "brick":
            assert host.ell is ell
            assert host.blocks is ell.blocks
            assert host.col_tile is ell.col_tile
            assert host.n_tiles is ell.n_tiles
        _assert_same_leaves(cache.get(_key(0), nbytes=nbytes), value)
        assert cache.tier_of(_key(0)) == MemoryTier.DEVICE


def test_brick_round_trip_is_bit_identical_and_stays_a_brick():
    """`promote_to_device(demote_to_host(p))` is `p` bit for bit, and is a
    `BrickPayload` again, so its next demotion copies nothing either."""
    import jax

    from repro.core import BrickPayload
    from repro.io.segment_cache import (
        demote_copy_nbytes, demote_to_host, promote_to_device,
    )

    ell, brick = _brick(seed=1, row_blocks=3, width=2)
    assert demote_copy_nbytes(brick) == 0
    host = demote_to_host(brick)
    assert isinstance(host, BrickPayload)
    assert not any(isinstance(x, jax.Array) for x in host)
    back = promote_to_device(host)
    assert isinstance(back, BrickPayload)
    assert all(isinstance(x, jax.Array) for x in back[:3])
    _assert_same_leaves(back, brick)
    assert demote_copy_nbytes(back) == 0
    assert demote_to_host(back).blocks is ell.blocks
    assert demote_copy_nbytes((brick.blocks, "meta")) == ell.blocks.nbytes


def test_export_entries_matches_a_device_to_host_copy():
    """Brick checkpointing reads the same structure, bytes and dtypes as a
    device->host copy of every leaf gives, from both tiers."""
    import jax

    ell0, brick0 = _brick(seed=2)
    ell1, brick1 = _brick(seed=3)
    cache = TieredSegmentCache(device_budget_bytes=ell0.nbytes())
    cache.put(_key(0), brick0, ell0.nbytes())
    cache.put(_key(1), brick1, ell1.nbytes())   # k0 to the host tier
    exported = {key: (value, nbytes)
                for key, value, nbytes in cache.export_entries()}
    for key, brick, ell in ((_key(0), brick0, ell0),
                            (_key(1), brick1, ell1)):
        value, nbytes = exported[key]
        assert nbytes == ell.nbytes()
        _assert_same_leaves(value, jax.tree_util.tree_map(
            lambda x: np.asarray(x) if isinstance(x, jax.Array) else x,
            brick))
    assert cache.tier_of(_key(1)) == MemoryTier.DEVICE, \
        "export must not evict"


# ---- the properties (plain functions — both drivers call these) ----------

def check_capacity_and_accounting(seed):
    """No op sequence may overrun a tier budget, and requested wire bytes
    split exactly into hit_bytes + miss_bytes."""
    rng = np.random.default_rng(seed)
    dev_budget = int(rng.integers(4, 64))
    host_budget = (int(rng.integers(4, 64))
                   if rng.random() < 0.7 else None)
    cache = TieredSegmentCache(dev_budget, host_budget)
    keys = [_key(j, graph=f"g{j % 3}") for j in range(10)]
    requested = 0
    for _ in range(80):
        k = keys[int(rng.integers(0, len(keys)))]
        nb = int(rng.integers(1, dev_budget + 16))
        if rng.random() < 0.5:
            requested += nb
            cache.get(k, nbytes=nb)
        else:
            cache.put(k, ("payload", k.segment_id, nb), nb)
        assert cache.device_used_bytes <= dev_budget
        if host_budget is not None:
            assert cache.host_used_bytes <= host_budget
    st = cache.stats
    assert st.hit_bytes + st.miss_bytes == requested


def check_lru_keeps_newest(seed):
    """After n distinct 1-byte puts into a k-slot device tier, exactly the
    last k live on device and the earlier ones were demoted oldest-first."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 8))
    n = int(rng.integers(k + 1, 20))
    cache = TieredSegmentCache(device_budget_bytes=k)
    for i in range(n):
        cache.put(_key(i), i, 1)
    for i in range(n - k):
        assert cache.tier_of(_key(i)) == MemoryTier.HOST
    for i in range(n - k, n):
        assert cache.tier_of(_key(i)) == MemoryTier.DEVICE
    # host tier preserves demotion (FIFO) order
    host_keys = [key.segment_id for key in cache._host]
    assert host_keys == sorted(host_keys)


def check_demote_promote_bit_identical(seed):
    """Bricks that bounce device->host->device come back bit-identical."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n_bricks = int(rng.integers(3, 7))
    arrays = [rng.standard_normal((int(rng.integers(1, 5)), 8, 8))
              .astype(np.float32) for _ in range(n_bricks)]
    nbytes = [a.nbytes for a in arrays]
    # device tier holds barely one brick: every put demotes the previous
    cache = TieredSegmentCache(device_budget_bytes=max(nbytes))
    for i, arr in enumerate(arrays):
        cache.put(_key(i), (jnp.asarray(arr), f"meta{i}"), nbytes[i])
    for i, arr in enumerate(arrays):
        value = cache.get(_key(i), nbytes=nbytes[i])
        assert value is not None, "demoted bricks must remain servable"
        got, meta = value
        assert meta == f"meta{i}"
        np.testing.assert_array_equal(np.asarray(got), arr)
    assert cache.stats.demoted_bytes > 0
    assert cache.stats.promoted_bytes > 0


# ---- hypothesis driver ---------------------------------------------------

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_capacity_and_accounting(seed):
        check_capacity_and_accounting(seed)

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_lru_keeps_newest(seed):
        check_lru_keeps_newest(seed)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_demote_promote_bit_identical(seed):
        check_demote_promote_bit_identical(seed)


# ---- deterministic fallback driver (no hypothesis installed) -------------

else:
    @pytest.mark.parametrize("seed", range(25))
    def test_capacity_and_accounting(seed):
        check_capacity_and_accounting(seed)

    @pytest.mark.parametrize("seed", range(25))
    def test_lru_keeps_newest(seed):
        check_lru_keeps_newest(seed)

    @pytest.mark.parametrize("seed", range(10))
    def test_demote_promote_bit_identical(seed):
        check_demote_promote_bit_identical(seed)
