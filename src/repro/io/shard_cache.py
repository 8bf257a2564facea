"""Mesh-sharded device tier for the segment cache.

`TieredSegmentCache` models one chip's tiered memory. Production serving
runs a *mesh* of chips (launch/mesh.py), and replicating the cache per chip
wastes the aggregate HBM: every chip retains — and re-demotes — its own
copy of every brick. `ShardedSegmentCache` instead partitions the device
tier across a named mesh axis, in the spirit of batched/partitioned SpGEMM
scheduling (arXiv:1903.11409) and Accel-GCN's workload-balanced block
mapping (arXiv:2308.11825):

  * every `SegmentKey` has one deterministic **owner shard**
    (`shard_of(key)`, a stable CRC over the key — NOT Python's randomized
    `hash`), so a brick is retained exactly once across the mesh; a
    partition-derived **owner map** (`install_owner_map`, fed by
    `repro.sparse.partition`) replaces the CRC default per namespace so
    connectivity-clustered row blocks co-locate on the shard that
    streams them;
  * per-shard device budgets and LRU state are **independent** — one hot
    graph cannot evict another graph's bricks from a different shard;
  * a hit whose owner is a **remote** shard ships the brick over the ICI
    path (`Path.ICI`, cheaper than the PCIe-class `dma`/`sio` paths,
    dearer than local HBM) — charged through the `TieredMemorySystem` so
    simulate-mode `bytes_by_path` stays honest, and executed for real
    (`jax.device_put` onto the local chip) when the cache is built from a
    mesh with >1 actual devices;
  * host spill, promotion, and the cross-worker `CacheDirectory` all ride
    the per-shard `TieredSegmentCache`s unchanged.

A 1-shard cache is byte-identical to a bare `TieredSegmentCache` (asserted
in tests/test_shard_cache.py): shard 0 is local, so no ICI transfer is ever
charged and every call delegates straight through.
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence

from repro.io.segment_cache import (
    CacheDirectory,
    CacheStats,
    SegmentKey,
    TieredSegmentCache,
    demote_to_host,
    prefix_matches,
    promote_to_device,
)
from repro.io.tiers import (
    ICI_ALL_TO_ALL,
    ICITopology,
    MemoryTier,
    Path,
    TieredMemorySystem,
)


def _shard_blob(key: SegmentKey) -> bytes:
    """Explicit field serialization of a key's four identity fields.

    Byte-identical to ``repr((graph_id, segment_id, wire_format, shape))``
    for canonical keys (str namespace, int segment id, str wire format,
    tuple-of-int shape) — including the 1-tuple trailing comma — but built
    field by field, so a `SegmentKey` dataclass-repr change (a new field,
    a renamed one) can never silently reshuffle every owner. The CRC of a
    known key is pinned in tests/test_shard_cache.py.
    """
    dims = [repr(int(d)) for d in key.shape]
    shape = "(" + ", ".join(dims) + ("," if len(dims) == 1 else "") + ")"
    return (f"({key.graph_id!r}, {int(key.segment_id)!r}, "
            f"{key.wire_format!r}, {shape})").encode()


def shard_of(key: SegmentKey, n_shards: int) -> int:
    """Deterministic owner shard of a segment key.

    CRC32 over an explicit serialization of the key's identity fields
    (`_shard_blob`): stable within a process (unlike `hash()`, which is
    salted per interpreter for str fields), uniform enough to balance
    bricks across shards, and identical for replicated workers looking at
    the same key.

    Hashes exactly the four identity fields — `SegmentKey.fingerprint` is
    deliberately excluded, so a segment keeps its owner shard across edge
    deltas (only its content identity changes) and pre-fingerprint goldens
    keep their placement bit-exactly.
    """
    if n_shards <= 1:
        return 0
    return zlib.crc32(_shard_blob(key)) % n_shards


def _place(value: Any, device) -> Any:
    """Commit a cached value's jax arrays to `device` (the ICI hop made
    real); non-array leaves (metadata, host mirrors) pass through."""
    if device is None:
        return value
    import jax

    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, device)
        if isinstance(leaf, jax.Array) else leaf, value)


class ShardedSegmentCache:
    """Device tier partitioned over a mesh axis; drop-in for
    `TieredSegmentCache` behind the `cache_lookup`/`cache_store` hooks.

    `device_budget_bytes` is the *aggregate* device budget; each of the
    `n_shards` shards gets an independent `device_budget_bytes // n_shards`
    slice (same for the host budget). `local_shard` is the shard this
    worker's streaming pipeline runs on: hits owned by any other shard are
    charged `nbytes` over `Path.ICI` (tag ``cache/ici``), and a remote put
    ships the fresh brick to its owner (tag ``cache/shard-place``).

    Build from a mesh with `from_mesh(mesh, axis=...)` to derive `n_shards`
    from the axis size and pin each shard's entries to a real device along
    that axis — with `XLA_FLAGS=--xla_force_host_platform_device_count=8`
    the bricks genuinely live on distinct (CPU) devices and remote hits
    really cross device boundaries.
    """

    def __init__(
        self,
        device_budget_bytes: int,
        host_budget_bytes: Optional[int] = None,
        tms: Optional[TieredMemorySystem] = None,
        n_shards: int = 1,
        local_shard: int = 0,
        devices: Optional[Sequence] = None,
        directory: Optional[CacheDirectory] = None,
        worker_id: Hashable = 0,
        demote: Callable[[Any], Any] = demote_to_host,
        promote: Callable[[Any], Any] = promote_to_device,
        topology: ICITopology = ICI_ALL_TO_ALL,
    ):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if not 0 <= local_shard < n_shards:
            raise ValueError(f"local_shard {local_shard} outside "
                             f"[0, {n_shards})")
        if device_budget_bytes < n_shards:
            raise ValueError(
                f"device_budget_bytes {device_budget_bytes} < n_shards "
                f"{n_shards}: every shard needs a positive budget")
        if devices is not None and len(devices) != n_shards:
            raise ValueError(f"devices ({len(devices)}) must match "
                             f"n_shards ({n_shards})")
        self.n_shards = int(n_shards)
        self.local_shard = int(local_shard)
        self.devices = list(devices) if devices is not None else None
        self.device_budget_bytes = int(device_budget_bytes)
        self.host_budget_bytes = (None if host_budget_bytes is None
                                  else int(host_budget_bytes))
        self.tms = tms
        self.directory = directory
        self.worker_id = worker_id
        self.topology = topology
        per_dev = self.device_budget_bytes // self.n_shards
        self._per_shard_device = per_dev
        per_host = self.host_budget_bytes
        if per_host is not None and self.n_shards > 1:
            per_host = max(1, per_host // self.n_shards)
        self._per_shard_host = per_host
        self.shards: List[TieredSegmentCache] = []
        for s in range(self.n_shards):
            dev = self.devices[s] if self.devices is not None else None
            shard_promote = (promote if dev is None
                             else (lambda v, d=dev: _place(promote(v), d)))
            self.shards.append(TieredSegmentCache(
                per_dev, per_host, tms=tms, demote=demote,
                promote=shard_promote, directory=directory,
                worker_id=worker_id))
        # Remote-hit accounting lives here (the shards know nothing about
        # the mesh); the aggregate `stats` property folds it in.
        self._remote_hits = 0
        self._ici_bytes = 0
        # Placement overrides (the owner map): keys whose owner differs
        # from the default owner because a put() carried an explicit shard
        # — the shard-placement rewrite pass pins a graph's hot bricks to
        # the shard that consumes them. Queried via `owner_of`.
        self._locations: Dict[SegmentKey, int] = {}
        # Partition-derived owner maps, keyed by cache namespace
        # (SegmentKey.graph_id): owners[segment_id] replaces the CRC
        # default for that namespace's keys (`install_owner_map`), with an
        # optional parallel cluster-id map the ShardPlacementPass groups
        # co-placements by. Dropped with the namespace on prefix/graph
        # invalidation; deliberately NOT dropped by `clear()` or
        # `invalidate_keys` — the map is placement *policy* derived from
        # the graph's topology, not cached content, so re-streamed and
        # warm-started bricks land back on their partition owners.
        self._owner_maps: Dict[str, List[int]] = {}
        self._cluster_maps: Dict[str, List[int]] = {}

    @classmethod
    def from_mesh(cls, mesh, device_budget_bytes: int, axis: str = "cache",
                  local_index: int = 0, **kw) -> "ShardedSegmentCache":
        """Partition over `mesh`'s `axis`: one shard per index, each pinned
        to the first device at that index (the owner chip)."""
        import numpy as np

        names = list(mesh.axis_names)
        if axis not in names:
            raise ValueError(f"mesh has no axis {axis!r} (has {names})")
        ax = names.index(axis)
        n_shards = mesh.devices.shape[ax]
        # Owner chip per shard index: first device of each slice along axis.
        dev_grid = np.moveaxis(np.asarray(mesh.devices), ax, 0)
        dev_grid = dev_grid.reshape(n_shards, -1)
        devices = [dev_grid[s, 0] for s in range(n_shards)]
        return cls(device_budget_bytes, n_shards=n_shards,
                   local_shard=local_index, devices=devices, **kw)

    # ---- introspection ---------------------------------------------------

    @property
    def stats(self) -> CacheStats:
        """Aggregate across shards (recomputed per access — read deltas of
        this, do not mutate it)."""
        agg = CacheStats()
        for shard in self.shards:
            agg.add(shard.stats)
        agg.remote_hits += self._remote_hits
        agg.ici_bytes += self._ici_bytes
        return agg

    @property
    def device_used_bytes(self) -> int:
        return sum(s.device_used_bytes for s in self.shards)

    @property
    def host_used_bytes(self) -> int:
        return sum(s.host_used_bytes for s in self.shards)

    def __len__(self) -> int:
        return sum(len(s) for s in self.shards)

    def __contains__(self, key: SegmentKey) -> bool:
        return key in self._owner(key)

    def tier_of(self, key: SegmentKey) -> Optional[MemoryTier]:
        return self._owner(key).tier_of(key)

    def owner_of(self, key: SegmentKey) -> int:
        """The shard that owns (or would own) `key`. Resolution order:
        a placement override recorded by `put(..., shard=...)`, then the
        namespace's installed partition owner map, then the deterministic
        CRC owner. This is the owner-map query the shard-placement
        rewrite pass builds on."""
        loc = self._locations.get(key)
        if loc is not None:
            return loc
        return self._default_owner(key)

    def _default_owner(self, key: SegmentKey) -> int:
        """`key`'s owner before any per-key placement override: the
        installed partition owner map when one covers it, else CRC."""
        owners = self._owner_maps.get(key.graph_id)
        if owners is not None and 0 <= key.segment_id < len(owners):
            return owners[key.segment_id]
        return shard_of(key, self.n_shards)

    def install_owner_map(self, namespace: str, owners: Sequence[int],
                          clusters: Optional[Sequence[int]] = None) -> None:
        """Install a partition-derived owner map for one cache namespace:
        `owners[i]` owns segment i of `namespace` (overriding the CRC
        default; per-key `put(shard=)` overrides still win). `clusters`
        is the parallel majority-cluster id per segment — what
        `cluster_of_key` serves to the ShardPlacementPass so co-clustered
        bricks are co-placed. Reinstalling replaces the previous map."""
        owners = [int(s) for s in owners]
        for s in owners:
            if not 0 <= s < self.n_shards:
                raise ValueError(
                    f"owner map shard {s} outside [0, {self.n_shards})")
        if clusters is not None and len(clusters) != len(owners):
            raise ValueError(
                f"cluster map length {len(clusters)} != owner map "
                f"length {len(owners)}")
        self._owner_maps[str(namespace)] = owners
        if clusters is not None:
            self._cluster_maps[str(namespace)] = [int(c) for c in clusters]
        else:
            self._cluster_maps.pop(str(namespace), None)

    def drop_owner_map(self, namespace: str) -> bool:
        """Remove one namespace's installed owner (and cluster) map;
        returns whether a map was installed."""
        had = self._owner_maps.pop(str(namespace), None) is not None
        self._cluster_maps.pop(str(namespace), None)
        return had

    def owner_map(self, namespace: str) -> Optional[List[int]]:
        """The installed owner map for `namespace` (a copy), or None."""
        owners = self._owner_maps.get(str(namespace))
        return list(owners) if owners is not None else None

    def cluster_of_key(self, key: SegmentKey) -> Optional[int]:
        """`key`'s majority-cluster id under its namespace's installed
        cluster map, or None — the grouping handle the
        ShardPlacementPass co-places whole clusters by."""
        clusters = self._cluster_maps.get(key.graph_id)
        if clusters is not None and 0 <= key.segment_id < len(clusters):
            return clusters[key.segment_id]
        return None

    def shard_index_of(self, key: SegmentKey) -> int:
        return self.owner_of(key)

    @property
    def shard_budget_bytes(self) -> int:
        """Device budget of each independent shard."""
        return self._per_shard_device

    def shard_headroom(self, shard: int) -> int:
        """Unused device-tier bytes on `shard` — what the placement pass
        may still pin there for free warm hits."""
        return self._per_shard_device - self.shards[shard].device_used_bytes

    def shard_host_headroom(self, shard: int) -> float:
        """Unused host-tier bytes on `shard` (inf when unbounded). A
        brick's owner shard matters even on the host tier — a
        remote-owner host hit pays promotion *plus* the ICI ship — but
        host placement is the placement pass's last resort: a device-
        resident brick anywhere beats a host promotion."""
        if self._per_shard_host is None:
            return float("inf")
        return self._per_shard_host - self.shards[shard].host_used_bytes

    def ici_hops(self, shard: int) -> int:
        """Links between `shard` and the local shard under the cache's
        `ICITopology` (0 for the local shard itself)."""
        return self.topology.hops(shard, self.local_shard, self.n_shards)

    def _owner(self, key: SegmentKey) -> TieredSegmentCache:
        return self.shards[self.owner_of(key)]

    # ---- maintenance -----------------------------------------------------

    def pin(self, graph_id: Hashable, obj: Any) -> None:
        for shard in self.shards:
            shard.pin(graph_id, obj)

    def invalidate_graph(self, graph_id: Hashable) -> int:
        self._drop_locations(str(graph_id), exact=graph_id)
        return sum(s.invalidate_graph(graph_id) for s in self.shards)

    def invalidate_prefix(self, prefix: str, exact: Hashable = None) -> int:
        self._drop_locations(prefix, exact=exact)
        return sum(s.invalidate_prefix(prefix, exact=exact)
                   for s in self.shards)

    def invalidate_keys(self, keys) -> int:
        """Drop exactly the given keys (delta-update invalidation), each at
        its owner shard, clearing any placement override too."""
        dropped = 0
        for key in keys:
            dropped += self._owner(key).invalidate_keys([key])
            self._locations.pop(key, None)
        return dropped

    def _drop_locations(self, prefix: str, exact: Hashable = None) -> None:
        for key in [k for k in self._locations
                    if prefix_matches(k.graph_id, prefix, exact)]:
            del self._locations[key]
        for ns in [ns for ns in self._owner_maps
                   if prefix_matches(ns, prefix, exact)]:
            del self._owner_maps[ns]
            self._cluster_maps.pop(ns, None)

    def clear(self) -> None:
        self._locations.clear()
        for shard in self.shards:
            shard.clear()

    def export_entries(self) -> list:
        """Snapshot of every shard's entries (see
        `TieredSegmentCache.export_entries`); shard order, so a re-import
        lands each brick back on its deterministic owner."""
        out = []
        for shard in self.shards:
            out.extend(shard.export_entries())
        return out

    # ---- the cache protocol ----------------------------------------------

    def get(self, key: SegmentKey, nbytes: int = 0,
            tms: Optional[TieredMemorySystem] = None) -> Optional[Any]:
        return self.get_with_cost(key, nbytes=nbytes, tms=tms)[0]

    def get_with_cost(self, key: SegmentKey, nbytes: int = 0,
                      tms: Optional[TieredMemorySystem] = None):
        """(value, transfer_seconds). A remote-shard hit adds the ICI hop(s)
        to the owner shard's own promotion cost (if any)."""
        s = self.owner_of(key)
        value, cost = self.shards[s].get_with_cost(key, nbytes=nbytes,
                                                   tms=tms)
        if value is not None and s != self.local_shard:
            hops = self.ici_hops(s)
            self._remote_hits += 1
            self._ici_bytes += nbytes * hops
            cost += self._charge_ici(tms, nbytes, "cache/ici", hops=hops)
            if self.devices is not None:
                value = _place(value, self.devices[self.local_shard])
        return value, cost

    def peek_cost(self, key: SegmentKey, nbytes: int = 0,
                  tms: Optional[TieredMemorySystem] = None,
                  shard: Optional[int] = None):
        """Price a get WITHOUT performing it (see
        `TieredSegmentCache.peek_cost`). A remote-owned key adds the ICI
        hop(s) a hit would ride — or, on a miss, the shard-place ship the
        subsequent put() would pay; `shard` is the placement override that
        put would carry (`CacheProbeOp.place_shard`), so an estimate prices
        the rewritten plan, not the CRC default."""
        s = self.owner_of(key)
        hit, cost = self.shards[s].peek_cost(key, nbytes=nbytes, tms=tms)
        if hit:
            if s != self.local_shard:
                cost += self._charge_ici(tms, nbytes, "cache/ici",
                                         hops=self.ici_hops(s))
        else:
            dst = s if shard is None else int(shard)
            if dst != self.local_shard:
                cost += self._charge_ici(tms, nbytes, "cache/shard-place",
                                         hops=self.ici_hops(dst))
        return hit, cost

    def put(self, key: SegmentKey, value: Any, nbytes: int,
            tms: Optional[TieredMemorySystem] = None,
            pin: Any = None, shard: Optional[int] = None) -> None:
        """Insert at the owner shard; a remote owner costs one ICI ship of
        the fresh brick (the upload landed on the local chip first).

        `shard` overrides the CRC owner — the shard-placement pass pins a
        plan's bricks to the shard that streams them. The override is
        recorded in the owner map so later get/peek calls resolve to the
        real location, and any stale copy at the previous owner is dropped.
        """
        cur = self.owner_of(key)
        dst = cur if shard is None else int(shard)
        if not 0 <= dst < self.n_shards:
            raise ValueError(f"placement shard {dst} outside "
                             f"[0, {self.n_shards})")
        if dst != cur:
            self.shards[cur].discard(key)
        # Record the override only when it differs from the *default*
        # owner — which is the installed partition owner map when one
        # covers this key, not the raw CRC: a put landing exactly on the
        # partition owner needs no per-key entry (and must not pin one,
        # or a later owner-map reinstall could not move it).
        if dst != self._default_owner(key):
            self._locations[key] = dst
        else:
            self._locations.pop(key, None)
        if dst != self.local_shard:
            hops = self.ici_hops(dst)
            self._ici_bytes += nbytes * hops
            self._charge_ici(tms, nbytes, "cache/shard-place", hops=hops)
            if self.devices is not None:
                value = _place(value, self.devices[dst])
        self.shards[dst].put(key, value, nbytes, tms=tms, pin=pin)

    def _charge_ici(self, tms: Optional[TieredMemorySystem], nbytes: int,
                    tag: str, hops: int = 1) -> float:
        tms = tms if tms is not None else self.tms
        if tms is None or nbytes <= 0:
            return 0.0
        return tms.transfer(Path.ICI, MemoryTier.DEVICE, MemoryTier.DEVICE,
                            int(nbytes), tag=tag, hops=hops)
