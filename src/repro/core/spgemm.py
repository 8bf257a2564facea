"""AiresSpGEMM — the paper's technique as a first-class composable API.

`AiresSpGEMM` wraps the full pipeline: Eq.5-7 planning → RoBW partitioning →
tile densification → double-buffered streaming → Pallas block-ELL kernel.
It is **differentiable**: a `jax.custom_vjp` computes dH = Aᵀ dX by
streaming the transposed RoBW plan (`robw_transpose_plan`) through the same
`DoubleBufferedStreamer`, so `jax.grad` through a GCN layer triggers real
backward I/O instead of a modeled multiplier.

`gcn_epoch` chains it through the Fig. 1 aggregation/combination chain for
per-epoch latency accounting. In execute mode the epoch runs a true
forward+backward pass (jax.vjp over the layer chain) and reports separate
forward/backward `StreamStats`; simulate mode keeps the paper's
`backward_factor` accounting for large-scale modeling.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Literal, NamedTuple, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.memory_model import FeatureSpec, plan_memory_unified
from repro.core.pipeline import (
    LANE_COMPUTE,
    LANE_DMA,
    CacheProbeOp,
    ComputeOp,
    PhaseSpec,
    PipelinePlan,
    ScheduleMetrics,
    TransferOp,
    modeled_spgemm_seconds,
)
from repro.core.robw import (
    densify_segment,
    robw_delta_partition,
    robw_partition,
    robw_transpose_plan,
    segments_to_block_ell,
)
from repro.core.scheduler import (
    SCHEDULERS,
)
from repro.io.segment_cache import SegmentKey, TieredSegmentCache
from repro.io.shard_cache import ShardedSegmentCache
from repro.io.streamer import DoubleBufferedStreamer, StreamStats
from repro.io.tiers import MemoryTier, Path, TierSpec, TPU_V5E_SYSTEM
from repro.sparse.formats import (
    CSR,
    BlockELL,
    csr_fingerprint,
    graph_cache_prefix,
    segment_fingerprint,
)
from repro.sparse.partition import Partition
from repro.sparse.updates import EdgeDelta
from repro.trace import span

# Both tiered caches speak the same get/put protocol; the engine and the
# epoch runner accept either (mesh-sharded device tier included).
SegmentCacheLike = Union[TieredSegmentCache, ShardedSegmentCache]


@dataclasses.dataclass
class AiresConfig:
    device_budget_bytes: int
    bm: int = 128
    bk: int = 128
    align: int = 8
    stream_depth: int = 2            # double buffering (Phase II)
    straggler_deadline_s: Optional[float] = None
    wire_format: Literal["csr", "bricks"] = "bricks"
    interpret: Optional[bool] = None  # None → auto (CPU container)
    # Plan (and densify) as if the feature matrix were this wide, regardless
    # of the H actually passed — one RoBW plan then serves every layer width
    # and every batched request width ≤ plan_features, so the segment cache
    # hits across layers/epochs/requests instead of re-planning per shape.
    # Widths beyond plan_features still get their own (conservative) plan.
    plan_features: Optional[int] = None
    # Explicit ELL bucket ladder for tile densification (see
    # `ell_bucket_capacity` and the autotuner, repro.core.autotune).
    # None (default) keeps the power-of-two buckets bit-exactly.
    ell_buckets: Optional[List[int]] = None


class BrickPayload(NamedTuple):
    """One segment's bricks as streamed: the three arrays and the host
    `BlockELL` they were uploaded from.

    Bricks are read-only, so a cached brick's host form is its `ell`'s own
    arrays (`host_form`): the segment cache demotes it by dropping the
    device arrays, with no device->host copy. `jax.tree_util` keeps the
    type, so a promoted host form is again a `BrickPayload`.
    """

    blocks: Any
    col_tile: Any
    n_tiles: Any
    ell: BlockELL

    def host_form(self) -> "BrickPayload":
        """The same payload with the `ell`'s numpy arrays as leaves: the
        bytes and dtypes a device->host copy of the leaves would give
        (`np.asarray` returns the `ell`'s array itself where the dtypes
        agree, as the float32 / int32 bricks do)."""
        e = self.ell
        return BrickPayload(np.asarray(e.blocks, self.blocks.dtype),
                            np.asarray(e.col_tile, self.col_tile.dtype),
                            np.asarray(e.n_tiles, self.n_tiles.dtype), e)


class _Segment(NamedTuple):
    """One segment of a prepared direction as streamed: its index in plan
    order, its host bricks, its cache key and its wire bytes."""

    i: int
    ell: BlockELL
    key: SegmentKey
    nbytes: int


@dataclasses.dataclass
class _Prepared:
    """Host-side artifacts of one streaming direction for one graph."""

    a: CSR                    # the matrix actually streamed (A or Aᵀ)
    mem: object               # MemoryEstimate
    plan: object              # RoBWPlan
    segs: List[object]
    ells: List[object]
    cache_ns: str = ""        # segment-cache namespace (graph+direction+plan)
    # Per-segment content fingerprints (segment_fingerprint of each
    # segment's rows) — the content half of every SegmentKey this plan
    # emits; the delta-update path preserves them for reused segments.
    fps: List[str] = dataclasses.field(default_factory=list)
    transpose: bool = False   # streams Aᵀ (the backward direction)


@dataclasses.dataclass
class UpdateStats:
    """What one `AiresSpGEMM.apply_edge_update` changed, summed over every
    prepared plan (direction × width) of the updated graph."""

    plans_updated: int = 0
    segments_retiled: int = 0
    segments_reused: int = 0
    retiled_bytes: int = 0        # wire bytes of the re-densified bricks
    # Cache keys the update made stale (old keys absent from the updated
    # plans) — exactly what the runtime must invalidate, nothing more.
    stale_keys: List[SegmentKey] = dataclasses.field(default_factory=list)


class AiresSpGEMM:
    """Out-of-core X = A @ H with the AIRES schedule, executing for real.

    The simulate-mode scheduler (`repro.core.scheduler.AiresScheduler`)
    models large-scale latency; this class *runs* the streaming pipeline —
    `jax.device_put` uploads overlap kernel dispatch via JAX async dispatch,
    with the same RoBW plan and memory model.

    Differentiation: `__call__` carries a custom VJP whose backward streams
    the transposed plan (dH = Aᵀ dX), so autodiff through a GCN layer incurs
    the paper's backward I/O for real. Per-call `StreamStats` accumulate in
    `forward_stats_log` / `backward_stats_log` (cleared by
    `reset_stats_logs`), with the most recent also on `last_stream_stats` /
    `last_backward_stream_stats`.
    """

    # Per-engine cap on cached (graph × shape × direction) preparations.
    # Densified BlockELL tiles outweigh the source CSR, so the cache is a
    # small LRU rather than unbounded — epoch loops reuse a handful of
    # entries (one per layer width per direction) and multi-graph training
    # evicts instead of growing without bound.
    PREPARED_CACHE_MAX = 8

    def __init__(self, config: AiresConfig,
                 segment_cache: Optional[SegmentCacheLike] = None,
                 plan_passes=None,
                 partition: Optional[Partition] = None):
        self.config = config
        # Partition-aware sharding (repro.sparse.partition): when set, RoBW
        # plans tile over the partition's cluster boundaries, cache
        # namespaces carry a `:p{n_clusters}` tag, and every prepared plan
        # installs its partition-derived owner map on a sharded segment
        # cache — warm-epoch ICI drops from topology, not retention
        # heuristics. None keeps every byte of the unpartitioned behavior.
        self.partition = partition
        # Optional tiered LRU over uploaded BlockELL payloads (shared across
        # engines by the serving layer): repeat streams of the same plan skip
        # the device_put entirely — see StreamStats.cache_hit_bytes.
        self.segment_cache = segment_cache
        # Optional repro.core.passes.PassPipeline applied to every stream
        # plan before it is estimated; a real stream takes the rewritten
        # plan's shard placements from it. None = identity.
        self.plan_passes = plan_passes
        self._prepared: Dict[tuple, _Prepared] = {}
        self._transposes: Dict[tuple, CSR] = {}
        self.forward_stats_log: List[StreamStats] = []
        self.backward_stats_log: List[StreamStats] = []
        self.last_stream_stats: Optional[StreamStats] = None
        self.last_backward_stream_stats: Optional[StreamStats] = None

    def plan(self, a: CSR, h_shape, boundaries=None) -> tuple:
        mem = plan_memory_unified(
            a, FeatureSpec(h_shape[0], h_shape[1], 4, 0.0),
            m_total=self.config.device_budget_bytes)
        if not mem.feasible:
            raise MemoryError(
                f"AIRES plan infeasible: budget {self.config.device_budget_bytes}"
                f" < M_B+M_C = {mem.m_b + mem.m_c:.0f}")
        plan = robw_partition(a, int(mem.m_a), align=self.config.align,
                              boundaries=boundaries)
        return mem, plan

    def reset_stats_logs(self) -> None:
        self.forward_stats_log = []
        self.backward_stats_log = []

    def clear_cache(self) -> None:
        """Drop all cached plans/densified tiles (and memoized transposes).

        Does NOT touch the shared segment cache — use
        `segment_cache.invalidate_prefix(graph_cache_prefix(a))` for that.
        """
        self._prepared.clear()
        self._transposes.clear()

    @staticmethod
    def graph_cache_prefix(a: CSR) -> str:
        """Identity prefix shared by every segment-cache namespace this
        engine derives for `a` (any direction, plan width, or budget).

        Content-addressed (`csr_fingerprint`), not ``id(a)``: ids are
        recycled after GC, and a stable prefix is what lets checkpointed
        bricks warm-start a *fresh* process's cache (the keys survive).
        Updated graphs keep their ancestor's prefix (`CSR.graph_key`
        lineage) so untouched segment keys survive edge deltas — see
        `repro.sparse.formats.graph_cache_prefix`."""
        return graph_cache_prefix(a)

    # ---- host-side preparation (cached per graph × feature shape) --------
    #
    # CSR inputs are treated as IMMUTABLE: the cache keys are content
    # fingerprints (structure AND values), but the fingerprint itself is
    # memoized on the instance, so mutating a CSR in place between calls
    # would serve stale densified tiles. Re-weighted graphs must be new CSR
    # objects (they then fingerprint — and cache — separately).

    def transpose_of(self, a: CSR) -> CSR:
        """Memoized Aᵀ — shared by backward streaming and epoch accounting.

        Content-addressed (`csr_fingerprint`, values included), never
        id(a): ids are recycled after GC, and this memo holds no reference
        to its source graph that would keep the id alive. The memo is
        LRU-bounded like `_prepared`.
        """
        key = (csr_fingerprint(a), a.nnz, a.shape)
        hit = self._transposes.pop(key, None)
        if hit is not None:
            self._transposes[key] = hit  # re-insert: most-recently-used
            return hit
        from repro.sparse.formats import csr_transpose
        a_t = csr_transpose(a)
        self._transposes[key] = a_t
        while len(self._transposes) > self.PREPARED_CACHE_MAX:
            self._transposes.pop(next(iter(self._transposes)))
        return a_t

    def _prepare(self, a: CSR, dense_shape, transpose: bool) -> _Prepared:
        """Plan + densify one streaming direction; LRU-cached for epoch
        reuse (see the immutability note above)."""
        cfg = self.config
        # Plan at the pinned width when configured (conservative for any
        # narrower H): one plan — and one set of cacheable bricks — serves
        # every width up to plan_features.
        plan_shape = (dense_shape[0],
                      max(cfg.plan_features or 0, dense_shape[1]))
        part = self.partition
        key = (csr_fingerprint(a), a.nnz, a.shape, plan_shape, transpose,
               tuple(cfg.ell_buckets or ()),
               0 if part is None else part.token)
        hit = self._prepared.pop(key, None)
        if hit is not None:
            self._prepared[key] = hit  # re-insert: most-recently-used
            return hit
        with span("prep"):
            prepared = self._prepare_miss(a, plan_shape, transpose, part)
        self._prepared[key] = prepared
        while len(self._prepared) > self.PREPARED_CACHE_MAX:
            self._prepared.pop(next(iter(self._prepared)))
        return prepared

    def _prepare_miss(self, a: CSR, plan_shape, transpose: bool,
                      part: Optional[Partition]) -> _Prepared:
        """Plan + densify one streaming direction (a `_prepare` miss)."""
        cfg = self.config
        # The partition tiles the *streamed* orientation: forward streams
        # A's rows directly; the transposed (backward) direction only lines
        # up for square graphs, where Aᵀ's rows are the same vertex set.
        part_rows = a.shape[1] if transpose else a.shape[0]
        if part is not None and part.n_rows != part_rows:
            part = None
        bounds = None if part is None else part.boundaries()
        with span("prep.robw"):
            if transpose:
                # Plan on Aᵀ: the backward output dH is (n_cols, F), so M_C
                # and the Eq. 7 segment budget must be sized for the
                # transposed orientation (they differ whenever A is
                # non-square).
                a_t = self.transpose_of(a)
                mem = plan_memory_unified(
                    a_t, FeatureSpec(plan_shape[0], plan_shape[1], 4, 0.0),
                    m_total=cfg.device_budget_bytes)
                if not mem.feasible:
                    raise MemoryError(
                        "AIRES backward plan infeasible: budget "
                        f"{cfg.device_budget_bytes} < M_B+M_C = "
                        f"{mem.m_b + mem.m_c:.0f}")
                _, plan = robw_transpose_plan(
                    a, int(mem.m_a), align=cfg.align, a_t=a_t,
                    boundaries=bounds)
                stream_a = a_t
            else:
                mem, plan = self.plan(a, plan_shape, boundaries=bounds)
                stream_a = a
        # Explicit bucket ladders tag the namespace: their bricks pad
        # differently, so they must never collide with (or warm-start
        # from) the default power-of-two entries. No buckets = the
        # pre-autotune namespace, byte-for-byte. Partitioned plans tag the
        # cluster count (`:p{k}`) the same way: their segment boundaries
        # differ, so bricks from different cluster counts must never
        # collide — and autotune's cluster-count trials each probe their
        # own namespace instead of clobbering the live one. The tag is
        # count-only on purpose: `Partition.refine` after an edge delta
        # keeps the count, so the namespace — and every untouched brick in
        # it — survives, exactly like the unpartitioned delta path.
        bucket_tag = ("" if not cfg.ell_buckets else
                      ":e" + "x".join(str(b) for b in cfg.ell_buckets))
        part_tag = "" if part is None else f":p{part.n_clusters}"
        cache_ns = (f"{self.graph_cache_prefix(a)}"
                    f":{'bwd' if transpose else 'fwd'}"
                    f":w{plan_shape[1]}:b{cfg.device_budget_bytes}"
                    f"{bucket_tag}{part_tag}")
        with span("prep.densify"):
            ells = list(segments_to_block_ell(stream_a, plan, bm=cfg.bm,
                                              bk=cfg.bk,
                                              buckets=cfg.ell_buckets))
        prepared = _Prepared(
            a=stream_a, mem=mem, plan=plan, segs=list(plan.segments),
            ells=ells, cache_ns=cache_ns,
            fps=[segment_fingerprint(stream_a, s.row_start, s.row_end)
                 for s in plan.segments],
            transpose=transpose)
        if self.segment_cache is not None:
            # Pin the source graph so the id()-derived namespace can't be
            # recycled into stale hits while cached bricks live.
            self.segment_cache.pin(cache_ns, a)
        if part is not None:
            self._install_owner_map(part, prepared, transpose)
        return prepared

    def _install_owner_map(self, part: Partition, prepared: _Prepared,
                           transpose: bool) -> None:
        """Project `part` onto one prepared plan's segments and install the
        resulting owner map on the sharded segment cache.

        No-op for unsharded caches, caches without owner-map support, or
        shard-count mismatches (a partition packed for 4 shards says
        nothing about an 8-shard cache). The transposed orientation votes
        with Aᵀ's row nnz — `part.row_nnz` counts A's rows, which are Aᵀ's
        *columns*.
        """
        cache = self.segment_cache
        if (cache is None or part.n_shards <= 1
                or not hasattr(cache, "install_owner_map")
                or part.n_shards != getattr(cache, "n_shards", 1)):
            return
        row_nnz = (np.diff(prepared.a.indptr).astype(np.int64)
                   if transpose else None)
        clusters = part.clusters_for_plan(prepared.plan, row_nnz=row_nnz)
        owners = [int(part.cluster_to_shard[c]) for c in clusters]
        cache.install_owner_map(prepared.cache_ns, owners, clusters)

    def _segment_entries(self, prepared: _Prepared) -> List[_Segment]:
        """Every segment of `prepared` in plan order: the one place a
        stream's `SegmentKey` and wire bytes are derived, for the real
        stream, its cost plan and the edge-update diff alike."""
        cfg = self.config
        return [_Segment(i, ell,
                         SegmentKey(prepared.cache_ns, i, cfg.wire_format,
                                    tuple(ell.blocks.shape), fingerprint=fp),
                         ell.nbytes())
                for i, (ell, fp) in enumerate(zip(prepared.ells,
                                                  prepared.fps))]

    # ---- incremental updates (evolving graphs) ---------------------------

    def apply_edge_update(self, old: CSR, new: CSR,
                          delta: EdgeDelta) -> UpdateStats:
        """Migrate every prepared plan of `old` to `new` incrementally.

        For each cached preparation (forward plans re-tile by
        `delta.touched_rows`, transposed plans by `delta.touched_cols`):
        untouched segments keep their bricks and fingerprints verbatim;
        touched spans re-partition under the old budget
        (`robw_delta_partition`) and re-densify only their rows
        (`densify_segment` — bit-identical to a from-scratch re-tile of the
        same rows). The cache namespace carries over unchanged (`new`
        inherits `old`'s `graph_key` lineage), so the untouched segments'
        cache entries keep hitting; re-placed bricks flow through
        `ShardPlacementPass` on the next stream like any not-yet-resident
        segment. Returns the stale keys the caller must invalidate.
        """
        old_fp = csr_fingerprint(old)
        cfg = self.config
        stats = UpdateStats()
        if (self.partition is not None
                and self.partition.n_rows == new.shape[0]):
            # Delta re-clustering: only the touched rows re-vote their
            # cluster label (majority neighbor); the cluster→shard map —
            # and therefore the `:p{k}` namespace and every untouched
            # brick's owner — carries over verbatim.
            self.partition = self.partition.refine(new, delta.touched_rows)
        token = 0 if self.partition is None else self.partition.token
        for key in [k for k in self._prepared if k[0] == old_fp]:
            prep = self._prepared.pop(key)
            _, _, _, plan_shape, transpose, buckets, _ = key
            if transpose:
                stream_new = self.transpose_of(new)
                touched = delta.touched_cols
            else:
                stream_new = new
                touched = delta.touched_rows
            new_plan, reuse = robw_delta_partition(stream_new, prep.plan,
                                                   touched)
            segs, ells, fps = [], [], []
            for seg, src in zip(new_plan.segments, reuse):
                segs.append(seg)
                if src is not None:
                    ells.append(prep.ells[src])
                    fps.append(prep.fps[src])
                    stats.segments_reused += 1
                else:
                    ell = densify_segment(stream_new, seg,
                                          bm=cfg.bm, bk=cfg.bk,
                                          buckets=cfg.ell_buckets)
                    ells.append(ell)
                    fps.append(segment_fingerprint(
                        stream_new, seg.row_start, seg.row_end))
                    stats.segments_retiled += 1
                    stats.retiled_bytes += ell.nbytes()
            old_keys = [e.key for e in self._segment_entries(prep)]
            # mem is reused: the budget (and Eq. 5 split) depends on shape
            # and width, both unchanged by an edge delta; the re-packed
            # spans were re-partitioned under the same m_a.
            new_prep = _Prepared(a=stream_new, mem=prep.mem, plan=new_plan,
                                 segs=segs, ells=ells,
                                 cache_ns=prep.cache_ns, fps=fps,
                                 transpose=transpose)
            self._prepared[(csr_fingerprint(new), new.nnz, new.shape,
                            plan_shape, transpose, buckets,
                            token)] = new_prep
            if self.segment_cache is not None:
                # Re-pin: the namespace now answers for the updated graph.
                self.segment_cache.pin(prep.cache_ns, new)
            part = self.partition
            if part is not None and part.n_rows == stream_new.shape[0]:
                # Refresh the namespace's owner map from the refined
                # labels: migrated rows may now live in a different
                # cluster, and the re-tiled plan's segments need owners.
                self._install_owner_map(part, new_prep, transpose)
            fresh = {e.key for e in self._segment_entries(new_prep)}
            stats.stale_keys.extend(k for k in old_keys if k not in fresh)
            stats.plans_updated += 1
        self._transposes.pop((old_fp, old.nnz, old.shape), None)
        return stats

    # ---- pipeline-plan building + streaming executors --------------------

    @staticmethod
    def device_payload(ell) -> BrickPayload:
        """Upload one BlockELL brick — the device-resident payload format
        shared by the streamer, the segment cache, and engine warm-start."""
        return BrickPayload(
            jax.device_put(jnp.asarray(ell.blocks)),
            jax.device_put(jnp.asarray(ell.col_tile)),
            jax.device_put(jnp.asarray(ell.n_tiles)),
            ell,
        )

    def _build_stream_plan(self, prepared: _Prepared,
                           feat: Optional[FeatureSpec] = None,
                           spec: Optional[TierSpec] = None) -> PipelinePlan:
        """Phase II of one streamed pass as a `PipelinePlan`: one transfer
        (or cache probe) and one modeled kernel per `_segment_entries`
        entry, so the plan prices the keys and bytes the real stream moves.

        `PipelinePlan.estimate()` reads its modeled cost (cache probes
        peek, never mutate) — what the serving engine's admission control
        prices a request with; a real stream reads only the placements the
        plan passes write (`_stream`).
        """
        cfg = self.config
        spec = spec if spec is not None else TPU_V5E_SYSTEM
        if feat is None:
            feat = FeatureSpec(prepared.a.shape[0],
                               cfg.plan_features or 1, 4, 0.0)
        plan = PipelinePlan(scheduler="aires-stream")
        plan.phases = [PhaseSpec("stream")]
        plan.mem = prepared.mem
        plan.robw = prepared.plan
        plan.segments = len(prepared.ells)
        cached = self.segment_cache is not None
        for seg, e in zip(prepared.segs, self._segment_entries(prepared)):
            miss = TransferOp(Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                              e.nbytes, tag="phaseII/seg",
                              payload=(e.i, e.ell))
            if cached:
                i_io = plan.add(CacheProbeOp(e.key, e.nbytes, miss,
                                             payload=(e.i, e.ell)),
                                "stream", LANE_DMA)
            else:
                i_io = plan.add(miss, "stream", LANE_DMA)
            plan.add(ComputeOp(modeled_spgemm_seconds(seg.nnz, feat, spec)),
                     "stream", LANE_COMPUTE, deps=(i_io,))
        return plan

    def stream_plan(self, a: CSR, h_shape, spec: Optional[TierSpec] = None,
                    transpose: bool = False,
                    apply_passes: bool = True) -> PipelinePlan:
        """Plan (and prepare) one streamed pass of `a` at `h_shape`.

        The configured `plan_passes` are applied, so estimates price the
        plan the stream will actually run. ``apply_passes=False`` returns
        the raw pre-rewrite plan — the autotuner's trial input (rewrite
        passes mutate ops in place, so each candidate pipeline needs a
        fresh build)."""
        h_shape = tuple(int(s) for s in h_shape)
        feat = FeatureSpec(h_shape[0], h_shape[1], 4, 0.0)
        prepared = self._prepare(a, h_shape, transpose)
        plan = self._build_stream_plan(prepared, feat=feat, spec=spec)
        if apply_passes and self.plan_passes is not None:
            plan, _ = self.plan_passes.apply(
                plan, spec=spec, segment_cache=self.segment_cache)
        return plan

    def _placements(self, prepared: _Prepared, width: int) -> Dict[int, int]:
        """{segment index: cache shard} for every probe the configured plan
        passes placed: the one thing a real stream reads from a rewritten
        plan."""
        if self.plan_passes is None:
            return {}
        feat = FeatureSpec(prepared.a.shape[1], width, 4, 0.0)
        plan, _ = self.plan_passes.apply(
            self._build_stream_plan(prepared, feat=feat),
            segment_cache=self.segment_cache)
        return {op.key.segment_id: op.place_shard
                for op in plan.phase_ops("stream")
                if isinstance(op, CacheProbeOp)
                and op.place_shard is not None}

    def _stream(self, prepared: _Prepared, consume_one: Callable,
                width: int) -> jax.Array:
        """Run one double-buffered pass over `prepared`'s segments, under
        the span `aires.pass`, and record its `StreamStats` in the
        direction's log and `last_*stream_stats`.

        consume_one(ell_dev, ell, i) -> per-segment device result, from
        the segment's device arrays, its host BlockELL and its index in
        the plan; `width` is the streamed dense operand's column count.
        Returns the row-concatenated output.
        """
        cfg = self.config
        cache = self.segment_cache
        with span("pass", direction="bwd" if prepared.transpose else "fwd",
                  width=width, segments=len(prepared.ells)):
            entries = self._segment_entries(prepared)
            place = self._placements(prepared, width)

            def consume(dev, i):
                blocks, col_tile, n_tiles, ell = dev
                ell_dev = dataclasses.replace(
                    ell, blocks=blocks, col_tile=col_tile, n_tiles=n_tiles)
                return consume_one(ell_dev, ell, i)

            cache_lookup = cache_store = None
            if cache is not None:
                def cache_lookup(e):
                    return cache.get(e.key, nbytes=e.nbytes)

                def cache_store(e, dev):
                    cache.put(e.key, dev, e.nbytes, shard=place.get(e.i))

                # Copy, not alias: TieredSegmentCache.stats mutates in place.
                before = dataclasses.replace(cache.stats)
            streamer = DoubleBufferedStreamer(
                lambda e: self.device_payload(e.ell), consume,
                depth=cfg.stream_depth, deadline_s=cfg.straggler_deadline_s,
                payload_nbytes=lambda e: e.nbytes,
                cache_lookup=cache_lookup, cache_store=cache_store)
            parts = streamer.run_all(entries)
            stats = streamer.stats
            if cache is not None:
                # Host-tier hits re-crossed the bus via device_put
                # promotions; surface them so uploaded_bytes=0 can't misread
                # as zero traffic. Likewise inter-chip traffic (sharded
                # cache) and peer-host serves (cache directory).
                # `cache.stats` may be a recomputed aggregate
                # (ShardedSegmentCache), so snapshot-and-diff.
                after = cache.stats
                stats.promoted_bytes = (
                    after.promoted_bytes - before.promoted_bytes)
                stats.ici_bytes = after.ici_bytes - before.ici_bytes
                stats.directory_hit_bytes = (
                    after.directory_hit_bytes - before.directory_hit_bytes)
                stats.demoted_bytes = (
                    after.demoted_bytes - before.demoted_bytes)
                stats.demote_copied_bytes = (
                    after.demote_copied_bytes - before.demote_copied_bytes)
            with span("assemble"):
                out = jnp.concatenate(
                    [p[: s.n_rows] for p, s in zip(parts, prepared.segs)],
                    axis=0)
        if prepared.transpose:
            self.last_backward_stream_stats = stats
            self.backward_stats_log.append(stats)
        else:
            self.last_stream_stats = stats
            self.forward_stats_log.append(stats)
        return out

    def _stream_spmm(self, prepared: _Prepared, dense) -> jax.Array:
        """X = stream(A) @ dense — shared by forward and transposed passes."""
        from repro.kernels import bcsr_spmm

        cfg = self.config
        dense_dev = jax.device_put(dense)  # Phase I: resident feature matrix
        return self._stream(
            prepared,
            lambda ell_dev, ell, _: bcsr_spmm(
                ell_dev, dense_dev, interpret=cfg.interpret,
                bricks=int(ell.n_tiles.sum())),
            int(dense.shape[1]))

    # ---- differentiable public API --------------------------------------

    def __call__(self, a: CSR, h: jax.Array) -> jax.Array:
        """X = A @ H, differentiable w.r.t. H (dH streams Aᵀ)."""
        h = jnp.asarray(h)
        fwd = self._prepare(a, h.shape, transpose=False)
        h_dtype = h.dtype

        @jax.custom_vjp
        def spgemm(h_in):
            return self._stream_spmm(fwd, h_in)

        def spgemm_fwd(h_in):
            return self._stream_spmm(fwd, h_in), None

        def spgemm_bwd(_, g):
            dh = self._backward_stream(a, g)
            return (dh.astype(h_dtype),)

        spgemm.defvjp(spgemm_fwd, spgemm_bwd)
        return spgemm(h)

    def _backward_stream(self, a: CSR, g) -> jax.Array:
        """dH = Aᵀ @ g via the transposed RoBW plan."""
        g = jnp.asarray(g)
        return self._stream_spmm(self._prepare(a, g.shape, transpose=True), g)

    def attend(self, a: CSR, z: jax.Array, s_src: jax.Array,
               s_dst: jax.Array, heads: int,
               negative_slope: float) -> jax.Array:
        """GAT's aggregation, streamed: (n, heads, head_width) where, for
        head k, row i is Σ_j softmax_j(LeakyReLU(s_dst[i, k] + s_src[j, k]))
        z[j, k] over the nonzeros j of A's row i (`kernels/gat_attn.py`),
        LeakyReLU with the given negative slope.

        z is (n, heads * head_width), s_src and s_dst (n, heads). The pass
        streams A's forward plan, its bricks and cache keys, as `__call__`
        does, with the bricks as the mask; its `StreamStats` join
        `forward_stats_log`. Forward only: no VJP is defined.
        """
        from repro.kernels import gat_attention
        from repro.kernels.gat_attn import pack_sources

        cfg = self.config
        n, width = z.shape
        fwd = self._prepare(a, (n, width), transpose=False)
        # Packed once, for every segment's kernel.
        zs = pack_sources(z, s_src, heads=heads, head_width=width // heads,
                          bk=cfg.bk)
        s_dst = jnp.pad(s_dst, ((0, cfg.bm), (0, 0)))
        starts = [seg.row_start for seg in fwd.segs]

        def consume_one(ell_dev, ell, i):
            rows = s_dst[starts[i]:starts[i] + ell.n_row_blocks * ell.bm]
            return gat_attention(ell_dev, zs, rows, heads=heads,
                                 head_width=width // heads,
                                 negative_slope=negative_slope,
                                 interpret=cfg.interpret,
                                 bricks=int(ell.n_tiles.sum()))

        return self._stream(fwd, consume_one, width)


@dataclasses.dataclass
class EpochMetrics:
    per_layer: List[ScheduleMetrics]
    epoch_makespan_s: float
    total_transfer_bytes: int
    # execute mode: modeled backward metrics (transposed stream) per layer
    per_layer_backward: List[ScheduleMetrics] = dataclasses.field(
        default_factory=list)
    # execute mode: real streaming stats, one entry per layer, layer order
    forward_stream: List[StreamStats] = dataclasses.field(default_factory=list)
    backward_stream: List[StreamStats] = dataclasses.field(default_factory=list)
    wall_seconds: float = 0.0

    def speedup_over(self, other: "EpochMetrics") -> float:
        return other.epoch_makespan_s / max(self.epoch_makespan_s, 1e-12)


def gcn_epoch(
    a: CSR,
    h0,
    weights: List[np.ndarray],
    scheduler_name: str,
    spec: TierSpec,
    device_budget: int,
    mode: Literal["simulate", "execute"] = "simulate",
    dataset: str = "",
    backward_factor: float = 2.0,
    engine_config: Optional[AiresConfig] = None,
    segment_cache: Optional[SegmentCacheLike] = None,
) -> EpochMetrics:
    """One training epoch of the Fig. 1 chain under a given scheduler.

    Per layer: X = Ã H (out-of-core SpGEMM, scheduled), H' = σ(X W) (dense,
    on-device).

    simulate — backward is modeled as `backward_factor`× the forward cost
    with the same streaming pattern, matching the paper's per-epoch
    accounting (§V-A) at scales where execution is impractical.

    execute — a true forward+backward pass runs through the differentiable
    `AiresSpGEMM` engine (`jax.vjp` over the layer chain): the backward
    really streams the transposed RoBW plan, and `EpochMetrics` carries the
    per-layer forward/backward `StreamStats` plus modeled per-layer metrics
    for the chosen scheduler over A (forward) and Aᵀ (backward).
    `backward_factor` is ignored in execute mode.
    """
    if mode == "execute":
        return _execute_epoch(a, h0, weights, scheduler_name, spec,
                              device_budget, dataset, engine_config,
                              segment_cache)
    return _simulate_epoch(a, h0, weights, scheduler_name, spec,
                           device_budget, dataset, backward_factor,
                           segment_cache)


def _simulate_epoch(a, h0, weights, scheduler_name, spec, device_budget,
                    dataset, backward_factor,
                    segment_cache=None) -> EpochMetrics:
    from repro.core.memory_model import FeatureSpec

    kw = ({"segment_cache": segment_cache}
          if segment_cache is not None and scheduler_name == "aires" else {})
    sched = SCHEDULERS[scheduler_name](spec, device_budget=device_budget, **kw)
    per_layer: List[ScheduleMetrics] = []
    makespan = 0.0
    total_bytes = 0
    h = h0
    for li, w in enumerate(weights):
        res = sched.run(a, h, mode="simulate", dataset=dataset)
        m = res.metrics
        per_layer.append(m)
        if m.oom:
            return EpochMetrics(per_layer, float("inf"), 0)
        # forward + modeled backward streaming cycles
        makespan += m.makespan_s * (1.0 + backward_factor)
        total_bytes += int(m.total_transfer_bytes * (1.0 + backward_factor))
        if isinstance(h, FeatureSpec):
            h = FeatureSpec(h.n_rows, w.shape[1], h.dtype_bytes,
                            h.sparsity_pct)
        else:
            h = np.zeros((h.shape[0], w.shape[1]), dtype=np.float32)
    return EpochMetrics(per_layer, makespan, total_bytes)


def _execute_epoch(a, h0, weights, scheduler_name, spec, device_budget,
                   dataset, engine_config, segment_cache=None) -> EpochMetrics:
    from repro.core.memory_model import FeatureSpec

    cfg = engine_config or AiresConfig(device_budget_bytes=device_budget)
    engine = AiresSpGEMM(cfg, segment_cache=segment_cache)
    engine.reset_stats_logs()
    sched = SCHEDULERS[scheduler_name](spec, device_budget=device_budget)
    # One transpose, shared with the engine's backward streaming plans.
    a_t = engine.transpose_of(a)

    # ---- modeled per-layer accounting: forward over A, backward over Aᵀ.
    per_layer: List[ScheduleMetrics] = []
    per_layer_bwd: List[ScheduleMetrics] = []
    makespan = 0.0
    total_bytes = 0
    n, f = h0.shape
    width = f
    for w in weights:
        feat_f = FeatureSpec(n, width, 4, 0.0)
        res_f = sched.run(a, feat_f, mode="simulate", dataset=dataset)
        # dX arriving at this layer's aggregation has the layer's own width.
        res_b = sched.run(a_t, FeatureSpec(n, width, 4, 0.0),
                          mode="simulate", dataset=dataset)
        per_layer.append(res_f.metrics)
        per_layer_bwd.append(res_b.metrics)
        if res_f.metrics.oom or res_b.metrics.oom:
            return EpochMetrics(per_layer, float("inf"), 0,
                                per_layer_backward=per_layer_bwd)
        makespan += res_f.metrics.makespan_s + res_b.metrics.makespan_s
        total_bytes += (res_f.metrics.total_transfer_bytes
                        + res_b.metrics.total_transfer_bytes)
        width = w.shape[1]

    # ---- real forward+backward through the differentiable engine.
    h0_j = jnp.asarray(np.asarray(h0, dtype=np.float32))
    ws = [jnp.asarray(np.asarray(w, dtype=np.float32)) for w in weights]

    def chain(h, ws_):
        for w_ in ws_:
            x = engine(a, h)
            h = jax.nn.relu(x @ w_)
        return h

    t0 = time.perf_counter()
    out, vjp_fn = jax.vjp(chain, h0_j, ws)
    grads = vjp_fn(jnp.ones_like(out) / out.size)
    jax.block_until_ready((out, grads))
    wall = time.perf_counter() - t0

    return EpochMetrics(
        per_layer=per_layer,
        epoch_makespan_s=makespan,
        total_transfer_bytes=total_bytes,
        per_layer_backward=per_layer_bwd,
        forward_stream=list(engine.forward_stats_log),
        backward_stream=list(reversed(engine.backward_stats_log)),
        wall_seconds=wall,
    )
