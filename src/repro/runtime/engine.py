"""Out-of-core GCN serving engine: multi-graph batching over AiresSpGEMM.

The ROADMAP's serving target meets the paper's Phase III: requests against
many resident graphs are queued, grouped by graph, and served through ONE
`AiresSpGEMM` per graph — all engines sharing one tiered segment cache
(`repro.io.segment_cache`), so the expensive part of a request (streaming
BlockELL bricks host→device) amortizes across requests, layers and epochs.

Three mechanisms do the work:

  * one prepared plan per graph — every engine plans at the pinned width
    `EngineConfig.max_batch_features` (`AiresConfig.plan_features`), so all
    layer widths and all batch widths up to the pin share a single RoBW plan
    and its cached bricks. This replaces leaning on `AiresSpGEMM`'s flat
    `PREPARED_CACHE_MAX=8` LRU, which cycles when widths multiply.
  * column-concat batching — X = A·[H₁|H₂|…] computes every queued
    request's aggregation for a graph in a single streamed pass; outputs
    split per request and the cheap dense transforms run per request.
  * Phase III chaining — activations stay jax device arrays between layers
    (relu((A H) W) chains), never round-tripping through host numpy until
    the final result is handed back.

Request semantics: a request with L weight matrices computes
    h ← relu((A h) Wₗ) for l < L-1;  output = (A h) W_{L-1}
(final layer linear); L = 0 returns the bare aggregation A·H. A request
whose `model` is a `GATConfig` runs that GAT instead (`models/gat.py`):
per layer a projection, the streamed attention over A's bricks
(`AiresSpGEMM.attend`) and the heads' combination.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import (
    load_segment_bricks,
    save_segment_bricks,
)
from repro.core.autotune import TunedSchedule, autotune_schedule
from repro.core.calibration import CostCalibrator
from repro.core.passes import PassPipeline, PlanPass
from repro.core.spgemm import AiresConfig, AiresSpGEMM, BrickPayload
from repro.io.segment_cache import (
    CacheDirectory,
    CacheStats,
    SegmentKey,
    TieredSegmentCache,
)
from repro.io.shard_cache import ShardedSegmentCache
from repro.io.tiers import (
    ICI_ALL_TO_ALL,
    ICITopology,
    MemoryTier,
    Path,
    TieredMemorySystem,
    TierSpec,
    TPU_V5E_SYSTEM,
)
from repro.models import gat, gcn
from repro.models.gat import GATConfig
from repro.sparse.formats import CSR, BlockELL
from repro.sparse.partition import Partition, partition_graph
from repro.sparse.updates import EdgeDelta, apply_edge_updates
from repro.trace import span


@dataclasses.dataclass
class EngineConfig:
    """Knobs for the serving engine (see README "Serving engine")."""

    device_budget_bytes: int
    cache_enabled: bool = True
    # Segment-cache tiers: device defaults to the streaming budget (the
    # bricks the plan streams are exactly what is worth keeping resident),
    # host to 8× that; None host budget = unbounded spill.
    cache_device_bytes: Optional[int] = None
    cache_host_bytes: Optional[int] = None
    # Sharded device tier (io/shard_cache.py): >1 partitions the cache's
    # device budget over `cache_shards` independent LRU shards, remote hits
    # riding the ICI path. 1 (default) keeps the PR-2 single-chip cache —
    # byte-identical accounting. A mesh passed to ServingEngine overrides
    # this with the size of `cache_shard_axis`.
    cache_shards: int = 1
    cache_shard_axis: str = "cache"
    # Identity of this replicated worker in a shared CacheDirectory.
    worker_id: int = 0
    # Planning width: one plan serves all request/layer widths up to this,
    # and batches are chunked so concatenated width never exceeds it.
    max_batch_features: int = 64
    bm: int = 8
    bk: int = 8
    align: int = 8
    stream_depth: int = 2
    straggler_deadline_s: Optional[float] = None
    interpret: Optional[bool] = None
    # Cost model used for admission control and warm-start accounting: the
    # engine prices each request with `PipelinePlan.estimate()` under this
    # TierSpec before it is allowed onto the queue.
    tier_spec: TierSpec = TPU_V5E_SYSTEM
    # Admission control: reject a submit() once the estimated cost of the
    # already-queued requests plus the new one exceeds this many modeled
    # seconds (None = unbounded queue, the pre-admission behavior).
    max_queue_cost_s: Optional[float] = None
    # Plan-rewrite passes (repro.core.passes): a PassPipeline — or a
    # sequence of PlanPass instances — applied to every stream plan before
    # it is estimated or executed; an EDFOrderingPass in the pipeline
    # additionally reorders run_batch() work earliest-deadline-first.
    # None (default) and the empty pipeline reproduce pass-free behavior
    # bit-exactly.
    plan_passes: Optional["PassPipeline | Sequence[PlanPass]"] = None
    # Inter-chip link topology for the sharded cache's ICI charges (ring
    # vs all-to-all); all-to-all reproduces the former flat-link costing.
    ici_topology: ICITopology = ICI_ALL_TO_ALL
    # Clock used for submit stamps, deadline expiry and EDF remaining-time
    # math. None (default) = `time.monotonic`. The continuous serving loop
    # (`repro.runtime.serving_loop`) injects a `VirtualClock` here so trace
    # replays and admission control run on one deterministic timeline.
    clock: Optional[Callable[[], float]] = None
    # Online cost-model calibration (repro.core.calibration): when set,
    # every admission/EDF/backpressure estimate prices against
    # `calibrator.calibrated(tier_spec)` instead of the raw spec, the
    # engine feeds each batch's RequestLatency stream back into it, and a
    # generation bump invalidates the memoized `_pass_costs` (and
    # reprices queued requests). None (default) = static costs, bit-exact
    # to the pre-calibration engine.
    calibrator: Optional[CostCalibrator] = None
    # Explicit ELL bucket ladder for every registered graph's bricks
    # (AiresConfig.ell_buckets); None keeps power-of-two buckets. Usually
    # installed per graph by `install_schedule` rather than set here.
    ell_buckets: Optional[Sequence[int]] = None
    # Partition-aware sharding (repro.sparse.partition): cluster count for
    # the connectivity clustering run over every registered graph when the
    # segment cache is sharded (`cache_shards > 1`). The partition's owner
    # map replaces CRC owners for that graph's bricks, cutting warm-epoch
    # ICI bytes from topology. 0 (default) = off, byte-identical to CRC
    # sharding; ignored on unsharded caches. Per-graph overrides: pass
    # `partition=` to register_graph, or install an autotuned schedule
    # whose `partition_clusters` is set.
    partition_shards: int = 0


@dataclasses.dataclass
class InferenceRequest:
    """One GCN (or GAT) inference against a registered graph.

    `deadline_s` is a relative deadline: the request must *finish* within
    that many wall seconds of submit(). Submission rejects requests whose
    modeled cost alone already exceeds the deadline (infeasible), and
    run_batch() expires requests whose deadline passed while queued.
    """

    graph: str
    features: np.ndarray                  # (n_nodes, F)
    weights: Sequence[np.ndarray] = ()    # per-layer (F_in, F_out) chain
    request_id: int = -1                  # assigned by submit()
    deadline_s: Optional[float] = None
    submitted_s: float = -1.0             # monotonic stamp set by submit()
    estimated_cost_s: float = 0.0         # modeled cost set by submit()
    # The model the request runs: None for the GCN above; a GATConfig for a
    # GAT, whose `weights` are its per-layer {"w", "a_src", "a_dst"}.
    model: Optional[GATConfig] = None

    def stream_widths(self) -> List[int]:
        """The width of each streamed pass the request needs alone."""
        if self.model is not None:
            return [self.model.stream_width(layer)
                    for layer in range(len(self.weights))]
        widths = [int(self.features.shape[1])]
        for w in list(self.weights)[:-1]:
            widths.append(int(np.asarray(w).shape[1]))
        return widths


@dataclasses.dataclass
class InferenceResult:
    request_id: int
    graph: str
    output: np.ndarray


@dataclasses.dataclass
class RejectedRequest:
    """Admission-control verdict for a request that never joined the queue
    (or expired on it). Reported in the next BatchReport."""

    graph: str
    reason: str                    # "deadline-infeasible" | "queue-full"
    estimated_cost_s: float
    deadline_s: Optional[float] = None
    request_id: int = -1           # -1: rejected before an id was assigned


class AdmissionError(RuntimeError):
    """submit() refused a request; `.decision` carries the verdict."""

    def __init__(self, decision: RejectedRequest):
        self.decision = decision
        super().__init__(
            f"request on graph {decision.graph!r} rejected "
            f"({decision.reason}): estimated cost "
            f"{decision.estimated_cost_s:.3g}s"
            + (f" vs deadline {decision.deadline_s:.3g}s"
               if decision.deadline_s is not None else ""))


class SubmitReceipt(int):
    """What `submit()` returns: the request id (an int — fully
    backward-compatible everywhere an id was expected) carrying the
    `PipelinePlan.estimate()` cost admission control priced the request
    with. 0.0 when no admission policy (deadline / queue cap) was in
    force — submit() does not pay for plan preparation in that case; use
    `ServingEngine.estimate_request_cost` for an on-demand prediction."""

    estimated_cost_s: float

    def __new__(cls, request_id: int, estimated_cost_s: float = 0.0):
        obj = super().__new__(cls, request_id)
        obj.estimated_cost_s = float(estimated_cost_s)
        return obj


@dataclasses.dataclass
class RequestLatency:
    """Predicted-vs-actual story of one served request.

    `predicted_s` is the request's `PipelinePlan.estimate()` cost (one
    streamed pass per layer — the number admission control uses).
    `actual_s` is the wall-clock from the batch's start until this
    request's output materialized — the user-visible in-batch latency,
    which includes waiting for earlier graph groups (exactly what EDF
    ordering shrinks for urgent requests). `processing_s` is the same
    stamp measured from this request's *own graph group's* start — the
    number comparable to `predicted_s` for cost-model calibration, since
    the prediction prices only this request's streamed work."""

    request_id: int
    graph: str
    predicted_s: float
    actual_s: float
    processing_s: float = 0.0

    @property
    def error_s(self) -> float:
        """Calibration error: group-relative completion vs prediction."""
        return self.processing_s - self.predicted_s


@dataclasses.dataclass
class GraphUpdateReport:
    """What one `update_graph` edge delta changed, end to end."""

    graph: str
    delta: EdgeDelta
    plans_updated: int            # prepared plans migrated (direction×width)
    segments_retiled: int         # bricks re-densified (touched rows only)
    segments_reused: int          # bricks carried over verbatim
    retiled_bytes: int            # wire bytes of the re-densified bricks
    stale_keys: int               # segment keys made stale by the delta
    cache_entries_dropped: int    # of those, entries actually evicted
    wall_seconds: float = 0.0


@dataclasses.dataclass
class WarmStartReport:
    """What warm_start() restored into the segment cache."""

    bricks: int = 0
    wire_bytes: int = 0
    modeled_seconds: float = 0.0   # storage→host + host→device, via the tms


@dataclasses.dataclass
class GroupStats:
    """I/O story of one served column-concat group (the per-group slice of
    a BatchReport's byte accounting) — what `serve_group` returns to both
    `run_batch` and the continuous serving loop."""

    uploaded_bytes: int = 0
    cache_hit_bytes: int = 0
    promoted_bytes: int = 0
    ici_bytes: int = 0
    directory_hit_bytes: int = 0
    demoted_bytes: int = 0
    demote_copied_bytes: int = 0
    segments_streamed: int = 0
    aggregation_passes: int = 0

    def accumulate(self, stats) -> None:
        """Fold one stream's `StreamStats` into the group totals."""
        self.uploaded_bytes += stats.uploaded_bytes
        self.cache_hit_bytes += stats.cache_hit_bytes
        self.promoted_bytes += stats.promoted_bytes
        self.ici_bytes += stats.ici_bytes
        self.directory_hit_bytes += stats.directory_hit_bytes
        self.demoted_bytes += stats.demoted_bytes
        self.demote_copied_bytes += stats.demote_copied_bytes
        self.segments_streamed += stats.segments
        self.aggregation_passes += 1

    def merge(self, other: "GroupStats") -> None:
        """Fold another group's totals into these (batch-level rollup)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))


@dataclasses.dataclass
class BatchReport:
    """One run_batch() drain: results + the I/O story of the batch."""

    results: List[InferenceResult]
    uploaded_bytes: int       # wire bytes freshly streamed host->device
    cache_hit_bytes: int      # wire bytes served from the segment cache
    promoted_bytes: int       # of those, host-tier hits re-crossing the bus
    segments_streamed: int    # consume() invocations (incl. cache hits)
    aggregation_passes: int   # streamed SpGEMM passes (batching merges these)
    wall_seconds: float = 0.0
    # Sharded cache: bytes that crossed the inter-chip path this batch
    # (remote-shard hits + shard placements). 0 for a 1-shard cache.
    ici_bytes: int = 0
    # Cross-worker directory: wire bytes served from a peer worker's host
    # copy, and demotion copies this worker skipped because a peer already
    # holds the brick. 0 with no directory attached.
    directory_hit_bytes: int = 0
    duplicate_avoided_bytes: int = 0
    # Bytes the segment cache moved down from its device tier (demotions)
    # while this batch streamed, and of those the bytes really copied
    # device->host: a brick that keeps its host arrays demotes uncopied.
    demoted_bytes: int = 0
    demote_copied_bytes: int = 0
    # Admission control: requests rejected at submit() since the previous
    # report, and queued requests whose deadline expired before this batch
    # ran them.
    rejected: List[RejectedRequest] = dataclasses.field(default_factory=list)
    expired: List[RejectedRequest] = dataclasses.field(default_factory=list)
    # Predicted-vs-actual latency per served request (request_id order).
    request_latency: List[RequestLatency] = dataclasses.field(
        default_factory=list)

    @property
    def bus_bytes(self) -> int:
        """Everything that actually crossed host->device this batch."""
        return self.uploaded_bytes + self.promoted_bytes

    @property
    def hit_rate(self) -> float:
        total = self.uploaded_bytes + self.cache_hit_bytes
        return self.cache_hit_bytes / total if total else 0.0


class ServingEngine:
    """Multi-graph out-of-core GCN inference with a shared segment cache.

    Usage:
        eng = ServingEngine(EngineConfig(device_budget_bytes=...))
        eng.register_graph("socLJ1", adjacency_csr)
        rid = eng.submit(InferenceRequest("socLJ1", h, weights=[w0, w1]))
        report = eng.run_batch()          # drains the queue, grouped by graph

    With `cache_enabled=False` every batch re-streams every segment — bit
    for bit the PR-1 `AiresSpGEMM` behavior (the ablation baseline).

    Scale-out: `config.cache_shards > 1` (or a `mesh` argument) partitions
    the cache's device tier across a mesh axis (`ShardedSegmentCache`), and
    a shared `CacheDirectory` lets replicated workers serve each other's
    demoted bricks instead of duplicating them — see README "Sharded
    serving". Both default off, reproducing PR-2 byte accounting exactly.
    """

    def __init__(self, config: EngineConfig,
                 directory: Optional[CacheDirectory] = None,
                 mesh=None):
        self.config = config
        self.directory = directory
        # Submit stamps, expiry and queue-position math all read this one
        # clock; a VirtualClock here puts the whole admission story on a
        # deterministic replay timeline.
        self.clock: Callable[[], float] = config.clock or time.monotonic
        # Plan-rewrite pipeline every batch's stream plans route through:
        # estimates price the rewritten plan, and real streams take its
        # shard placements. A bare sequence of passes is
        # wrapped here; track_costs=False keeps per-stream estimates off
        # the serving hot path (scheduler runs still report deltas).
        pp = config.plan_passes
        if pp is None:
            self.plan_pipeline: Optional[PassPipeline] = None
        elif isinstance(pp, PassPipeline):
            self.plan_pipeline = pp
        else:
            self.plan_pipeline = PassPipeline(
                list(pp), spec=config.tier_spec, track_costs=False)
        # All modeled I/O this engine performs outside a stream's own
        # accounting window — cache demote/promote churn, warm-start loads —
        # lands here, so `tms.bytes_by_path()` stays honest from the first
        # epoch (the warm-start bricks did cross sio+dma once).
        # keep_records=False: a serving process lives for days; only the
        # bounded per-path aggregates may grow, never a per-transfer log.
        self.tms = TieredMemorySystem(config.tier_spec, keep_records=False)
        self.cache: Optional["TieredSegmentCache | ShardedSegmentCache"] = None
        if not config.cache_enabled and (directory is not None
                                         or mesh is not None):
            raise ValueError(
                "cache_enabled=False contradicts an explicit "
                f"{'directory' if directory is not None else 'mesh'}: "
                "the sharded tier and the cross-worker directory are "
                "cache features")
        if directory is not None:
            # Distinct replica identities, or the directory silently no-ops.
            directory.claim_worker(config.worker_id)
        if config.cache_enabled:
            device_bytes = (config.cache_device_bytes
                            or config.device_budget_bytes)
            if mesh is not None:
                self.cache = ShardedSegmentCache.from_mesh(
                    mesh, device_bytes, axis=config.cache_shard_axis,
                    host_budget_bytes=config.cache_host_bytes, tms=self.tms,
                    directory=directory, worker_id=config.worker_id,
                    topology=config.ici_topology)
            elif config.cache_shards > 1:
                self.cache = ShardedSegmentCache(
                    device_budget_bytes=device_bytes,
                    host_budget_bytes=config.cache_host_bytes,
                    n_shards=config.cache_shards, tms=self.tms,
                    directory=directory, worker_id=config.worker_id,
                    topology=config.ici_topology)
            else:
                self.cache = TieredSegmentCache(
                    device_budget_bytes=device_bytes,
                    host_budget_bytes=config.cache_host_bytes, tms=self.tms,
                    directory=directory, worker_id=config.worker_id)
        self._graphs: "OrderedDict[str, CSR]" = OrderedDict()
        self._engines: Dict[str, AiresSpGEMM] = {}
        self._queue: List[InferenceRequest] = []
        self._next_id = 0
        # Admission-control state: memoized per-(graph, width) pass cost
        # estimates, and the verdicts awaiting their BatchReport.
        self._pass_costs: Dict[tuple, float] = {}
        self._rejected: List[RejectedRequest] = []
        # Calibration generation the memos were priced under; when the
        # calibrator moves past it, cost_spec() clears the memos and
        # reprices the queue. Installed autotuned schedules, per graph.
        self._cost_generation = (config.calibrator.generation
                                 if config.calibrator is not None else 0)
        self._installed_schedules: Dict[str, TunedSchedule] = {}

    # ---- graph registry --------------------------------------------------

    def register_graph(self, name: str, a: CSR,
                       partition: Optional[Partition] = None) -> None:
        """Make a graph servable. CSRs are immutable once registered (the
        cache keys on identity + structure, like AiresSpGEMM's plan cache).

        `partition` installs a connectivity-clustered owner map for this
        graph's bricks (see `repro.sparse.partition`); when omitted and
        `EngineConfig.partition_shards > 0` on a sharded cache, one is
        clustered here from the graph's CSR adjacency. Partitioned graphs
        prepare their forward plan eagerly so the owner map is installed
        on the cache before any `warm_start` puts route bricks to owners.
        """
        if name in self._graphs:
            raise ValueError(f"graph {name!r} already registered")
        a.validate()
        cfg = self.config
        if partition is None:
            partition = self._auto_partition(a)
        self._graphs[name] = a
        eng = AiresSpGEMM(
            AiresConfig(
                device_budget_bytes=cfg.device_budget_bytes,
                bm=cfg.bm, bk=cfg.bk, align=cfg.align,
                stream_depth=cfg.stream_depth,
                straggler_deadline_s=cfg.straggler_deadline_s,
                interpret=cfg.interpret,
                plan_features=cfg.max_batch_features,
                ell_buckets=(list(cfg.ell_buckets)
                             if cfg.ell_buckets else None),
            ),
            segment_cache=self.cache,
            plan_passes=self.plan_pipeline,
            partition=partition)
        self._engines[name] = eng
        if partition is not None and self.cache is not None:
            eng._prepare(a, (a.n_rows, cfg.max_batch_features),
                         transpose=False)

    def _auto_partition(self, a: CSR) -> Optional[Partition]:
        """Cluster `a` per `EngineConfig.partition_shards` — None when the
        knob is off or the cache is not sharded (CRC owners are already
        correct, and an owner map of all-zeros would only add overhead)."""
        k = int(self.config.partition_shards or 0)
        n_shards = int(getattr(self.cache, "n_shards", 1) or 1)
        if k <= 0 or n_shards <= 1:
            return None
        return partition_graph(
            a, k, n_shards=n_shards,
            topology=self.config.ici_topology,
            local_shard=int(getattr(self.cache, "local_shard", 0)))

    def evict_graph(self, name: str) -> List[InferenceRequest]:
        """Drop a graph, its engine, its cached segments (every namespace,
        not just plans still in the prepared LRU), and any queued requests
        against it — which are returned so the caller can re-route them."""
        a = self._graphs.pop(name, None)
        self._engines.pop(name, None)
        self._installed_schedules.pop(name, None)
        self._pass_costs = {k: v for k, v in self._pass_costs.items()
                            if k[0] != name}
        if a is not None:
            prefix = AiresSpGEMM.graph_cache_prefix(a)
            if self.cache is not None:
                self.cache.invalidate_prefix(prefix)
            if self.directory is not None:
                # Unpublish this worker's holdings: peers must not be
                # routed a peer-promote for entries we no longer back.
                self.directory.drop_prefix(prefix,
                                           worker_id=self.config.worker_id)
        orphaned = [r for r in self._queue if r.graph == name]
        self._queue = [r for r in self._queue if r.graph != name]
        return orphaned

    def update_graph(self, name: str, inserts=None,
                     deletes=None) -> GraphUpdateReport:
        """Apply an edge delta to a registered graph, in place of the
        evict-and-reregister cycle: prepared plans migrate incrementally
        (`AiresSpGEMM.apply_edge_update` re-tiles only touched row blocks),
        and exactly the stale segment keys are invalidated — device, host,
        sharded tiers, and every `CacheDirectory` holder, peers included.
        Untouched bricks stay resident, so the next epoch re-uploads only
        what the delta touched. Queued requests keep working: the node
        count is unchanged and they resolve the graph by name at serve
        time."""
        a = self._graphs.get(name)
        if a is None:
            raise KeyError(f"graph {name!r} not registered")
        t0 = time.perf_counter()
        new, delta = apply_edge_updates(a, inserts=inserts, deletes=deletes)
        stats = self._engines[name].apply_edge_update(a, new, delta)
        self._graphs[name] = new
        dropped = 0
        if stats.stale_keys:
            if self.cache is not None:
                dropped = self.cache.invalidate_keys(stats.stale_keys)
            if self.directory is not None:
                for key in stats.stale_keys:
                    self.directory.drop(key)
        # Cost memos price segment count and nnz — both may have changed.
        self._pass_costs = {k: v for k, v in self._pass_costs.items()
                            if k[0] != name}
        return GraphUpdateReport(
            graph=name, delta=delta, plans_updated=stats.plans_updated,
            segments_retiled=stats.segments_retiled,
            segments_reused=stats.segments_reused,
            retiled_bytes=stats.retiled_bytes,
            stale_keys=len(stats.stale_keys),
            cache_entries_dropped=dropped,
            wall_seconds=time.perf_counter() - t0)

    @property
    def graphs(self) -> List[str]:
        return list(self._graphs)

    def cache_stats(self) -> Optional[CacheStats]:
        return self.cache.stats if self.cache is not None else None

    # ---- brick checkpointing + warm start --------------------------------
    #
    # Cache keys are content-addressed (csr_fingerprint namespaces), so the
    # bricks one serving process checkpoints are the bricks the next
    # process's streams will look up — warm start survives restarts.

    def checkpoint_cache(self, directory: str, step: int = 0) -> str:
        """Persist the segment cache's bricks (both tiers) for warm_start.

        Only engine-format entries — the `BrickPayload` `AiresSpGEMM`
        streams — are checkpointed; anything else sharing the cache is
        skipped.
        """
        if self.cache is None:
            raise ValueError("cache_enabled=False: nothing to checkpoint")
        bricks = []
        for key, value, nbytes in self.cache.export_entries():
            if not isinstance(value, BrickPayload):
                continue
            ell = value.ell
            meta = {
                "graph_id": key.graph_id,
                "segment_id": key.segment_id,
                "wire_format": key.wire_format,
                "shape": list(key.shape),
                "fingerprint": key.fingerprint,
                "nbytes": int(nbytes),
                "bm": ell.bm, "bk": ell.bk,
                "n_rows": ell.n_rows, "n_cols": ell.n_cols,
            }
            bricks.append((meta, {"blocks": np.asarray(ell.blocks),
                                  "col_tile": np.asarray(ell.col_tile),
                                  "n_tiles": np.asarray(ell.n_tiles)}))
        return save_segment_bricks(directory, bricks, step=step)

    def warm_start(self, checkpoint_dir: str) -> WarmStartReport:
        """Pre-populate the segment cache from checkpointed bricks.

        Every restored brick is charged through the engine's
        `TieredMemorySystem` — one storage→host read plus one host→device
        upload — so the first epoch's `tms.bytes_by_path()` stays honest:
        warm-started bricks were not free, they crossed the bus before the
        first request arrived (just not inside any request's latency).
        """
        if self.cache is None:
            raise ValueError("cache_enabled=False contradicts warm_start")
        report = WarmStartReport()
        for meta, arrays in load_segment_bricks(checkpoint_dir):
            ell = BlockELL(
                blocks=arrays["blocks"], col_tile=arrays["col_tile"],
                n_tiles=arrays["n_tiles"], bm=int(meta["bm"]),
                bk=int(meta["bk"]), n_rows=int(meta["n_rows"]),
                n_cols=int(meta["n_cols"]))
            # `fingerprint` absent in pre-delta checkpoints: restore with ""
            # — such keys simply miss (and re-stream) under the
            # fingerprint-bearing keys current plans emit.
            key = SegmentKey(meta["graph_id"], meta["segment_id"],
                             meta["wire_format"], tuple(meta["shape"]),
                             fingerprint=meta.get("fingerprint", ""))
            nbytes = int(meta["nbytes"])
            report.modeled_seconds += self.tms.transfer(
                Path.STORAGE_HOST, MemoryTier.STORAGE, MemoryTier.HOST,
                nbytes, tag="warmstart/load")
            report.modeled_seconds += self.tms.transfer(
                Path.DMA, MemoryTier.HOST, MemoryTier.DEVICE,
                nbytes, tag="warmstart/promote")
            self.cache.put(key, AiresSpGEMM.device_payload(ell), nbytes,
                           tms=self.tms)
            report.bricks += 1
            report.wire_bytes += nbytes
        return report

    # ---- admission control (satellite of the pipeline-IR tentpole) -------

    def cost_spec(self) -> TierSpec:
        """The `TierSpec` every cost estimate prices against. Without a
        calibrator this is the configured spec, bit-exactly. With one,
        it is `calibrator.calibrated(tier_spec)`; and whenever the
        calibrator's generation has moved since the memos were priced,
        the `_pass_costs` memo is dropped and every queued request whose
        estimate an admission policy already filled is repriced — EDF
        order and `max_queue_cost_s` backpressure see the new costs on
        the very next decision."""
        cal = self.config.calibrator
        if cal is None:
            return self.config.tier_spec
        if cal.generation != self._cost_generation:
            # Mark current *first*: repricing below re-enters cost_spec()
            # via estimate_request_cost, which must not recurse.
            self._cost_generation = cal.generation
            self._pass_costs.clear()
            self._queue = [
                dataclasses.replace(
                    r, estimated_cost_s=self.estimate_request_cost(r))
                if r.estimated_cost_s > 0.0 else r
                for r in self._queue]
        return cal.calibrated(self.config.tier_spec)

    def _pass_cost(self, name: str, width: int,
                   spec: Optional[TierSpec] = None) -> float:
        """Modeled makespan of one streamed aggregation pass at `width`,
        via the engine's own `PipelinePlan.estimate()` (cold-cache reading:
        admission must hold even if the cache is evicted underneath the
        queue). Memoized under the current `cost_spec()` — the plan is
        pinned per graph, so the estimate only varies with the feature
        width (and the calibration generation, which clears the memo).
        An explicit `spec` bypasses the memo entirely — that is how
        callers compare calibrated vs uncalibrated pricing."""
        if spec is not None:
            a = self._graphs[name]
            plan = self._engines[name].stream_plan(
                a, (a.n_rows, int(width)), spec=spec)
            return plan.estimate(spec).makespan_s
        # cost_spec() first: a generation move clears the memo below.
        sp = self.cost_spec()
        key = (name, int(width))
        if key not in self._pass_costs:
            a = self._graphs[name]
            plan = self._engines[name].stream_plan(
                a, (a.n_rows, int(width)), spec=sp)
            self._pass_costs[key] = plan.estimate(sp).makespan_s
        return self._pass_costs[key]

    def estimate_request_cost(self, request: InferenceRequest,
                              spec: Optional[TierSpec] = None) -> float:
        """Modeled seconds to serve `request`: one streamed pass per layer,
        each at that layer's activation width. `spec` pins the pricing
        spec (unmemoized); default is the calibrated `cost_spec()`."""
        return sum(self._pass_cost(request.graph, wd, spec=spec)
                   for wd in request.stream_widths())

    def estimate_group_cost(self, name: str, group: Sequence[InferenceRequest]
                            ) -> float:
        """Modeled seconds for one column-concat group of requests against
        `name`: mirrors `_batched_aggregate`'s greedy chunking exactly —
        per layer level, live request widths pack into passes capped at
        `max_batch_features`, each pass priced by the memoized
        `PipelinePlan.estimate()` cost at its concatenated width. This is
        the per-group cost the continuous loop's queue-position EDF
        accumulates into time-to-front. A GAT request streams alone."""
        cap = self.config.max_batch_features
        per_req: List[List[int]] = []
        total = 0.0
        for r in group:
            if r.model is not None:
                total += sum(self._pass_cost(name, w)
                             for w in r.stream_widths())
            else:
                per_req.append(r.stream_widths())
        for layer in range(max((len(lv) for lv in per_req), default=0)):
            width = 0
            for lv in per_req:
                if layer >= len(lv):
                    continue
                f = lv[layer]
                if width and width + f > cap:
                    total += self._pass_cost(name, width)
                    width = 0
                width += f
            if width:
                total += self._pass_cost(name, width)
        return total

    def queued_cost_s(self) -> float:
        """Estimated cost of everything still awaiting service. In the
        round engine the queue empties only at a drain; under the
        continuous loop served groups leave it step by step, so the
        `max_queue_cost_s` backpressure prices the *remaining* queue, not
        a round snapshot."""
        if self.config.calibrator is not None:
            self.cost_spec()  # reprice stale entries before summing
        return sum(r.estimated_cost_s for r in self._queue)

    def feed_latencies(self, latencies: Sequence[RequestLatency]) -> int:
        """Feed one batch's `RequestLatency` stream into the configured
        calibrator (no-op without one). `run_batch` calls this after every
        drain; the continuous loop (`ContinuousServer.step`) calls it per
        served group. Returns the number of samples folded in."""
        cal = self.config.calibrator
        if cal is None or not latencies:
            return 0
        return cal.observe_batch(latencies)

    # ---- autotuned schedules (repro.core.autotune) ------------------------

    def autotune(self, name: str, width: Optional[int] = None,
                 install: bool = False) -> TunedSchedule:
        """Search (coalescing min_bytes × pass order × ELL bucket set) for
        one registered graph, priced under the calibrated `cost_spec()`;
        optionally install the winner. Never predicted worse than default
        (the default arm is always a candidate)."""
        if name not in self._graphs:
            raise KeyError(f"graph {name!r} not registered")
        tuned = autotune_schedule(
            self._engines[name], self._graphs[name], graph=name,
            width=int(width or self.config.max_batch_features),
            spec=self.cost_spec(), segment_cache=self.cache)
        if install:
            self.install_schedule(tuned)
        return tuned

    def install_schedule(self, tuned: TunedSchedule) -> None:
        """Install an autotuned schedule for `tuned.graph`: that graph's
        `AiresSpGEMM` gets its own `PassPipeline` in tuned order (other
        graphs keep the shared engine pipeline), a changed ELL bucket set
        drops the graph's prepared plans and cached bricks (its cache
        namespaces carry a bucket tag, so stale default-bucket entries
        are reclaimed, not shadowed), and the graph's cost memos are
        invalidated so admission prices the tuned plans."""
        name = tuned.graph
        if name not in self._graphs:
            raise KeyError(f"graph {name!r} not registered")
        eng = self._engines[name]
        eng.plan_passes = PassPipeline(
            tuned.build_passes(), spec=self.config.tier_spec,
            track_costs=False)
        changed = False
        new_buckets = (list(tuned.ell_buckets)
                       if tuned.ell_buckets is not None else None)
        if new_buckets != (eng.config.ell_buckets or None):
            eng.config = dataclasses.replace(eng.config,
                                             ell_buckets=new_buckets)
            changed = True
        # A changed cluster count re-partitions the graph (same clustering
        # the autotuner's trial arm priced); like a bucket change, the old
        # namespaces (different `:p` tag) are reclaimed, not shadowed.
        old_clusters = (eng.partition.n_clusters
                        if eng.partition is not None else None)
        if tuned.partition_clusters != old_clusters:
            if tuned.partition_clusters is None:
                eng.partition = None
            else:
                eng.partition = partition_graph(
                    self._graphs[name], int(tuned.partition_clusters),
                    n_shards=int(getattr(self.cache, "n_shards", 1) or 1),
                    topology=self.config.ici_topology,
                    local_shard=int(getattr(self.cache, "local_shard", 0)))
            changed = True
        if changed:
            eng.clear_cache()
            if self.cache is not None:
                self.cache.invalidate_prefix(
                    AiresSpGEMM.graph_cache_prefix(self._graphs[name]))
        self._pass_costs = {k: v for k, v in self._pass_costs.items()
                            if k[0] != name}
        self._installed_schedules[name] = tuned

    @property
    def installed_schedules(self) -> Dict[str, TunedSchedule]:
        return dict(self._installed_schedules)

    def _reject(self, request: InferenceRequest, reason: str,
                est: float) -> None:
        decision = RejectedRequest(
            graph=request.graph, reason=reason, estimated_cost_s=est,
            deadline_s=request.deadline_s, request_id=request.request_id)
        self._rejected.append(decision)
        raise AdmissionError(decision)

    # ---- request queue ---------------------------------------------------

    def submit(self, request: InferenceRequest) -> SubmitReceipt:
        """Queue a request; returns its id as a `SubmitReceipt` (an int)
        carrying the admission-control cost prediction, so callers see the
        latency estimate the engine already computed for them."""
        if request.graph not in self._graphs:
            raise KeyError(f"graph {request.graph!r} not registered")
        n = self._graphs[request.graph].n_rows
        if request.features.shape[0] != n:
            raise ValueError(
                f"features rows {request.features.shape[0]} != graph nodes {n}")
        cap = self.config.max_queue_cost_s
        est = 0.0
        if request.deadline_s is not None or cap is not None:
            # Price the request only when an admission policy can act on
            # it: the estimate's first call per (graph, width) runs RoBW +
            # densification, which must not tax submit() latency for
            # deployments that never set a deadline or a queue cap.
            est = self.estimate_request_cost(request)
        if request.deadline_s is not None and est > request.deadline_s:
            self._reject(request, "deadline-infeasible", est)
        if cap is not None and self.queued_cost_s() + est > cap:
            self._reject(request, "queue-full", est)
        request = dataclasses.replace(
            request, request_id=self._next_id, estimated_cost_s=est,
            submitted_s=self.clock())
        self._next_id += 1
        self._queue.append(request)
        return SubmitReceipt(request.request_id, est)

    def infer(self, graph: str, features: np.ndarray,
              weights: Sequence[np.ndarray] = (),
              deadline_s: Optional[float] = None) -> np.ndarray:
        """Convenience: run one request immediately, without draining (or
        disturbing) other callers' queued requests.

        Admission verdicts accumulated from *other* callers' submits since
        the last batch are stashed across the internal drain and restored
        for the next real `run_batch` report — they must not vanish into
        the private report this method discards. If this request itself
        cannot produce a result (its own deadline expired before the
        internal batch ran), an `AdmissionError` naming the expiry is
        raised instead of an opaque `StopIteration`.
        """
        pending, self._queue = self._queue, []
        foreign, self._rejected = self._rejected, []
        try:
            rid = self.submit(InferenceRequest(graph, features, weights,
                                               deadline_s=deadline_s))
            report = self.run_batch()
        finally:
            # Restore other callers' state: their queued requests, and the
            # verdicts whose BatchReport has not happened yet (plus this
            # call's own submit-rejection, if submit() raised above — that
            # verdict surfaces in the next real report, as usual).
            self._queue = pending + self._queue
            self._rejected = foreign + self._rejected
        for r in report.results:
            if r.request_id == rid:
                return r.output
        for verdict in report.expired:
            if verdict.request_id == rid:
                raise AdmissionError(verdict)
        raise RuntimeError(
            f"infer request {int(rid)} on graph {graph!r} produced no "
            f"result and no expiry verdict — the internal batch returned "
            f"{len(report.results)} result(s) for other ids")

    # ---- batched execution -----------------------------------------------
    #
    # run_batch() is a composition of three reusable pieces — group-form
    # (`prepare_queue` + `order_queue`), group-run (`serve_group`) — which
    # the continuous serving loop (repro.runtime.serving_loop) drives one
    # group at a time instead of as a full drain.

    def prepare_queue(self, queue: List[InferenceRequest], now: float
                      ) -> Tuple[List[InferenceRequest],
                                 List[RejectedRequest]]:
        """Group-form step 1: stamp, expire, price. Returns the serve-ready
        queue (new `InferenceRequest` copies — caller-held objects are
        never mutated) and the expiry verdicts.

          * a request that reached the queue without passing ``submit()``
            (e.g. an `evict_graph` orphan re-queued directly) still holds
            the ``submitted_s = -1.0`` sentinel; it is stamped `now` on
            first sight so its relative deadline starts counting here
            instead of instantly expiring against the monotonic epoch;
          * a request whose relative deadline passed while it waited is
            dropped, not run — it could only waste the batch's budget
            producing an answer nobody can use;
          * requests no admission policy already priced get their
            `estimated_cost_s` filled via `dataclasses.replace` — the
            estimate shares the plan preparation the stream needs anyway
            (memoized per graph × width). If the calibrator moved since
            the queue was priced, *every* entry is repriced — `queue`
            was detached from `self._queue` by the caller, so the
            generation sweep in `cost_spec()` cannot reach it.
        """
        stale = False
        cal = self.config.calibrator
        if cal is not None and cal.generation != self._cost_generation:
            self.cost_spec()
            stale = True
        ready: List[InferenceRequest] = []
        expired: List[RejectedRequest] = []
        for r in queue:
            if r.submitted_s < 0.0:
                r = dataclasses.replace(r, submitted_s=now)
            if r.deadline_s is not None and now - r.submitted_s > r.deadline_s:
                expired.append(RejectedRequest(
                    graph=r.graph, reason="deadline-expired",
                    estimated_cost_s=r.estimated_cost_s,
                    deadline_s=r.deadline_s, request_id=r.request_id))
                continue
            if r.estimated_cost_s <= 0.0 or stale:
                r = dataclasses.replace(
                    r, estimated_cost_s=self.estimate_request_cost(r))
            ready.append(r)
        return ready, expired

    def order_queue(self, queue: List[InferenceRequest]
                    ) -> Tuple[List[InferenceRequest], List[str]]:
        """Group-form step 2: deadline-aware ordering. An EDFOrderingPass
        in the configured pipeline reorders the queue (earliest deadline
        first, Moore–Hodgson tardy demotion over `estimated_cost_s`), and
        graph groups then run in first-appearance order of that queue.
        Without an ordering pass, registration order — byte-identical to
        the pre-pass engine."""
        if (self.plan_pipeline is not None
                and self.plan_pipeline.orders_requests):
            queue = self.plan_pipeline.order_requests(queue)
            return queue, list(dict.fromkeys(r.graph for r in queue))
        return queue, list(self._graphs)  # registration order

    def run_batch(self) -> BatchReport:
        """Drain the queue: group by graph, batch aggregations per layer."""
        queue, self._queue = self._queue, []
        results: List[InferenceResult] = []
        t0 = time.perf_counter()
        unknown = sorted({r.graph for r in queue} - set(self._graphs))
        if unknown:
            self._queue = queue + self._queue  # nothing consumed
            raise KeyError(
                f"queued requests reference unregistered graphs {unknown}")
        queue, expired = self.prepare_queue(queue, self.clock())
        queue, graph_order = self.order_queue(queue)
        totals = GroupStats()
        latency: List[RequestLatency] = []
        # Duplicate-avoided demotions happen inside put()/evictions, outside
        # any stream's stats window — diff the cache's cumulative counter.
        dup0 = (self.cache.stats.duplicate_avoided_bytes
                if self.cache is not None else 0)
        for name in graph_order:
            group = [r for r in queue if r.graph == name]
            if not group:
                continue
            group_results, done_s, stats = self.serve_group(name, group, t0)
            results.extend(group_results)
            latency.extend(
                RequestLatency(r.request_id, name, r.estimated_cost_s,
                               *done_s[r.request_id])
                for r in group)
            totals.merge(stats)
        results.sort(key=lambda r: r.request_id)
        latency.sort(key=lambda l: l.request_id)
        self.feed_latencies(latency)
        dup = ((self.cache.stats.duplicate_avoided_bytes - dup0)
               if self.cache is not None else 0)
        rejected, self._rejected = self._rejected, []
        return BatchReport(
            results=results, uploaded_bytes=totals.uploaded_bytes,
            cache_hit_bytes=totals.cache_hit_bytes,
            promoted_bytes=totals.promoted_bytes,
            segments_streamed=totals.segments_streamed,
            aggregation_passes=totals.aggregation_passes,
            wall_seconds=time.perf_counter() - t0,
            ici_bytes=totals.ici_bytes,
            directory_hit_bytes=totals.directory_hit_bytes,
            duplicate_avoided_bytes=dup,
            demoted_bytes=totals.demoted_bytes,
            demote_copied_bytes=totals.demote_copied_bytes,
            rejected=rejected, expired=expired, request_latency=latency)

    def serve_group(self, name: str, group: List[InferenceRequest],
                    t0: float) -> tuple:
        """Group-run: serve one graph's requests through the column-concat
        streamed passes; returns (results, completion stamps keyed by
        request id — `(since_batch_t0, since_group_start)` wall seconds,
        taken when each request's output materializes on host — and the
        group's `GroupStats` byte accounting)."""
        with span("engine.group", graph=name, requests=len(group)):
            return self._serve_group(name, group, t0)

    def _serve_group(self, name: str, group: List[InferenceRequest],
                     t0: float) -> tuple:
        a = self._graphs[name]
        eng = self._engines[name]
        mark = len(eng.forward_stats_log)
        g0 = time.perf_counter()
        # Per-request device-resident state: (request, activation, next layer).
        with span("engine.inputs"):
            acts = [jnp.asarray(np.asarray(r.features, dtype=np.float32))
                    for r in group]
            wss = [[jax.tree_util.tree_map(
                        lambda x: jnp.asarray(np.asarray(x, np.float32)), w)
                    for w in r.weights] for r in group]
        outputs: Dict[int, np.ndarray] = {}
        done_s: Dict[int, tuple] = {}

        def finish(i: int) -> None:
            with span("engine.readback", request=group[i].request_id):
                outputs[i] = np.asarray(acts[i])
            now = time.perf_counter()
            done_s[group[i].request_id] = (now - t0, now - g0)

        # GCN requests go layer by layer, their aggregations merged into
        # column-concat passes.
        gcns = [i for i, r in enumerate(group) if r.model is None]
        n_aggs = {i: max(len(wss[i]), 1) for i in gcns}
        for layer in range(max(n_aggs.values(), default=0)):
            live = [i for i in gcns if layer < n_aggs[i]]
            aggregated = self._batched_aggregate(
                eng, a, [acts[i] for i in live])
            for i, x in zip(live, aggregated):
                ws = wss[i]
                if layer < len(ws):
                    with span("engine.combine",
                              request=group[i].request_id):
                        acts[i] = gcn.serve_combine(x, ws, layer)
                else:                             # bare aggregation request
                    acts[i] = x
                if layer == n_aggs[i] - 1:
                    finish(i)
        # A GAT request streams alone, so it runs its layers in turn:
        # only one request's activations are held at a time.
        for i, r in enumerate(group):
            if r.model is None:
                continue
            for layer in range(len(wss[i])):
                acts[i] = self._gat_layer(eng, a, r, acts[i], wss[i], layer)
            finish(i)
        results = [InferenceResult(group[i].request_id, name, outputs[i])
                   for i in range(len(group))]
        stats = GroupStats()
        for s in eng.forward_stats_log[mark:]:
            stats.accumulate(s)
        return results, done_s, stats

    @staticmethod
    def _gat_layer(eng: AiresSpGEMM, a: CSR, request: InferenceRequest, h,
                   params: list, layer: int):
        """One GAT layer of `request`: project and score, the streamed
        attention, then the heads' combination, skip and ELU."""
        cfg, rid = request.model, request.request_id
        with span("engine.project", request=rid):
            z, s_src, s_dst = gat.project(cfg, layer, params[layer], h)
        x = eng.attend(a, z, s_src, s_dst, cfg.heads[layer],
                       cfg.negative_slope)
        with span("engine.combine", request=rid):
            return gat.combine(cfg, layer, x, h)

    def _batched_aggregate(self, eng: AiresSpGEMM, a: CSR,
                           hs: List[jnp.ndarray]) -> List[jnp.ndarray]:
        """A @ each h, merging requests into column-concat streamed passes.

        Greedy chunking: pack requests into passes while the concatenated
        width stays within max_batch_features; a single over-wide request
        streams alone (AiresSpGEMM re-plans conservatively for it).
        """
        cap = self.config.max_batch_features
        out: List[Optional[jnp.ndarray]] = [None] * len(hs)
        chunk: List[int] = []
        width = 0
        for i, h in enumerate(hs):
            f = int(h.shape[1])
            if chunk and width + f > cap:
                self._aggregate_chunk(eng, a, hs, chunk, out)
                chunk, width = [], 0
            chunk.append(i)
            width += f
        if chunk:
            self._aggregate_chunk(eng, a, hs, chunk, out)
        return out

    @staticmethod
    def _aggregate_chunk(eng, a, hs, chunk, out) -> None:
        if len(chunk) == 1:
            out[chunk[0]] = eng(a, hs[chunk[0]])
            return
        h_cat = jnp.concatenate([hs[i] for i in chunk], axis=1)
        x_cat = eng(a, h_cat)
        col = 0
        for i in chunk:
            f = int(hs[i].shape[1])
            out[i] = x_cat[:, col:col + f]
            col += f
