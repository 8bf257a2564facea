"""Fig. 9 — per-epoch latency across GCN feature sizes 16..256.

Paper claim: AIRES's speedup is consistent across model configurations.

`--cache` adds the tiered-segment-cache ablation arm: two consecutive
epochs of the AIRES scheduler sharing one cache — the second epoch's
Phase II DMA drops to cache promotions only, and the row reports its
makespan plus the wire bytes the cache kept off the bus.

`--passes` adds the plan-rewrite ablation arm (repro.core.passes): the
same warm-epoch runs routed through a PassPipeline — shard-aware RoBW
placement (with `--shards`: warm ici_bytes must come out strictly lower
than the pass-free shard arm, the ISSUE 5 acceptance metric) plus
transfer coalescing.

`--partition` (with `--shards`) adds a partition-aware owner-map arm:
the scheduler tiles RoBW over LDG cluster boundaries and installs a
cluster->shard owner map, so warm-epoch remote hits concentrate on
near shards instead of the CRC-uniform spread (repro.sparse.partition).
"""
from __future__ import annotations

import argparse
from typing import List

import numpy as np

from benchmarks.common import SCALE, budget_for, csv_row, dataset, feature_spec
from repro.core import (
    PassPipeline,
    SCHEDULERS,
    ShardPlacementPass,
    TransferCoalescingPass,
    gcn_epoch,
)
from repro.io import ShardedSegmentCache, TieredSegmentCache
from repro.io.tiers import PAPER_GPU_SYSTEM
from repro.launch.compile_cache import enable_compile_cache
from repro.sparse.partition import partition_graph

DATASET = "kV2a"
FEATURE_SIZES = [16, 32, 64, 128, 256]


def _pass_pipeline() -> PassPipeline:
    return PassPipeline([ShardPlacementPass(), TransferCoalescingPass()],
                        spec=PAPER_GPU_SYSTEM)


def run(cache: bool = False, shards: int = 0,
        passes: bool = False, partition: bool = False) -> List[str]:
    rows = [f"# fig9 feature-size ablation on {DATASET} (scale={SCALE})"]
    a = dataset(DATASET)
    part = (partition_graph(a, 2 * shards, n_shards=shards)
            if partition and shards else None)
    for f in FEATURE_SIZES:
        feat = feature_spec(a, f)
        budget = budget_for(DATASET, a, feat)
        spans = {}
        for sched in ("maxmemory", "etc", "aires"):
            em = gcn_epoch(a, feat, [np.zeros((f, f))] * 2, sched,
                           PAPER_GPU_SYSTEM, budget, dataset=DATASET)
            spans[sched] = em.epoch_makespan_s
        rows.append(csv_row(
            f"fig9/F{f}/aires", spans["aires"] * 1e6,
            f"speedup_vs_maxmem={spans['maxmemory']/spans['aires']:.2f}"
            f";vs_etc={spans['etc']/spans['aires']:.2f}"))
        if cache:
            # Cache device tier sized at the streaming budget — i.e. the
            # ablation models an operator dedicating as much spare HBM
            # again to brick retention (see TieredSegmentCache docstring:
            # the tier is spare memory beyond the Eq. 5-7 working set).
            rows.append(_warm_epoch_row(
                a, feat, budget, TieredSegmentCache(device_budget_bytes=budget),
                f"fig9/F{f}/aires+cache"))
            if passes:
                rows.append(_warm_epoch_row(
                    a, feat, budget,
                    TieredSegmentCache(device_budget_bytes=budget),
                    f"fig9/F{f}/aires+cache+passes",
                    passes=_pass_pipeline()))
        if shards:
            # Mesh-sharded device tier: each shard retains 1/shards of the
            # plan; warm-epoch remote hits ride ICI (cheap) instead of the
            # PCIe-class DMA re-upload — the fig9 scale-out arm.
            rows.append(_warm_epoch_row(
                a, feat, budget,
                ShardedSegmentCache(device_budget_bytes=budget,
                                    n_shards=shards),
                f"fig9/F{f}/aires+cache{shards}shard", ici=True))
            if passes:
                # Placement pass: the plan's bricks are pinned to the shard
                # that streams them — warm ici_bytes strictly below the
                # pass-free row above (the acceptance comparison).
                rows.append(_warm_epoch_row(
                    a, feat, budget,
                    ShardedSegmentCache(device_budget_bytes=budget,
                                        n_shards=shards),
                    f"fig9/F{f}/aires+cache{shards}shard+passes", ici=True,
                    passes=_pass_pipeline()))
            if part is not None:
                # Partition-aware owners: connectivity-clustered bricks
                # co-located on their cluster's shard — warm ici_bytes
                # drop from topology (vs the CRC shard row above).
                rows.append(_warm_epoch_row(
                    a, feat, budget,
                    ShardedSegmentCache(device_budget_bytes=budget,
                                        n_shards=shards),
                    f"fig9/F{f}/aires+cache{shards}shard+partition",
                    ici=True, partition=part))
    return rows


def _warm_epoch_row(a, feat, budget, seg_cache, label, ici=False,
                    passes=None, partition=None) -> str:
    """Two consecutive AIRES epochs sharing `seg_cache`; report the warm one."""
    sched = SCHEDULERS["aires"](PAPER_GPU_SYSTEM, device_budget=budget,
                                segment_cache=seg_cache, passes=passes,
                                partition=partition)
    warm = cold = None
    for _ in range(2):  # epoch 1 fills, epoch 2 hits
        cold, warm = warm, sched.run(a, feat, dataset=DATASET).metrics
    derived = (f"hit_bytes={warm.cache_hit_bytes}"
               f";dma_bytes={warm.bytes_by_path.get('dma', 0)}")
    if ici:
        derived += f";ici_bytes={warm.bytes_by_path.get('ici', 0)}"
    derived += f";speedup_vs_cold={cold.makespan_s/warm.makespan_s:.2f}"
    return csv_row(label, warm.makespan_s * 1e6, derived)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cache", action="store_true",
                    help="add the tiered-segment-cache warm-epoch arm")
    ap.add_argument("--shards", type=int, default=0,
                    help="add a mesh-sharded cache arm with this many shards")
    ap.add_argument("--passes", action="store_true",
                    help="add plan-rewrite-pass arms (shard placement + "
                         "transfer coalescing) next to the cache/shard arms")
    ap.add_argument("--partition", action="store_true",
                    help="add a partition-aware owner-map arm next to the "
                         "shard arm (requires --shards)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    print("\n".join(run(cache=args.cache, shards=args.shards,
                        passes=args.passes, partition=args.partition)))


if __name__ == "__main__":
    main()
