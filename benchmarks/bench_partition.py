"""Partition-aware sharding bench — connectivity-clustered vs CRC owners.

Serves GCN epochs against a stochastic-block-model graph (the clustered
community structure `repro.sparse.partition` exploits) on a 4-shard ring
cache through two arms built on identical engines, budgets and passes
(ShardPlacementPass enabled in both):

  * crc       — the default owner map: `shard_of` CRC-hashes every
                segment key, spreading bricks uniformly over the mesh.
                A warm epoch ships ~(S-1)/S of the working set over ICI
                at ring-average hop distance.
  * partition — `EngineConfig.partition_shards` clusters the CSR
                adjacency (LDG, 2x-shards clusters), RoBW tiles over the
                cluster boundaries, and the cluster->shard map packs
                nnz-heavy clusters onto the nearest shards first under a
                1.5x balance cap. Warm-epoch ICI bytes drop from
                *topology*: co-clustered bricks live local or one hop
                away instead of uniformly spread.

Outputs must be bit-identical across arms (cluster-aligned RoBW segments
still hold complete rows), and the partitioned arm's warm-epoch
`ici_bytes` must come out strictly below CRC's — the ISSUE acceptance
metric. Writes BENCH_partition.json.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

import numpy as np

from benchmarks.common import SCALE
from repro.core import ShardPlacementPass, plan_memory_dense_features
from repro.data import generate_sbm_graph, normalized_adjacency
from repro.io.tiers import ICI_RING
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import EngineConfig, InferenceRequest, ServingEngine

N_VERTICES = max(2_048, int(4_000_000 * SCALE))
N_EDGES = max(16_384, int(60_000_000 * SCALE))
N_BLOCKS = 8               # SBM communities = cluster count below
P_IN = 0.9                 # fraction of edges confined to their block
SHARDS = 4                 # ring: hops from shard 0 are [0, 1, 2, 1]
CLUSTERS = 2 * SHARDS      # >shards so the nnz-balanced packing can skew
WIDTH = 32                 # request feature width
HIDDEN = 16                # single GCN layer, WIDTH -> HIDDEN
EPOCHS = 4                 # epoch 1 fills the cache; report the last
SEG_FRAC = 24              # stream budget sized for ~SEG_FRAC segments

EPOCH_KEYS = ("uploaded_bytes", "cache_hit_bytes", "promoted_bytes",
              "ici_bytes", "segments_streamed")


def sbm_graph():
    return normalized_adjacency(generate_sbm_graph(
        N_VERTICES, N_EDGES, n_blocks=N_BLOCKS, p_in=P_IN, seed=0))


def stream_budget(a) -> int:
    est = plan_memory_dense_features(a, a.n_rows, WIDTH, float("inf"))
    return int(est.m_b + est.m_c + a.nbytes() / SEG_FRAC)


def build_workload(a, seed: int):
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((a.n_rows, WIDTH)).astype(np.float32)
    w = [rng.standard_normal((WIDTH, HIDDEN)).astype(np.float32)]
    return h, w


def make_engine(a, budget: int, cache_bytes: int,
                partitioned: bool) -> ServingEngine:
    eng = ServingEngine(EngineConfig(
        device_budget_bytes=budget,
        cache_device_bytes=cache_bytes,
        cache_shards=SHARDS,
        ici_topology=ICI_RING,
        plan_passes=[ShardPlacementPass()],
        max_batch_features=WIDTH,
        partition_shards=CLUSTERS if partitioned else 0))
    eng.register_graph("g", a)
    return eng


def epoch(eng: ServingEngine, h, w):
    eng.submit(InferenceRequest("g", h, w))
    return eng.run_batch()


def measure_wire_bytes(a, budget: int) -> Dict[str, int]:
    """One unsharded cold epoch: the graph's total brick bytes W (what
    both arms' aggregate cache budget is sized to, so each shard holds
    ~W/SHARDS and neither arm can simply pin the whole plan locally)."""
    probe = ServingEngine(EngineConfig(device_budget_bytes=budget,
                                       max_batch_features=WIDTH))
    probe.register_graph("g", a)
    h, w = build_workload(a, seed=0)
    cold = epoch(probe, h, w)
    return {
        "wire_total_bytes": int(cold.uploaded_bytes),
        "segments": int(cold.segments_streamed
                        // max(1, cold.aggregation_passes)),
    }


def run_arm(a, budget: int, cache_bytes: int, h, w,
            partitioned: bool):
    eng = make_engine(a, budget, cache_bytes, partitioned)
    epochs: List[Dict[str, int]] = []
    outputs: List[np.ndarray] = []
    for _ in range(EPOCHS):
        rep = epoch(eng, h, w)
        outputs.append(np.asarray(rep.results[0].output))
        epochs.append({
            "uploaded_bytes": rep.uploaded_bytes,
            "cache_hit_bytes": rep.cache_hit_bytes,
            "promoted_bytes": rep.promoted_bytes,
            "ici_bytes": rep.ici_bytes,
            "segments_streamed": rep.segments_streamed,
        })
    summary = {"epochs": epochs, "warm": epochs[-1],
               "cold_uploaded_bytes": epochs[0]["uploaded_bytes"]}
    if partitioned:
        part = eng._engines["g"].partition
        summary["partition"] = {
            "n_clusters": part.n_clusters,
            "shard_nnz": [int(x) for x in part.shard_nnz],
        }
    return summary, outputs


def validate_report(report: Dict[str, object]) -> None:
    """Schema + acceptance check for BENCH_partition.json (CI smoke)."""
    for key in ("scale", "graph", "seed", "shards", "clusters", "arms",
                "outputs_bitwise_equal"):
        assert key in report, f"missing top-level key {key!r}"
    for key in ("n_rows", "nnz", "n_blocks", "segments",
                "wire_total_bytes"):
        assert key in report["graph"], f"graph missing {key!r}"
    assert set(report["arms"]) == {"crc", "partition"}
    for arm, summary in report["arms"].items():
        assert len(summary["epochs"]) == EPOCHS, arm
        for entry in summary["epochs"]:
            for k in EPOCH_KEYS:
                assert isinstance(entry.get(k), int), (arm, k)
        assert summary["cold_uploaded_bytes"] > 0, arm
    part = report["arms"]["partition"]
    assert part["partition"]["n_clusters"] == report["clusters"]
    # Same math, different owners: outputs are bit-identical per epoch.
    assert report["outputs_bitwise_equal"] is True
    # The headline acceptance: clustering the owner map cuts warm-epoch
    # ICI bytes strictly, from topology alone (same passes, same cache
    # budget, same graph — only who owns each brick changed).
    crc_ici = report["arms"]["crc"]["warm"]["ici_bytes"]
    part_ici = part["warm"]["ici_bytes"]
    assert crc_ici > 0, "CRC arm shipped nothing over ICI — cache too big?"
    assert part_ici < crc_ici, (
        f"partitioned owners must beat CRC: {part_ici} >= {crc_ici}")


def _jsonable(obj):
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    return obj


def run(seed: int) -> Dict[str, object]:
    a = sbm_graph()
    budget = stream_budget(a)
    h, w = build_workload(a, seed)
    probe = measure_wire_bytes(a, budget)
    # Aggregate cache budget = the plan's wire bytes: each of the 4
    # shards holds ~W/4, so placement cannot pin the whole working set
    # on the local shard and the owner map decides who pays ICI.
    cache_bytes = probe["wire_total_bytes"]

    crc, crc_out = run_arm(a, budget, cache_bytes, h, w, partitioned=False)
    part, part_out = run_arm(a, budget, cache_bytes, h, w, partitioned=True)
    identical = all(np.array_equal(x, y)
                    for x, y in zip(crc_out, part_out))

    report = {
        "scale": SCALE,
        "seed": seed,
        "shards": SHARDS,
        "clusters": CLUSTERS,
        "graph": {
            "name": "sbm", "n_rows": a.n_rows, "nnz": a.nnz,
            "n_blocks": N_BLOCKS, "p_in": P_IN,
            "segments": probe["segments"],
            "wire_total_bytes": probe["wire_total_bytes"],
        },
        "cache_device_bytes": cache_bytes,
        "arms": {"crc": crc, "partition": part},
        "outputs_bitwise_equal": bool(identical),
    }
    return _jsonable(report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default="BENCH_partition.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = run(args.seed)
    validate_report(report)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")

    g = report["graph"]
    print(f"sbm graph: {g['n_rows']} rows, {g['nnz']} nnz, "
          f"{g['n_blocks']} blocks, {g['segments']} segments, "
          f"wire={g['wire_total_bytes']}")
    for arm in ("crc", "partition"):
        warm = report["arms"][arm]["warm"]
        print(f"{arm:9s} warm epoch: ici={warm['ici_bytes']} "
              f"hits={warm['cache_hit_bytes']} "
              f"promoted={warm['promoted_bytes']} "
              f"uploaded={warm['uploaded_bytes']}")
    crc_ici = report["arms"]["crc"]["warm"]["ici_bytes"]
    part_ici = report["arms"]["partition"]["warm"]["ici_bytes"]
    print(f"warm ICI bytes: crc={crc_ici} partition={part_ici} "
          f"({100 * (1 - part_ici / crc_ici):.1f}% lower; "
          f"outputs identical={report['outputs_bitwise_equal']})")
    print(f"wrote {args.out} (scale={SCALE})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
