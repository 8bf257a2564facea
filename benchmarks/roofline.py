"""§Roofline — three-term roofline per (arch × shape × mesh) from the
dry-run artifacts (results/dryrun/*.json).

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs          (197 TF bf16)
  memory_s     = HLO_bytes_per_device / HBM_bw              (819 GB/s)
  collective_s = collective_bytes_per_device / link_bw      (~50 GB/s/link)

cost_analysis of the SPMD-partitioned module is per-device, so dividing by
per-chip peaks directly gives the per-step time lower bound each resource
imposes; the max of the three is the roofline bound and its argmax the
bottleneck. MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference), N = active
params for MoE; the MODEL/HLO ratio exposes remat/redundant compute.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config
from repro.io.tiers import Path, TPU_V5E_SYSTEM
from repro.launch.compile_cache import enable_compile_cache

# Per-chip peaks sourced from the one TierSpec the whole repo prices
# against (repro.io.tiers.TPU_V5E_SYSTEM) — the same constants the
# autotuner's roofline cross-check reads, so the two can never drift.
PEAK_FLOPS = TPU_V5E_SYSTEM.peak_flops    # bf16 per chip
HBM_BW = TPU_V5E_SYSTEM.hbm_bw            # bytes/s per chip
ICI_BW = TPU_V5E_SYSTEM.bw[Path.ICI]      # bytes/s per link

def _default_results_dir() -> str:
    if os.environ.get("DRYRUN_DIR"):
        return os.environ["DRYRUN_DIR"]
    # prefer the optimized sweep; fall back to the baseline sweep
    return ("results/dryrun_final" if os.path.isdir("results/dryrun_final")
            else "results/dryrun")


RESULTS_DIR = _default_results_dir()


def _expert_params(cfg) -> int:
    if not cfg.is_moe:
        return 0
    return cfg.n_layers * 3 * cfg.d_model * (cfg.expert_d_ff or cfg.d_ff) \
        * cfg.n_experts


def model_flops(arch: str, shape_name: str, n_params: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    exp = _expert_params(cfg)
    n_active = n_params - exp + (exp * cfg.top_k // max(cfg.n_experts, 1)
                                 if cfg.is_moe else 0)
    if shape["kind"] == "train":
        tokens = shape["global_batch"] * shape["seq_len"]
        return 6.0 * n_active * tokens
    if shape["kind"] == "prefill":
        tokens = shape["global_batch"] * shape["seq_len"]
        return 2.0 * n_active * tokens
    tokens = shape["global_batch"]  # one new token per sequence
    return 2.0 * n_active * tokens


def analyze_cell(d: Dict) -> Optional[Dict]:
    if not d.get("ok"):
        return None
    chips = 512 if d["mesh"] == "2x16x16" else 256
    flops_dev = d.get("total_flops", d["cost"]["flops"])
    bytes_dev = d.get("total_bytes_accessed", d["cost"]["bytes_accessed"])
    coll_dev = d.get("total_collective_bytes", d["collectives"]["bytes"])
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(d["arch"], d["shape"], d["params"])
    hlo_global = flops_dev * chips
    return {
        "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
        "chips": chips,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "bottleneck": bottleneck,
        "bound_s": terms[bottleneck],
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "roofline_fraction": (mf / chips / PEAK_FLOPS) / terms[bottleneck]
        if terms[bottleneck] else 0.0,
        "hbm_gib_per_chip": (d["memory"]["argument_bytes"]
                             + d["memory"]["temp_bytes"]) / 2**30,
    }


def load_cells(results_dir: str = RESULTS_DIR) -> List[Dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        row = analyze_cell(d)
        if row is None:
            cells.append({"arch": d.get("arch"), "shape": d.get("shape"),
                          "mesh": d.get("mesh"),
                          "skip": d.get("skipped", d.get("error", "?"))})
        else:
            cells.append(row)
    return cells


def run() -> List[str]:
    rows = ["# roofline terms per (arch x shape x mesh); seconds per step"]
    for c in load_cells():
        if "skip" in c:
            rows.append(f"roofline/{c['arch']}/{c['shape']}/{c['mesh']},0.0,"
                        f"SKIP:{str(c['skip'])[:60]}")
            continue
        rows.append(
            f"roofline/{c['arch']}/{c['shape']}/{c['mesh']},"
            f"{c['bound_s']*1e6:.1f},"
            f"compute={c['compute_s']:.4f}s;memory={c['memory_s']:.4f}s;"
            f"collective={c['collective_s']:.4f}s;bottleneck={c['bottleneck']};"
            f"useful_ratio={c['useful_ratio']:.3f};"
            f"roofline_frac={c['roofline_fraction']:.3f};"
            f"hbm_gib={c['hbm_gib_per_chip']:.2f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
