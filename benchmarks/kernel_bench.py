"""Kernel microbench: bcsr_spmm wall time (interpret mode — correctness
path only; on CPU this measures the streaming pipeline, not MXU perf) plus
the derived arithmetic-intensity numbers the TPU roofline uses.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np
import jax.numpy as jnp

from repro.kernels import bcsr_spmm
from repro.launch.compile_cache import enable_compile_cache
from repro.sparse import csr_from_dense, tile_csr_to_block_ell


def run() -> List[str]:
    rows = ["# kernel microbench (interpret mode on CPU)"]
    rng = np.random.default_rng(0)
    for n, f, dens in [(256, 64, 0.05), (512, 128, 0.02)]:
        dense = ((rng.random((n, n)) < dens)
                 * rng.standard_normal((n, n))).astype(np.float32)
        a = csr_from_dense(dense)
        ell = tile_csr_to_block_ell(a, bm=32, bk=32)
        h = rng.standard_normal((n, f)).astype(np.float32)
        hj = jnp.asarray(h)
        out = bcsr_spmm(ell, hj, bn=32)           # compile + warm
        out.block_until_ready()
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            out = bcsr_spmm(ell, hj, bn=32)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / reps * 1e6
        # TPU-side derived numbers: bytes moved vs MACs per segment
        flops = 2 * a.nnz * f
        bytes_moved = ell.nbytes() + h.nbytes + n * f * 4
        rows.append(
            f"kernel/bcsr_spmm/n{n}_f{f},{us:.1f},"
            f"flops={flops};bytes={bytes_moved};"
            f"intensity={flops/bytes_moved:.3f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
