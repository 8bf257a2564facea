"""Operations and bytes the GCN's work needs, from its shapes alone.

Counted from the algorithm, not from the program's brick format, so a
number stays comparable whatever later implements a pass:

  SpMM pass X = Â·H:  2·nnz·F operations; bytes: Â in CSR (int32 indptr
                      and indices, float32 values), H read once, X written
                      once, both float32.
  GCN layer:          one pass plus the combination, 2·n·F_in·F_out.
  training step:      the forward plus a backward of twice its operations;
                      recomputed work does not count.
"""
from __future__ import annotations

from typing import Sequence, Tuple

F32 = 4
INDEX = 4


def spmm_flops(nnz: int, f: int) -> int:
    return 2 * nnz * f


def spmm_bytes(n_rows: int, n_cols: int, nnz: int, f: int) -> int:
    csr = (n_rows + 1) * INDEX + nnz * (INDEX + F32)
    return csr + n_cols * f * F32 + n_rows * f * F32


def spmm_min_seconds(n_rows: int, n_cols: int, nnz: int, f: int,
                     peaks) -> Tuple[float, str]:
    """Least time a pass can take on the chip, and which bound sets it."""
    compute = spmm_flops(nnz, f) / peaks.flops_per_s
    memory = spmm_bytes(n_rows, n_cols, nnz, f) / peaks.hbm_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")


def gcn_forward_flops(n: int, nnz: int, dims: Sequence[int]) -> int:
    """dims = (F_in, hidden..., classes): one aggregation and one
    combination per layer."""
    return sum(spmm_flops(nnz, d_in) + 2 * n * d_in * d_out
               for d_in, d_out in zip(dims[:-1], dims[1:]))


def gcn_train_flops(n: int, nnz: int, dims: Sequence[int]) -> int:
    return 3 * gcn_forward_flops(n, nnz, dims)
