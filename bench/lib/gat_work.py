"""Operations and bytes GAT's work needs, from its shapes alone.

Counted from the algorithm (arXiv:1710.10903 §2.1), not from the program's
brick format. For a layer of K heads of F' columns on n vertices whose
A + I has nnz entries, per head:

  projection z = h W:       2·n·F_in·F' operations
  scores a_src·z, a_dst·z:  4·n·F'
  attention:                2·nnz·F' (the weighted sum) plus
                            SOFTMAX_OPS per entry: the logit's add,
                            LeakyReLU's multiply and select, the max, the
                            subtraction, exp and the sum
  attention bytes:          A + I's pattern in CSR (int32 indptr and
                            indices, no values), z read once, the output
                            written once, both score vectors; float32
"""
from __future__ import annotations

from typing import Sequence, Tuple

F32 = 4
INDEX = 4
SOFTMAX_OPS = 7


def layers(config: dict) -> list:
    """(F_in, K, F') of each layer of a configuration."""
    out, f_in = [], config["feature_dim"]
    for k, f, cat in zip(config["heads"], config["head_dims"],
                         config["concat"]):
        out.append((f_in, k, f))
        f_in = k * f if cat else f
    return out


def attn_flops(nnz: int, heads: int, f: int) -> int:
    return heads * (2 * f + SOFTMAX_OPS) * nnz


def attn_bytes(n: int, nnz: int, heads: int, f: int) -> int:
    pattern = (n + 1) * INDEX + nnz * INDEX
    return pattern + 2 * n * heads * f * F32 + 2 * n * heads * F32


def attn_min_seconds(n: int, nnz: int, heads: int, f: int,
                     peaks) -> Tuple[float, str]:
    """Least time an attention pass can take on the chip, and its bound."""
    compute = attn_flops(nnz, heads, f) / peaks.flops_per_s
    memory = attn_bytes(n, nnz, heads, f) / peaks.hbm_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")


def gat_forward_flops(n: int, nnz: int, dims: Sequence[tuple]) -> int:
    """dims = (F_in, K, F') per layer, as `layers` gives them."""
    return sum(2 * n * f_in * k * f + 4 * n * k * f + attn_flops(nnz, k, f)
               for f_in, k, f in dims)
