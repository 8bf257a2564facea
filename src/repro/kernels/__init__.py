"""Pallas TPU kernels for the compute hot spots the paper optimizes.

The paper's C3 contribution is a specialized tiled compressed matmul (CUDA
in the original); here it is a TPU-native block-ELL SpMM with scalar-prefetch
tile indices (DESIGN §2), and GAT's attention walks the same bricks as its
mask (`gat_attn`). Each kernel has a pl.pallas_call implementation
(TPU target, validated with interpret=True on CPU), a jit'd wrapper in
ops.py, and a pure-jnp oracle in ref.py.
"""
from repro.kernels.ops import (
    bcsr_spmm,
    gat_attention,
    decode_attention,
    flash_attention,
)
from repro.kernels import ref

__all__ = ["bcsr_spmm", "gat_attention",
           "decode_attention", "flash_attention", "ref"]
