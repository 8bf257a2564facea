"""jit'd public wrappers around the Pallas kernels.

Handle padding/layout so callers pass natural shapes; pick interpret mode
automatically on CPU (the container target) while lowering to real Mosaic
on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bcsr_spmm as _bcsr
from repro.kernels import decode_attn as _dec
from repro.kernels import flash_attn as _flash
from repro.kernels import gat_attn as _gat
from repro.sparse.formats import BlockELL
from repro.trace import span


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def _needed_rows(ell: BlockELL) -> int:
    """Rows of H the segment's column tiles reach. Reading the largest
    tile of a device brick waits for its upload."""
    with span("kernel.sync"):
        return int(np.max(ell.col_tile, initial=0) + 1) * ell.bk


def bcsr_spmm(
    ell: BlockELL,
    h: jax.Array,
    *,
    bn: int = 128,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
    bricks: Optional[int] = None,
) -> jax.Array:
    """X = A @ H for a BlockELL segment of A and dense H (n_cols, F).

    Returns (ell.n_rows, F) — padding rows/cols are stripped. The span
    `aires.kernel` carries the kernel's `grid_steps` and `bricks`, the
    populated slots it walks: given, or summed from `ell.n_tiles` where
    that is a host array (never read back from the device).
    """
    if interpret is None:
        interpret = _on_cpu()
    if bricks is None and isinstance(ell.n_tiles, np.ndarray):
        bricks = int(ell.n_tiles.sum())
    h = jnp.asarray(h)
    f = h.shape[1]
    bn = min(bn, ((f + 127) // 128) * 128)
    f_pad = -(-f // bn) * bn
    counts = dict(grid_steps=_bcsr.grid_steps(
        ell.n_row_blocks, ell.ell_width, ell.bm, ell.bk, f_pad,
        jax.dtypes.canonicalize_dtype(ell.blocks.dtype), h.dtype, out_dtype))
    if bricks is not None:
        counts["bricks"] = bricks
    with span("kernel", rows=ell.n_rows, **counts):
        h_pad = _pad_to(_pad_to(h, 0, ell.bk), 1, bn)
        # Segment column coverage may exceed h rows when A is wider than H
        # rows (never in GCN aggregation: A is n×n, H is n×f).
        need_k = _needed_rows(ell)
        if h_pad.shape[0] < need_k:
            h_pad = jnp.pad(h_pad, ((0, need_k - h_pad.shape[0]), (0, 0)))
        out = _bcsr.bcsr_spmm_pallas(
            jnp.asarray(ell.blocks),
            jnp.asarray(ell.col_tile),
            jnp.asarray(ell.n_tiles),
            h_pad,
            bm=ell.bm,
            bk=ell.bk,
            bn=bn,
            interpret=interpret,
            out_dtype=out_dtype,
        )
        return out[: ell.n_rows, :f]


def gat_attention(
    ell: BlockELL,
    zs: jax.Array,
    s_dst: jax.Array,
    *,
    heads: int,
    head_width: int,
    negative_slope: float,
    bricks: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GAT's attention over one segment: (ell.n_rows, heads, head_width).

    The segment's bricks are the mask, `zs` the pass's packed sources
    (`gat_attn.pack_sources`), `s_dst` the destination scores of the segment's
    n_row_blocks * bm rows, padding rows included (they are stripped). The
    span `aires.attn` carries `grid_steps`, `heads` and, as for
    `aires.kernel`, the `bricks` walked, from the host copy of `n_tiles`.
    """
    if interpret is None:
        interpret = _on_cpu()
    steps = _gat.grid_steps(
        ell.n_row_blocks, ell.ell_width, ell.bm, ell.bk, heads, head_width,
        jax.dtypes.canonicalize_dtype(ell.blocks.dtype))
    with span("attn", rows=ell.n_rows, grid_steps=steps, bricks=bricks,
              heads=heads):
        need_k = _needed_rows(ell)
        if zs.shape[0] < need_k:
            zs = jnp.pad(zs, ((0, need_k - zs.shape[0]), (0, 0)))
        out = _gat.gat_attn_pallas(
            jnp.asarray(ell.blocks),
            jnp.asarray(ell.col_tile),
            jnp.asarray(ell.n_tiles),
            zs,
            s_dst,
            heads=heads,
            head_width=head_width,
            negative_slope=negative_slope,
            bm=ell.bm,
            bk=ell.bk,
            interpret=interpret,
        )
        return out[: ell.n_rows]


def fused_gcn_layer(
    ell: BlockELL,
    h: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    interpret: Optional[bool] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """σ((A @ H) @ W + b) fused per row block — Fig. 1 chain without
    materializing X in HBM."""
    if interpret is None:
        interpret = _on_cpu()
    with span("kernel", rows=ell.n_rows):
        h_pad = _pad_to(jnp.asarray(h), 0, ell.bk)
        need_k = _needed_rows(ell)
        if h_pad.shape[0] < need_k:
            h_pad = jnp.pad(h_pad, ((0, need_k - h_pad.shape[0]), (0, 0)))
        out = _bcsr.fused_gcn_layer_pallas(
            jnp.asarray(ell.blocks),
            jnp.asarray(ell.col_tile),
            jnp.asarray(ell.n_tiles),
            h_pad,
            jnp.asarray(w),
            jnp.asarray(b),
            bm=ell.bm,
            bk=ell.bk,
            interpret=interpret,
            out_dtype=out_dtype,
        )
        return out[: ell.n_rows]


def decode_attention(
    q: jax.Array,       # (B, n_q_heads, d)
    k: jax.Array,       # (B, n_kv_heads, S, d)
    v: jax.Array,       # (B, n_kv_heads, S, d)
    lens: jax.Array,    # (B,) int32
    *,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA flash-decode. Returns (B, n_q_heads, d)."""
    if interpret is None:
        interpret = _on_cpu()
    b_sz, n_q, d = q.shape
    n_kv = k.shape[1]
    group = n_q // n_kv
    qg = q.reshape(b_sz, n_kv, group, d)
    s = k.shape[2]
    block_s = min(block_s, s)
    k_pad = _pad_to(k, 2, block_s)
    v_pad = _pad_to(v, 2, block_s)
    out = _dec.decode_attention_pallas(
        qg, k_pad, v_pad, lens.astype(jnp.int32),
        block_s=block_s, interpret=interpret)
    return out.reshape(b_sz, n_q, d)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """Causal/windowed flash attention (B, H, S, d) — prefill hot spot."""
    if interpret is None:
        interpret = _on_cpu()
    s_len = q.shape[2]
    block_q = min(block_q, s_len)
    block_k = min(block_k, s_len)
    return _flash.flash_attention_pallas(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        window=window, interpret=interpret)
