"""RoBW-128 tile densification: CSR row blocks → BlockELL bricks.

This is the Phase-I CPU preprocessing of the paper (Fig. 5) adapted to TPU:
instead of shipping ragged CSR triples, the host scatters each row block's
nonzeros into dense (bm, bk) column-tile bricks that the MXU can consume
directly, and records the tile topology (col_tile ids) for scalar prefetch.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.sparse.formats import CSR, BlockELL


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tile_csr_to_block_ell(
    a: CSR,
    bm: int = 128,
    bk: int = 128,
    ell_width: Optional[int] = None,
    dtype: np.dtype = np.float32,
) -> BlockELL:
    """Densify CSR into MXU-aligned block-ELL.

    ell_width: max nonzero column tiles kept per row block. None → the true
    max over this segment (exact). If a row block has more populated tiles
    than ell_width, the *least-populated* tiles are dropped — callers that
    need exactness must pass ell_width=None or a verified bucket capacity
    (the memory model guarantees this for AIRES schedules; tests assert it).
    """
    n_rows, n_cols = a.shape
    n_row_blocks = max(1, (n_rows + bm - 1) // bm)
    n_col_tiles = (n_cols + bk - 1) // bk

    # Every nonzero's row block and column tile, then the distinct
    # (row block, tile) pairs in row-block-then-tile order: the sorted
    # populated tiles of each row block, for all row blocks at once.
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(a.indptr))
    cols = np.asarray(a.indices, dtype=np.int64)
    rb = rows // bm
    tile = cols // bk
    pairs, pair_of_nz, counts = np.unique(
        rb * max(n_col_tiles, 1) + tile, return_inverse=True,
        return_counts=True)
    pair_rb = pairs // max(n_col_tiles, 1)
    pair_tile = pairs % max(n_col_tiles, 1)
    width = np.bincount(pair_rb, minlength=n_row_blocks)

    true_width = int(width.max(initial=0))
    if ell_width is None:
        ell_width = max(1, true_width)
    ell_width = max(1, min(ell_width, n_col_tiles))

    # Slot of each pair in its row block's ELL row; -1 = dropped.
    first = np.cumsum(width) - width
    slot = np.arange(pairs.shape[0], dtype=np.int64) - first[pair_rb]
    for r in np.nonzero(width > ell_width)[0]:
        # Keep the most-populated tiles (drop the tail), in tile order.
        # AIRES schedules never get here (bucket capacity ≥ true width).
        lo, hi = first[r], first[r] + width[r]
        kept = np.zeros(hi - lo, dtype=bool)
        kept[np.argsort(-counts[lo:hi], kind="stable")[:ell_width]] = True
        slot[lo:hi] = np.where(kept, np.cumsum(kept) - 1, -1)

    blocks = np.zeros((n_row_blocks, ell_width, bm, bk), dtype=dtype)
    col_tile = np.full((n_row_blocks, ell_width), -1, dtype=np.int32)
    n_tiles = np.minimum(width, ell_width).astype(np.int32)
    kept = slot >= 0
    col_tile[pair_rb[kept], slot[kept]] = pair_tile[kept]
    nz_slot = slot[pair_of_nz]
    nz = nz_slot >= 0
    blocks[rb[nz], nz_slot[nz], (rows - rb * bm)[nz],
           (cols - tile * bk)[nz]] = a.data[nz]

    return BlockELL(blocks=blocks, col_tile=col_tile, n_tiles=n_tiles,
                    bm=bm, bk=bk, n_rows=n_rows, n_cols=n_cols)


def block_ell_to_dense(e: BlockELL) -> np.ndarray:
    """Inverse of tile_csr_to_block_ell (for oracles/tests)."""
    n_rows_pad = e.n_row_blocks * e.bm
    n_cols_pad = round_up(e.n_cols, e.bk)
    out = np.zeros((n_rows_pad, n_cols_pad), dtype=e.blocks.dtype)
    for rb in range(e.n_row_blocks):
        for s in range(int(e.n_tiles[rb])):
            t = int(e.col_tile[rb, s])
            out[rb * e.bm : (rb + 1) * e.bm, t * e.bk : (t + 1) * e.bk] += \
                e.blocks[rb, s]
    return out[: e.n_rows, : e.n_cols]
