"""Block-ELL × dense SpMM — the paper's tiled compressed matmul on TPU.

X[rb*bm:(rb+1)*bm, ft*bn:(ft+1)*bn] = Σ_s blocks[rb, s] @ H[col_tile[rb, s]]

Grid (row_blocks, n_feat_tiles, ell_width); the reduction dim s is
innermost so the output block is revisited and accumulated in place (TPU
'arbitrary' dimension semantics compatible). Tile indices are scalar-
prefetched so the H BlockSpec can route each grid step's HBM→VMEM DMA to the
right column tile — this is the TPU replacement for the CUDA gather loop.

Scalar-prefetch operands live in SMEM, which holds 1 MiB on v5e and pads
the last dimension of a 2-D operand to 128 lanes. `col_tile` is therefore
prefetched flat (index rb*ell_w + s), and a segment whose tile table does
not fit `SMEM_PREFETCH_WORDS` is covered by several calls of one kernel,
each over a slice of `smem_rows_per_call` row blocks. Slicing the bricks
too keeps each call's operands small: Mosaic lays an (8, 8) f32 brick out
in (8, 128) lane tiles, so a call's bricks take 16x their bytes on the
device while it runs.

Padded ELL slots (col_tile == -1) are skipped with @pl.when; their DMA is
routed to tile 0 (harmless read) and contributes nothing.

Brick products run at `Precision.HIGHEST` (full float32 on the MXU) and
accumulate in float32, so the kernel matches a float32 reference on the
chip as it does in interpret mode.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 words one call may scalar-prefetch (col_tile + n_tiles):
# a quarter of v5e's 1 MiB SMEM, leaving the rest to Mosaic's own scalars.
SMEM_PREFETCH_WORDS = 1 << 16


def smem_rows_per_call(n_rb: int, ell_w: int) -> int:
    """Row blocks one `pallas_call` covers so its tile table fits SMEM."""
    return max(1, min(n_rb, (SMEM_PREFETCH_WORDS - 1) // (ell_w + 1)))


def _spmm_kernel(n_tiles_ref, col_tile_ref, a_ref, h_ref, o_ref):
    rb = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_tiles_ref[rb])
    def _acc():
        o_ref[...] += jnp.dot(
            a_ref[0, 0], h_ref[...], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(o_ref.dtype)


def _split_spmm(blocks, col_tile, n_tiles, h, *, bm: int, bk: int, bn: int,
                interpret: bool, out_dtype, rows: int) -> jax.Array:
    """The segment's SpMM as calls of one kernel over `rows` row blocks each."""
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    f_pad = h.shape[1]
    rows = min(rows, n_rb)
    col_flat = col_tile.reshape(n_rb * ell_w)

    def h_index_map(rb, ft, s, n_tiles_ref, col_tile_ref):
        # Route the DMA to the referenced column tile; padded slots read
        # tile 0.
        return (jnp.maximum(col_tile_ref[rb * ell_w + s], 0), ft)

    call = pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, f_pad // bn, ell_w),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda rb, ft, s, *_: (rb, s, 0, 0)),
                pl.BlockSpec((bk, bn), h_index_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda rb, ft, s, *_: (rb, ft)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows * bm, f_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    if rows == n_rb:
        return call(n_tiles, col_flat, blocks, h)

    def one_call(i, out):
        # The last call is shifted back to end at n_rb; the rows it shares
        # with the previous call are recomputed to the same values.
        start = jnp.minimum(i * rows, n_rb - rows)
        part = call(
            jax.lax.dynamic_slice_in_dim(n_tiles, start, rows),
            jax.lax.dynamic_slice_in_dim(col_flat, start * ell_w,
                                         rows * ell_w),
            jax.lax.dynamic_slice_in_dim(blocks, start, rows), h)
        return jax.lax.dynamic_update_slice_in_dim(out, part, start * bm,
                                                   axis=0)

    return jax.lax.fori_loop(
        0, -(-n_rb // rows), one_call,
        jnp.zeros((n_rb * bm, f_pad), out_dtype))


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype"),
)
def bcsr_spmm_pallas(
    blocks: jax.Array,     # (n_rb, ell_w, bm, bk)
    col_tile: jax.Array,   # (n_rb, ell_w) int32
    n_tiles: jax.Array,    # (n_rb,) int32
    h: jax.Array,          # (K_pad, F_pad) — K_pad % bk == 0, F_pad % bn == 0
    *,
    bm: int,
    bk: int,
    bn: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    return _split_spmm(blocks, col_tile, n_tiles, h, bm=bm, bk=bk, bn=bn,
                       interpret=interpret, out_dtype=out_dtype,
                       rows=smem_rows_per_call(n_rb, ell_w))


def _fused_gcn_kernel(n_tiles_ref, col_tile_ref, a_ref, h_ref, w_ref, b_ref,
                      o_ref, x_scratch):
    """Fused σ((Σ_s A_s H_s) W + b) per row block (chain fusion, Fig. 1).

    Grid (n_rb, ell_w): accumulate the aggregation X tile in VMEM scratch,
    apply the combination matmul + bias + ReLU on the last reduction step —
    X never round-trips to HBM.
    """
    rb = pl.program_id(0)
    s = pl.program_id(1)
    ell_w = pl.num_programs(1)

    @pl.when(s == 0)
    def _init():
        x_scratch[...] = jnp.zeros_like(x_scratch)

    @pl.when(s < n_tiles_ref[rb])
    def _acc():
        x_scratch[...] += jnp.dot(
            a_ref[0, 0], h_ref[...], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @pl.when(s == ell_w - 1)
    def _combine():
        x = x_scratch[...]
        y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
        y = y + b_ref[...]
        o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "interpret", "out_dtype"),
)
def fused_gcn_layer_pallas(
    blocks: jax.Array,    # (n_rb, ell_w, bm, bk)
    col_tile: jax.Array,  # (n_rb, ell_w)
    n_tiles: jax.Array,   # (n_rb,)
    h: jax.Array,         # (K_pad, F)
    w: jax.Array,         # (F, F_out)
    b: jax.Array,         # (F_out,)
    *,
    bm: int,
    bk: int,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    f = h.shape[1]
    f_out = w.shape[1]
    grid = (n_rb, ell_w)

    out = pl.pallas_call(
        _fused_gcn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bm, bk),
                    lambda rb, s, n_tiles_ref, col_tile_ref: (rb, s, 0, 0),
                ),
                pl.BlockSpec(
                    (bk, f),
                    lambda rb, s, n_tiles_ref, col_tile_ref: (
                        jnp.maximum(col_tile_ref[rb, s], 0), 0),
                ),
                pl.BlockSpec((f, f_out),
                             lambda rb, s, *_: (0, 0)),
                pl.BlockSpec((1, f_out),
                             lambda rb, s, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (bm, f_out),
                lambda rb, s, n_tiles_ref, col_tile_ref: (rb, 0),
            ),
            scratch_shapes=[pltpu.VMEM((bm, f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_rb * bm, f_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(n_tiles, col_tile, blocks, h, w, b.reshape(1, -1))
    return out
