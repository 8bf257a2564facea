"""Block-ELL × dense SpMM — the paper's tiled compressed matmul on TPU.

X[rb*bm:(rb+1)*bm, :] = Σ_{s < n_tiles[rb]} blocks[rb, s] @ H[col_tile[rb, s]]

One grid step covers a group of `group` row blocks at the full padded
feature width, and walks only their populated ELL slots. The group's bricks
come by BlockSpec; H stays in HBM and each referenced (bk, F_pad) tile is
copied into a VMEM ring of `RING` tiles, the copies for the next slots in
flight while the current brick is multiplied. A padded slot costs no copy
and no arithmetic. The group size follows from the shapes so that a step's
buffers fit `VMEM_STEP_BYTES`: Mosaic lays an (8, 8) f32 brick out in an
(8, 128) tile, 16x its bytes, so a wide ELL leaves room for few row blocks.
Where one row block's bricks do not fit, the step takes one row block and a
chunk of its slots, and a second grid axis walks the chunks.

Scalar-prefetch operands live in SMEM, which holds 1 MiB on v5e and pads
the last dimension of a 2-D operand to 128 lanes. `col_tile` is therefore
prefetched flat (index rb*ell_w + s), and a segment whose tile table does
not fit `SMEM_PREFETCH_WORDS` is covered by several calls of one kernel,
each over a slice of `smem_rows_per_call` row blocks. Slicing the bricks
too keeps each call's operands small: a call's bricks take 16x their bytes
on the device while it runs.

Brick products are exact float32 multiply-adds on the VPU, accumulated in
float32 over the slots in ascending order, so the kernel matches a float32
reference on the chip as it does in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 words one call may scalar-prefetch (col_tile + n_tiles):
# a quarter of v5e's 1 MiB SMEM, leaving the rest to Mosaic's own scalars.
SMEM_PREFETCH_WORDS = 1 << 16
# VMEM bytes one grid step's buffers may take: half of v5e's default
# scoped limit (16 MiB), the rest left to Mosaic.
VMEM_STEP_BYTES = 8 << 20
# H tiles in the copy ring: one being multiplied, the rest in flight.
RING = 16


def smem_rows_per_call(n_rb: int, ell_w: int) -> int:
    """Row blocks one `pallas_call` covers so its tile table fits SMEM."""
    return max(1, min(n_rb, (SMEM_PREFETCH_WORDS - 1) // (ell_w + 1)))


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of an array in VMEM, its last two dims padded to whole
    (sublane, 128) tiles: 8 sublanes for 32-bit types, 16 for 16-bit."""
    item = np.dtype(dtype).itemsize
    sub = 32 // item
    return (math.prod(shape[:-2]) * -(-shape[-2] // sub) * sub
            * -(-shape[-1] // 128) * 128 * item)


def step_shape(rows: int, ell_w: int, bm: int, bk: int, f_pad: int,
               a_dtype, h_dtype, out_dtype, ring_width: int = 0,
               row_bytes: int = 0) -> tuple:
    """(group, chunk): row blocks and ELL slots one grid step covers.

    The pipeline double-buffers the step's bricks and output block (of
    `f_pad` columns); the ring holds `RING` tiles of `ring_width` columns
    (0: `f_pad`). `row_bytes` is VMEM a kernel takes per row block beside
    those. A group takes all `ell_w` slots of as many row blocks as fit;
    where not even one row block's do, the step takes one row block and as
    many slots as fit."""
    room = VMEM_STEP_BYTES - RING * _vmem_bytes((bk, ring_width or f_pad),
                                                h_dtype)
    out_row = 2 * _vmem_bytes((bm, f_pad), out_dtype) + row_bytes
    brick = 2 * _vmem_bytes((bm, bk), a_dtype)
    if out_row + ell_w * brick <= room:
        return max(1, min(rows, room // (out_row + ell_w * brick))), ell_w
    return 1, max(1, (room - out_row) // brick)


def grid_steps(n_rb: int, ell_w: int, bm: int, bk: int, f_pad: int,
               a_dtype, h_dtype, out_dtype=jnp.float32, ring_width: int = 0,
               row_bytes: int = 0) -> int:
    """Grid steps a kernel of `step_shape`'s shapes runs for a segment."""
    rows = smem_rows_per_call(n_rb, ell_w)
    group, chunk = step_shape(rows, ell_w, bm, bk, f_pad, a_dtype, h_dtype,
                              out_dtype, ring_width, row_bytes)
    return -(-n_rb // rows) * -(-rows // group) * -(-ell_w // chunk)


def _brick_product(acc, a, h):
    """acc + a @ h as float32 multiply-adds over a's columns in order."""
    a = a.astype(jnp.float32)
    h = h.astype(jnp.float32)
    for j in range(a.shape[1]):
        acc = acc + a[:, j:j + 1] * h[j:j + 1, :]
    return acc


def slot_walk(n_tiles_ref, col_ref, src_ref, ring, sems, cols, *,
              rows: int, group: int, chunk: int, ell_w: int, bk: int):
    """The walk over a grid step's populated slots that both Block-ELL
    kernels share: lists the column tiles of the slots walked into the
    SMEM scratch `cols`, starts the first copies of `src_ref`'s
    (bk, width) tiles into the VMEM ring, and returns
    (first row block, row blocks in the step, first slot of the chunk,
    end(r), step(k)). Walked brick k's tile is `ring[k % RING]` once
    `step(k)`, which also starts the copy RING - 1 bricks ahead, returns.
    """
    g, c = pl.program_id(0), pl.program_id(1)
    first = g * group                       # the group's first row block
    n_rows = jnp.minimum(group, rows - first)   # fewer in the tail group
    lo = c * chunk                          # the chunk's first slot
    depth = ring.shape[0]

    def end(r):
        """One past row block r's last populated slot in this chunk."""
        return jnp.clip(n_tiles_ref[first + r], lo, lo + chunk)

    # The column tiles of the step's populated slots, in the order walked.
    def list_row(r, k):
        def one(s, k):
            cols[k] = col_ref[(first + r) * ell_w + s]
            return k + 1
        return jax.lax.fori_loop(lo, end(r), one, k)

    total = jax.lax.fori_loop(0, n_rows, list_row, 0)

    def copy(k):
        slot = k % depth
        return pltpu.make_async_copy(src_ref.at[pl.ds(cols[k] * bk, bk)],
                                     ring.at[slot], sems.at[slot])

    def start(k):
        @pl.when(k < total)
        def _():
            copy(k).start()

    for k in range(depth - 1):
        start(k)

    def step(k):
        start(k + depth - 1)
        copy(k).wait()

    return first, n_rows, lo, end, step


def _spmm_kernel(n_tiles_ref, col_ref, a_ref, h_ref, o_ref, ring, sems,
                 cols, *, rows: int, group: int, chunk: int, ell_w: int,
                 bm: int, bk: int):
    c = pl.program_id(1)
    _, n_rows, lo, end, step = slot_walk(
        n_tiles_ref, col_ref, h_ref, ring, sems, cols, rows=rows,
        group=group, chunk=chunk, ell_w=ell_w, bk=bk)
    depth = ring.shape[0]

    def row(r, k):
        def walk(s, carry):
            acc, k = carry
            step(k)
            acc = _brick_product(acc, a_ref[r, s - lo], ring[k % depth])
            return acc, k + 1

        at = pl.ds(pl.multiple_of(r * bm, bm), bm)
        acc = jnp.zeros((bm, o_ref.shape[1]), jnp.float32)
        if chunk < ell_w:   # a later chunk adds to what the earlier wrote
            acc = jnp.where(c == 0, acc, o_ref[at, :].astype(jnp.float32))
        acc, k = jax.lax.fori_loop(lo, end(r), walk, (acc, k))
        o_ref[at, :] = acc.astype(o_ref.dtype)
        return k

    jax.lax.fori_loop(0, n_rows, row, 0)


def brick_spec(group: int, chunk: int, bm: int, bk: int) -> pl.BlockSpec:
    """The BlockSpec of a step's (group, chunk) bricks."""
    def brick_index(g, c, n_tiles_ref, col_ref):
        if group > 1:
            return g, c, 0, 0
        # Chunks past the row block's last populated slot keep the block
        # already in VMEM, so they cost no copy.
        last = jnp.maximum(n_tiles_ref[g] - 1, 0) // chunk
        return g, jnp.minimum(c, last), 0, 0

    return pl.BlockSpec((group, chunk, bm, bk), brick_index)


def call_in_parts(call, n_tiles, col_flat, row_args, shared, *, rows: int,
                  ell_w: int, bm: int, width: int, out_dtype) -> jax.Array:
    """`call(n_tiles, col_flat, *row_args, *shared)` over a segment whose
    tile table does not fit SMEM: calls over slices of `rows` row blocks
    (the leading axis of `n_tiles` and of each of `row_args`), each
    writing its (rows * bm, width) rows of the output."""
    n_rb = n_tiles.shape[0]
    if rows == n_rb:
        return call(n_tiles, col_flat, *row_args, *shared)

    def one_call(i, out):
        # The last call is shifted back to end at n_rb; the rows it shares
        # with the previous call are recomputed to the same values.
        start = jnp.minimum(i * rows, n_rb - rows)
        part = call(
            jax.lax.dynamic_slice_in_dim(n_tiles, start, rows),
            jax.lax.dynamic_slice_in_dim(col_flat, start * ell_w,
                                         rows * ell_w),
            *[jax.lax.dynamic_slice_in_dim(x, start, rows)
              for x in row_args], *shared)
        return jax.lax.dynamic_update_slice_in_dim(out, part, start * bm,
                                                   axis=0)

    return jax.lax.fori_loop(
        0, -(-n_rb // rows), one_call,
        jnp.zeros((n_rb * bm, width), out_dtype))


def _split_spmm(blocks, col_tile, n_tiles, h, *, bm: int, bk: int,
                interpret: bool, out_dtype, rows: int, group: int = 0,
                chunk: int = 0) -> jax.Array:
    """The segment's SpMM as calls of one kernel over `rows` row blocks
    each; `group` and `chunk` (0: from `step_shape`) shape its steps."""
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    f_pad = h.shape[1]
    rows = min(rows, n_rb)
    auto = step_shape(rows, ell_w, bm, bk, f_pad, blocks.dtype, h.dtype,
                      out_dtype)
    group = min(group or auto[0], rows)
    chunk = min(chunk or auto[1], ell_w)
    col_flat = col_tile.reshape(n_rb * ell_w)

    call = pl.pallas_call(
        functools.partial(_spmm_kernel, rows=rows, group=group, chunk=chunk,
                          ell_w=ell_w, bm=bm, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(rows, group), pl.cdiv(ell_w, chunk)),
            in_specs=[
                brick_spec(group, chunk, bm, bk),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((group * bm, f_pad),
                                   lambda g, c, *_: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((RING, bk, f_pad), h.dtype),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SMEM((group * chunk,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows * bm, f_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return call_in_parts(call, n_tiles, col_flat, (blocks,), (h,),
                         rows=rows, ell_w=ell_w, bm=bm, width=f_pad,
                         out_dtype=out_dtype)


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
    """X = A @ H for one segment; each step takes H's F_pad columns whole
    (`bn` is the unit `h` was padded to)."""
    del bn
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    return _split_spmm(blocks, col_tile, n_tiles, h, bm=bm, bk=bk,
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
