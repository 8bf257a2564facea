"""Graph attention over a Block-ELL segment: GAT's aggregation on the chip.

For head k, row i of the output is

    out[i, k] = Σ_j α_ijk z[j, k],  α_ijk = softmax_j LeakyReLU(s_dst[i, k] + s_src[j, k])

over the j where the segment's brick holds a nonzero at (i, j). The bricks
are those the GCN streams: Â = D̂^-1/2 (A + I) D̂^-1/2 has a nonzero exactly
where A + I has an edge, so a brick's pattern is the attention mask and its
values are not read. RoBW segments are whole row ranges, so each row's
softmax ends inside one segment.

The grid, the SMEM tile table, the walk over populated slots only and the
VMEM ring of source tiles are `bcsr_spmm`'s. A ring tile is a (bk, W) row
block of the packed sources (`pack_sources`): each head's z columns padded
to a multiple of 128, then one 128-lane block in which every row of tile t
holds s_src of the tile's bk vertices for every head (lane k*bk + j is
s_src[t*bk + j, k]), so a brick's source scores arrive with its z rows in
one copy. Per brick and head the kernel takes the logits, LeakyReLU, masks
the brick's zeros, and keeps a running max and sum per (row, head), across
slot chunks too. `p @ z` runs on the MXU at HIGHEST: on one v5e it took
0.1152 s against 0.1146 s for `bcsr_spmm`'s exact float32 VPU products
over a 241,164-brick segment at 4 x 256, and 0.1098 s against 0.1340 s at
6 x 121. The row is normalized after its last chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bcsr_spmm import (
    RING, _vmem_bytes, brick_spec, call_in_parts, slot_walk,
    smem_rows_per_call, step_shape,
)
from repro.kernels.bcsr_spmm import grid_steps as _bcsr_grid_steps

LANES = 128
# A masked logit: its exp is 0 against any real running max. Finite, so
# that a row with nothing walked yet rescales by exp(0) and not by NaN.
MASKED = -1e30


def padded_width(head_width: int) -> int:
    """A head's columns in the packed sources and the kernel's output."""
    return -(-head_width // LANES) * LANES


def _vmem_extra(bm: int, heads: int, fp: int) -> dict:
    """`step_shape`'s VMEM beside bcsr_spmm's: a ring tile adds the score
    lanes to every head's padded columns; a row block's s_dst rows are
    double-buffered."""
    return dict(ring_width=heads * fp + LANES,
                row_bytes=2 * _vmem_bytes((bm, heads), jnp.float32))


def grid_steps(n_rb: int, ell_w: int, bm: int, bk: int, heads: int,
               head_width: int, a_dtype) -> int:
    """Grid steps `gat_attn_pallas` runs for a segment of these shapes."""
    fp = padded_width(head_width)
    return _bcsr_grid_steps(n_rb, ell_w, bm, bk, heads * fp, a_dtype,
                            jnp.float32, jnp.float32,
                            **_vmem_extra(bm, heads, fp))


@functools.partial(jax.jit, static_argnames=("heads", "head_width", "bk"))
def pack_sources(z, s_src, *, heads: int, head_width: int, bk: int):
    """(n_pad, heads * fp + 128) sources for the kernel's ring, n_pad the
    rows of z rounded up to bk: z (n, heads * head_width) with each head
    padded to fp columns, then the score lanes (module docstring)."""
    # Built from column slices, a lane gather and a row repeat: splitting
    # z's lanes into (heads, head_width), or transposing the scores within
    # a tile, takes the TPU compiler over a minute at 478,800 rows.
    n = z.shape[0]
    fp = padded_width(head_width)
    n_pad = -(-n // bk) * bk
    per_tile = LANES // bk
    assert heads <= per_tile, (heads, bk)
    cols = []
    for k in range(heads):
        cols.append(z[:, k * head_width:(k + 1) * head_width])
        if fp > head_width:
            cols.append(jnp.zeros((n, fp - head_width), z.dtype))
    # Row t of s: lane j * per_tile + k holds s_src[t * bk + j, k]; the
    # gather moves it to lane k * bk + j.
    s = jnp.pad(s_src, ((0, n_pad - n), (0, per_tile - heads)))
    s = s.reshape(n_pad // bk, bk * per_tile)
    lane = np.arange(LANES)
    s = jnp.take(s, (lane % bk) * per_tile + lane // bk, axis=1)
    return jnp.concatenate(
        [jnp.pad(jnp.concatenate(cols, axis=1), ((0, n_pad - n), (0, 0))),
         jnp.repeat(s, bk, axis=0)], axis=1)


def _attend_brick(acc, m, s, e, live, z, slope):
    """One head's step over one brick: e the (bm, bk) logits before
    LeakyReLU (negative slope `slope`), live the brick's mask, z its
    (bk, fp) source rows; the running max m and sum s are (bm, 1). Returns
    (acc, m, s) updated."""
    e = jnp.where(e > 0, e, slope * e)
    e = jnp.where(live, e, MASKED)
    m_new = jnp.maximum(m, jnp.max(e, axis=1, keepdims=True))
    rescale = jnp.exp(m - m_new)
    p = jnp.where(live, jnp.exp(e - m_new), 0.0)
    s = s * rescale + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * rescale + jnp.dot(p, z, precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
    return acc, m_new, s


def _attn_kernel(n_tiles_ref, col_ref, a_ref, sd_ref, zs_ref, o_ref, ring,
                 sems, cols, stat_ref, *, rows: int, group: int, chunk: int,
                 ell_w: int, bm: int, bk: int, heads: int, fp: int,
                 slope: float):
    c = pl.program_id(1)
    _, n_rows, lo, end, step = slot_walk(
        n_tiles_ref, col_ref, zs_ref, ring, sems, cols, rows=rows,
        group=group, chunk=chunk, ell_w=ell_w, bk=bk)
    depth = ring.shape[0]
    scores = heads * fp               # first lane of the s_src block
    chunked = chunk < ell_w

    def row(r, k):
        at = pl.ds(pl.multiple_of(r * bm, bm), bm)
        dst = sd_ref[r]                                   # (bm, heads)
        acc = [jnp.zeros((bm, fp), jnp.float32)] * heads
        m = [jnp.full((bm, 1), MASKED, jnp.float32)] * heads
        s = [jnp.zeros((bm, 1), jnp.float32)] * heads
        if chunked:   # a later chunk goes on from the earlier one's state
            first = c == 0
            acc = [jnp.where(first, acc[h], o_ref[at, pl.ds(h * fp, fp)])
                   for h in range(heads)]
            m = [jnp.where(first, m[h], stat_ref[0][:, h:h + 1])
                 for h in range(heads)]
            s = [jnp.where(first, s[h], stat_ref[1][:, h:h + 1])
                 for h in range(heads)]

        def walk(slot, carry):
            acc, m, s, k = carry
            step(k)
            live = a_ref[r, slot - lo] != 0
            tile = ring[k % depth]
            src = tile[0:1, scores:scores + LANES]
            heads_out = [_attend_brick(
                acc[h], m[h], s[h],
                dst[:, h:h + 1] + src[:, h * bk:(h + 1) * bk], live,
                tile[:, h * fp:(h + 1) * fp], slope) for h in range(heads)]
            acc, m, s = (list(x) for x in zip(*heads_out))
            return acc, m, s, k + 1

        acc, m, s, k = jax.lax.fori_loop(lo, end(r), walk, (acc, m, s, k))

        def normalize():
            for h in range(heads):
                o_ref[at, pl.ds(h * fp, fp)] = acc[h] / jnp.where(
                    s[h] > 0, s[h], 1.0)

        if not chunked:
            normalize()
        else:
            last = c == pl.num_programs(1) - 1
            pl.when(last)(normalize)

            @pl.when(jnp.logical_not(last))
            def _keep():
                lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
                ms = ss = jnp.zeros((bm, LANES), jnp.float32)
                for h in range(heads):
                    o_ref[at, pl.ds(h * fp, fp)] = acc[h]
                    ms = jnp.where(lane == h, m[h], ms)
                    ss = jnp.where(lane == h, s[h], ss)
                stat_ref[0] = ms
                stat_ref[1] = ss
        return k

    jax.lax.fori_loop(0, n_rows, row, 0)


def _split_attn(blocks, col_tile, n_tiles, zs, s_dst, *, heads: int,
                head_width: int, negative_slope: float, bm: int, bk: int,
                interpret: bool, rows: int, group: int = 0,
                chunk: int = 0) -> jax.Array:
    """The segment's attention as calls of one kernel over `rows` row
    blocks each; `group` and `chunk` (0: from `step_shape`) shape its
    steps. s_dst is (n_rb * bm, heads); the result (n_rb * bm, heads * fp).
    """
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    fp = padded_width(head_width)
    rows = min(rows, n_rb)
    auto = step_shape(rows, ell_w, bm, bk, heads * fp, blocks.dtype,
                      jnp.float32, jnp.float32, **_vmem_extra(bm, heads, fp))
    group = min(group or auto[0], rows)
    chunk = min(chunk or auto[1], ell_w)
    col_flat = col_tile.reshape(n_rb * ell_w)

    call = pl.pallas_call(
        functools.partial(_attn_kernel, rows=rows, group=group, chunk=chunk,
                          ell_w=ell_w, bm=bm, bk=bk, heads=heads, fp=fp,
                          slope=negative_slope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(rows, group), pl.cdiv(ell_w, chunk)),
            in_specs=[
                brick_spec(group, chunk, bm, bk),
                pl.BlockSpec((group, bm, heads), lambda g, c, *_: (g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((group * bm, heads * fp),
                                   lambda g, c, *_: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((RING, bk, zs.shape[1]), zs.dtype),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SMEM((group * chunk,), jnp.int32),
                pltpu.VMEM((2, bm, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows * bm, heads * fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return call_in_parts(call, n_tiles, col_flat,
                         (blocks, s_dst.reshape(n_rb, bm, heads)), (zs,),
                         rows=rows, ell_w=ell_w, bm=bm, width=heads * fp,
                         out_dtype=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("heads", "head_width", "negative_slope", "bm", "bk",
                     "interpret"),
)
def gat_attn_pallas(
    blocks: jax.Array,     # (n_rb, ell_w, bm, bk): the mask's bricks
    col_tile: jax.Array,   # (n_rb, ell_w) int32
    n_tiles: jax.Array,    # (n_rb,) int32
    zs: jax.Array,         # (K_pad, heads * fp + 128): `pack_sources`
    s_dst: jax.Array,      # (n_rb * bm, heads): the segment's rows' scores
    *,
    heads: int,
    head_width: int,
    negative_slope: float,
    bm: int,
    bk: int,
    interpret: bool = False,
) -> jax.Array:
    """(n_rb * bm, heads, head_width) attention output of one segment.

    The sources are packed once per pass (`pack_sources`) and shared by
    every segment's call, rather than packed per call from z and s_src."""
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    out = _split_attn(blocks, col_tile, n_tiles, zs, s_dst, heads=heads,
                      head_width=head_width, negative_slope=negative_slope,
                      bm=bm, bk=bk,
                      interpret=interpret,
                      rows=smem_rows_per_call(n_rb, ell_w))
    return out.reshape(n_rb * bm, heads, -1)[:, :, :head_width]
