"""Per-kernel correctness sweeps: Pallas (interpret=True) vs ref.py oracle."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import bcsr_spmm, decode_attention
from repro.kernels.ref import decode_attention_ref
from repro.sparse import csr_from_dense, tile_csr_to_block_ell


def _rand_sparse(n, m, density, dtype, seed):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((n, m)) < density)
             * rng.standard_normal((n, m))).astype(dtype)
    return dense


@pytest.mark.parametrize("n,m,f", [(16, 16, 8), (40, 24, 16), (64, 64, 32),
                                   (33, 57, 24)])
@pytest.mark.parametrize("density", [0.05, 0.3])
def test_bcsr_spmm_shapes(n, m, f, density):
    dense = _rand_sparse(n, m, density, np.float32, seed=n * m + f)
    a = csr_from_dense(dense)
    ell = tile_csr_to_block_ell(a, bm=8, bk=8)
    h = np.random.default_rng(1).standard_normal((m, f)).astype(np.float32)
    out = np.asarray(bcsr_spmm(ell, jnp.asarray(h), bn=8))
    np.testing.assert_allclose(out, dense @ h, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_bcsr_spmm_dtypes(dtype):
    dense = _rand_sparse(32, 32, 0.2, np.float32, seed=7).astype(dtype)
    a = csr_from_dense(dense)
    ell = tile_csr_to_block_ell(a, bm=8, bk=8, dtype=dtype)
    h = np.random.default_rng(2).standard_normal((32, 16)).astype(dtype)
    out = np.asarray(bcsr_spmm(ell, jnp.asarray(h), bn=8))
    np.testing.assert_allclose(
        out, dense.astype(np.float32) @ h.astype(np.float32),
        atol=1e-2 if dtype == np.float16 else 1e-4)


def test_bcsr_spmm_empty_rows():
    dense = np.zeros((24, 24), np.float32)
    dense[3, 5] = 2.0  # single nonzero
    a = csr_from_dense(dense)
    ell = tile_csr_to_block_ell(a, bm=8, bk=8)
    h = np.ones((24, 8), np.float32)
    out = np.asarray(bcsr_spmm(ell, jnp.asarray(h), bn=8))
    np.testing.assert_allclose(out, dense @ h, atol=1e-5)


@pytest.mark.parametrize("rows", [1, 3, 4, 7])
def test_bcsr_spmm_split_calls_bitexact(rows):
    """A segment covered by several calls (the SMEM bound on the tile
    table) gives the single call's result bit for bit, including the last
    call that is shifted back to overlap its predecessor."""
    from repro.kernels.bcsr_spmm import _split_spmm, bcsr_spmm_pallas

    dense = _rand_sparse(56, 48, 0.15, np.float32, seed=11)
    ell = tile_csr_to_block_ell(csr_from_dense(dense), bm=8, bk=8)
    h = jnp.asarray(np.random.default_rng(3).standard_normal(
        (48, 128)).astype(np.float32))
    args = (jnp.asarray(ell.blocks), jnp.asarray(ell.col_tile),
            jnp.asarray(ell.n_tiles), h)
    kw = dict(bm=8, bk=8, interpret=True)
    whole = np.asarray(bcsr_spmm_pallas(*args, **kw))
    split = np.asarray(_split_spmm(*args, out_dtype=jnp.float32, rows=rows,
                                   **kw))
    np.testing.assert_array_equal(split, whole)
    np.testing.assert_allclose(whole, dense @ np.asarray(h), atol=1e-4)


def test_smem_rows_per_call_bounds_the_tile_table():
    from repro.kernels.bcsr_spmm import SMEM_PREFETCH_WORDS, smem_rows_per_call

    assert smem_rows_per_call(64, 8) == 64
    for n_rb, ell_w in [(4096, 16), (13789, 64), (100, 4096)]:
        rows = smem_rows_per_call(n_rb, ell_w)
        assert 1 <= rows < n_rb
        assert rows * (ell_w + 1) <= SMEM_PREFETCH_WORDS


def _ell_arrays(n_tiles, ell_w, n_col_tiles, seed, cols=None,
                dtype=np.float32):
    """Block-ELL arrays with the given populated slots per row block;
    padded slots hold zero bricks and column tile -1."""
    rng = np.random.default_rng(seed)
    n_tiles = np.asarray(n_tiles, np.int32)
    n_rb = len(n_tiles)
    col = np.full((n_rb, ell_w), -1, np.int32)
    blocks = np.zeros((n_rb, ell_w, 8, 8), dtype)
    for rb, n in enumerate(n_tiles):
        col[rb, :n] = (cols[rb] if cols is not None else
                       rng.choice(n_col_tiles, n, replace=n > n_col_tiles))
        blocks[rb, :n] = rng.standard_normal((n, 8, 8))
    return blocks, col, n_tiles


# (populated slots per row block, ell_w, column tiles, F, brick dtype,
#  group, chunk, column tiles by row block): 0 for group and chunk lets
# the kernel choose them from the shapes.
_WALK_CASES = {
    "empty-row-blocks": ([2, 0, 3, 0, 1], 3, 6, 16, np.float32, 0, 0, None),
    "full-row-blocks": ([4, 4, 1, 4], 4, 9, 16, np.float32, 0, 0, None),
    "tail-group": ([3, 1, 2, 3, 0, 2, 1], 3, 8, 16, np.float32, 3, 0, None),
    "repeated-tiles": ([3, 2, 3, 1], 3, 5, 16, np.float32, 2, 0,
                       [[2, 2, 2], [2, 4], [4, 4, 1], [1]]),
    "f-not-128": ([2, 3, 1], 3, 7, 200, np.float32, 0, 0, None),
    "float16": ([3, 1, 2, 2], 3, 6, 16, np.float16, 2, 0, None),
    "wide-group-1": ([512, 5, 0], 512, 600, 16, np.float32, 0, 0, None),
    "wide-chunked": ([1024, 5, 700], 1024, 1100, 16, np.float32, 0, 0,
                     None),
}


@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_bcsr_spmm_walks_populated_slots(case):
    """Only populated slots are walked, in groups of row blocks (a tail
    group where the group does not divide the rows) or, for a wide ELL, one
    row block and a chunk of its slots at a time; the result equals the
    densified reference."""
    from repro.kernels.bcsr_spmm import _split_spmm, step_shape
    from repro.kernels.ref import bcsr_spmm_ref

    n_tiles, ell_w, n_ct, f, dtype, group, chunk, cols = _WALK_CASES[case]
    blocks, col, nt = _ell_arrays(n_tiles, ell_w, n_ct, seed=len(case),
                                  cols=cols, dtype=dtype)
    h = np.random.default_rng(4).standard_normal((n_ct * 8, f)).astype(dtype)
    if case.startswith("wide"):
        auto = step_shape(len(nt), ell_w, 8, 8, f, dtype, dtype, np.float32)
        assert auto[0] == 1 and (auto[1] < ell_w) == (case == "wide-chunked")
    out = _split_spmm(jnp.asarray(blocks), jnp.asarray(col), jnp.asarray(nt),
                      jnp.asarray(h), bm=8, bk=8, interpret=True,
                      out_dtype=jnp.float32, rows=len(nt), group=group,
                      chunk=chunk)
    ref = np.asarray(bcsr_spmm_ref(blocks, col, nt, h, bm=8, bk=8))
    # float32 sums of the same products: apart by rounding, which scales
    # with the sum of their magnitudes.
    scale = np.asarray(bcsr_spmm_ref(np.abs(blocks), col, nt, np.abs(h),
                                     bm=8, bk=8))
    assert np.all(np.abs(np.asarray(out) - ref) <= 1e-6 * scale + 1e-30)


def test_step_shape_fits_vmem():
    """A step's double-buffered bricks and output block and the H ring fit
    the VMEM budget: the group shrinks as the ELL widens, to one row block
    and then to a chunk of its slots; a fixed group of 15 would not fit."""
    from repro.kernels.bcsr_spmm import (RING, VMEM_STEP_BYTES, _vmem_bytes,
                                         grid_steps, step_shape)

    def step_bytes(group, chunk):
        return (2 * group * chunk * _vmem_bytes((8, 8), np.float32)
                + 2 * _vmem_bytes((group * 8, 256), np.float32)
                + RING * _vmem_bytes((8, 256), np.float32))

    assert _vmem_bytes((8, 8), np.float32) == 8 * 128 * 4
    assert _vmem_bytes((8, 8), np.float16) == 16 * 128 * 2
    assert step_shape(1008, 64, 8, 8, 256, *[np.float32] * 3) == (15, 64)
    assert grid_steps(13784, 64, 8, 8, 256, np.float32, np.float32) == 14 * 68
    last = None
    for ell_w in (16, 64, 512, 1024, 4096):
        group, chunk = step_shape(64, ell_w, 8, 8, 256, *[np.float32] * 3)
        assert step_bytes(group, chunk) <= VMEM_STEP_BYTES
        assert last is None or group <= last
        last = group
    assert (group, chunk < 4096) == (1, True)
    assert step_bytes(15, 4096) > VMEM_STEP_BYTES


def test_bcsr_spmm_span_counts(monkeypatch):
    """`aires.kernel` carries the grid steps and, from a host copy of
    n_tiles only, the bricks walked."""
    import contextlib
    from repro.kernels import ops
    from repro.kernels.bcsr_spmm import grid_steps

    seen = []
    monkeypatch.setattr(ops, "span", lambda name, **kw: (
        seen.append((name, kw)), contextlib.nullcontext())[1])
    dense = _rand_sparse(40, 40, 0.2, np.float32, seed=9)
    ell = tile_csr_to_block_ell(csr_from_dense(dense), bm=8, bk=8)
    h = jnp.asarray(np.ones((40, 16), np.float32))
    bcsr_spmm(ell, h, bn=8)
    steps = grid_steps(5, ell.ell_width, 8, 8, 16, np.float32, np.float32)
    assert seen[0] == ("kernel", dict(rows=40, grid_steps=steps,
                                      bricks=int(ell.n_tiles.sum())))
    seen.clear()
    dev = dataclasses.replace(ell, blocks=jnp.asarray(ell.blocks),
                              col_tile=jnp.asarray(ell.col_tile),
                              n_tiles=jnp.asarray(ell.n_tiles))
    bcsr_spmm(dev, h, bn=8)
    assert seen[0] == ("kernel", dict(rows=40, grid_steps=steps))
    bcsr_spmm(dev, h, bn=8, bricks=7)
    assert seen[2] == ("kernel", dict(rows=40, grid_steps=steps, bricks=7))


@pytest.mark.parametrize("b,nq,nkv,s,d", [
    (2, 8, 2, 64, 16), (1, 4, 4, 32, 8), (3, 16, 4, 48, 32),
])
def test_decode_attention(b, nq, nkv, s, d):
    rng = np.random.default_rng(b * s)
    q = rng.standard_normal((b, nq, d)).astype(np.float32)
    k = rng.standard_normal((b, nkv, s, d)).astype(np.float32)
    v = rng.standard_normal((b, nkv, s, d)).astype(np.float32)
    lens = rng.integers(1, s + 1, size=(b,)).astype(np.int32)
    out = np.asarray(decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lens),
        block_s=16))
    ref = np.asarray(decode_attention_ref(
        q.reshape(b, nkv, nq // nkv, d), k, v, lens)).reshape(b, nq, d)
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_decode_attention_full_vs_short_lens():
    """Padding KV past `lens` must not change the result."""
    rng = np.random.default_rng(0)
    b, nq, nkv, s, d = 2, 4, 2, 32, 16
    q = rng.standard_normal((b, nq, d)).astype(np.float32)
    k = rng.standard_normal((b, nkv, s, d)).astype(np.float32)
    v = rng.standard_normal((b, nkv, s, d)).astype(np.float32)
    lens = np.array([10, 20], np.int32)
    out1 = np.asarray(decode_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), jnp.asarray(lens),
                                       block_s=8))
    k2 = k.copy(); v2 = v.copy()
    k2[:, :, 25:] = 999.0; v2[:, :, 25:] = -999.0  # poison beyond lens
    out2 = np.asarray(decode_attention(jnp.asarray(q), jnp.asarray(k2),
                                       jnp.asarray(v2), jnp.asarray(lens),
                                       block_s=8))
    np.testing.assert_allclose(out1, out2, atol=1e-5)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
@pytest.mark.parametrize("b,h,s,d", [(2, 3, 64, 16), (1, 2, 48, 32)])
def test_flash_attention(b, h, s, d, causal, window):
    from repro.kernels import flash_attention
    from repro.kernels.ref import flash_attention_ref
    rng = np.random.default_rng(b * s + d)
    q = rng.standard_normal((b, h, s, d)).astype(np.float32)
    k = rng.standard_normal((b, h, s, d)).astype(np.float32)
    v = rng.standard_normal((b, h, s, d)).astype(np.float32)
    out = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window, block_q=16, block_k=16))
    ref = np.asarray(flash_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, window=window))
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_flash_attention_dtype_bf16():
    from repro.kernels import flash_attention
    from repro.kernels.ref import flash_attention_ref
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
