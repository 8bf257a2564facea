"""Compile the Block-ELL kernels for a described TPU v5e, with no chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described rather than attached, so these tests catch what interpret
mode cannot: a kernel refused for more SMEM or VMEM than it may use, or a
program that does not fit the chip. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bcsr_spmm import bcsr_spmm_pallas
from repro.kernels.gat_attn import gat_attn_pallas, pack_sources, padded_width
from repro.models.gat import scores

V5E_HBM_BYTES = 16 * 10**9
F = 256   # gcn_paper feature width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    previous = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if previous is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    previous = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", previous)
    compilation_cache.reset_cache()


# (n_rb, ell_w, K) with bm = bk = 8. The first two are the largest forward
# and transposed segments chip_smoke.py streams at its default scale (1e-2,
# seed 0: 550,400 rows; it prints them on its early lines). A 2-D
# (4096, 16) tile table, padded to 128 SMEM lanes, needs 2 MiB of v5e's
# 1 MiB SMEM: the kernel must split such a segment over several calls. One
# row block of a 4096-wide ELL takes 32 MiB of double-buffered bricks in
# VMEM, twice v5e's scoped limit: a grid step must take a chunk of its slots.
SEGMENTS = {
    "smoke-forward": (13784, 64, 550400),
    "smoke-transposed": (13776, 64, 550400),
    "smem-4096x16": (4096, 16, 32768),
    "wide-64x4096": (64, 4096, 32768),
}


@pytest.mark.parametrize("name", list(SEGMENTS))
def test_bcsr_spmm_compiles_for_v5e(name, one_chip, no_persistent_cache):
    n_rb, ell_w, k = SEGMENTS[name]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = bcsr_spmm_pallas.lower(
        spec((n_rb, ell_w, 8, 8), jnp.float32),
        spec((n_rb, ell_w), jnp.int32),
        spec((n_rb,), jnp.int32),
        spec((k, F), jnp.float32),
        bm=8, bk=8, bn=128)
    assert "tpu_custom_call" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES // 4, used


# (n_rb, ell_w, heads, head width): the largest segment of the rUSA.gat_serve
# plan (478,800 rows at plan width 1,024: 11,991 row blocks, ELL 64), at
# gat_ppi's layers 1-2 (4 x 256) and layer 3 (6 x 121, padded per head to
# 128); and a row block too wide for VMEM, whose slots come in chunks.
GAT_SEGMENTS = {
    "rusa-4x256": (11991, 64, 4, 256),
    "rusa-6x121": (11991, 64, 6, 121),
    "wide-64x4096": (64, 4096, 4, 256),
}
GAT_ROWS = 478800


@pytest.mark.parametrize("name", list(GAT_SEGMENTS))
def test_gat_attn_compiles_for_v5e(name, one_chip, no_persistent_cache):
    n_rb, ell_w, heads, hw = GAT_SEGMENTS[name]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = gat_attn_pallas.lower(
        spec((n_rb, ell_w, 8, 8), jnp.float32),
        spec((n_rb, ell_w), jnp.int32),
        spec((n_rb,), jnp.int32),
        spec((GAT_ROWS, heads * padded_width(hw) + 128), jnp.float32),
        spec((n_rb * 8, heads), jnp.float32),
        heads=heads, head_width=hw, negative_slope=0.2, bm=8, bk=8)
    assert "tpu_custom_call" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES // 2, used


# Per head: (heads, head width). Splitting z's 726 lanes into (6, 121), or
# transposing the scores within a tile, took the v5e compiler 85-108 s at
# 478,800 rows; the column slices and lane gather that replace them, about
# 2 s. A request compiles these once per layer shape, in its set-up.
QUICK_COMPILE_S = 30


@pytest.mark.parametrize("heads,hw", [(4, 256), (6, 121)])
def test_gat_sources_and_scores_compile_quickly(heads, hw, one_chip,
                                                no_persistent_cache):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    for fn, args, kw in (
            (pack_sources, (spec((GAT_ROWS, heads * hw)),
                            spec((GAT_ROWS, heads))),
             dict(heads=heads, head_width=hw, bk=8)),
            (jax.jit(scores), (spec((GAT_ROWS, heads * hw)),
                               spec((heads, hw))), {})):
        t = time.perf_counter()
        fn.lower(*args, **kw).compile()
        assert time.perf_counter() - t < QUICK_COMPILE_S, fn
