"""Graph generators, token pipeline, and sharding-rule unit tests."""
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import arch_ids, get_config
from repro.data import (
    SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
)
from repro.launch.sharding import batch_pspec, param_pspec


def test_generate_graph_counts():
    spec = scaled_spec(SUITESPARSE_SPECS["rUSA"], 1e-4)
    a = generate_graph(spec, seed=0)
    a.validate()
    assert a.n_rows == spec.n_vertices
    # dedup may remove a few parallel edges
    assert 0.5 * spec.n_edges <= a.nnz <= spec.n_edges


def test_powerlaw_has_skew():
    spec = scaled_spec(SUITESPARSE_SPECS["socLJ1"], 5e-4)
    a = generate_graph(spec, seed=0)
    deg = a.row_nnz()
    assert deg.max() > 10 * max(np.median(deg), 1)


def test_normalized_adjacency_spectral(tmp_path):
    spec = scaled_spec(SUITESPARSE_SPECS["rUSA"], 5e-5)
    a = normalized_adjacency(generate_graph(spec, seed=1))
    # Ã of an undirected-ish graph has rows bounded by 1 in L1 after
    # symmetric normalization; self loops guarantee nonzero diagonal.
    from repro.sparse import csr_to_dense
    d = csr_to_dense(a)
    assert (np.diag(d) > 0).all()
    # degree normalization keeps entries and spectrum bounded (A here is
    # directed, so the radius can exceed 1 slightly — bound loosely)
    assert d.max() <= 1.0 + 1e-6
    eig = np.max(np.abs(np.linalg.eigvals(d + d.T) / 2))
    assert eig < 2.5


def test_token_pipeline_sharding_partition():
    from repro.data import TokenPipeline
    full = TokenPipeline(100, 8, 8, seed=5)
    t_full, _ = full.batch_at(3)
    assert t_full.shape == (8, 8)
    shard = TokenPipeline(100, 8, 8, seed=5, shard_index=1, shard_count=4)
    t_s, _ = shard.batch_at(3)
    assert t_s.shape == (2, 8)


MESHES = [
    AbstractMesh((16, 16), ("data", "model")),
    AbstractMesh((2, 16, 16), ("pod", "data", "model")),
]


@pytest.mark.parametrize("mesh", MESHES, ids=["single", "multi"])
@pytest.mark.parametrize("arch", arch_ids())
def test_param_rules_divide(arch, mesh):
    """Every rule-produced spec must divide the dims it shards — for every
    full-size arch on both production meshes."""
    import jax
    from repro.models.stacked import init_params_stacked
    cfg = get_config(arch)
    abs_params = jax.eval_shape(
        lambda k: init_params_stacked(cfg, k), jax.random.PRNGKey(0))

    def check(path, leaf):
        spec = param_pspec(jax.tree_util.keystr(path), leaf.shape, mesh)
        for dim, axis in zip(leaf.shape, spec):
            if axis is None:
                continue
            size = 1
            for ax in (axis if isinstance(axis, tuple) else (axis,)):
                size *= mesh.shape[ax]
            assert dim % size == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, abs_params)


@pytest.mark.parametrize("mesh", MESHES, ids=["single", "multi"])
def test_batch_pspec_divisibility(mesh):
    assert batch_pspec((256, 4096), mesh)[0] is not None
    assert batch_pspec((1, 4096), mesh)[0] is None  # batch=1 replicates
