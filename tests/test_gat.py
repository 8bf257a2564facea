"""GAT: the attention kernel, the streamed pass and the serving engine
against plain references, on seeded random weights at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/test_gat.py

The kernel runs in interpret mode against `gat_attn_ref` (dense masked
softmax); `AiresSpGEMM.attend` over several segments against one segment;
`ServingEngine` serving a GAT request beside a GCN request against
`gat_reference` (edge list, `segment_max` / `segment_sum`) and the dense
GCN. TOL is the tolerance of every end-to-end comparison: float32 sums
taken in another order (the kernel's running softmax against the
reference's segment sums) through three layers and exp, which read under
3e-7 here; a single bfloat16 pass in the projections errs by about 1e-3,
and the last test checks that TOL refuses it.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.gat_ppi import CONFIG, SMOKE
from repro.core import AiresConfig, AiresSpGEMM, plan_memory_dense_features
from repro.kernels.gat_attn import _split_attn, pack_sources, step_shape
from repro.kernels.ref import gat_attn_ref
from repro.models import gat
from repro.models.gat import gat_forward, gat_init, gat_reference
from repro.sparse import csr_from_dense

TOL = 1e-5


def _rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _mask_bricks(n_tiles, ell_w, n_col_tiles, seed):
    """Block-ELL arrays whose populated bricks hold positive values at
    about a third of their entries, as Â's bricks do (a brick row may
    hold none); padded slots hold zero bricks and column tile -1."""
    rng = np.random.default_rng(seed)
    n_tiles = np.asarray(n_tiles, np.int32)
    col = np.full((len(n_tiles), ell_w), -1, np.int32)
    blocks = np.zeros((len(n_tiles), ell_w, 8, 8), np.float32)
    for rb, n in enumerate(n_tiles):
        col[rb, :n] = np.sort(rng.choice(n_col_tiles, n, replace=False))
        blocks[rb, :n] = ((rng.random((n, 8, 8)) < 0.3)
                          * (0.1 + rng.random((n, 8, 8))))
    return blocks, col, n_tiles


# (populated slots per row block, ell_w, column tiles, heads, head width,
#  group, chunk, LeakyReLU's negative slope): 0 for group and chunk lets
#  the kernel choose them.
_ATTN_CASES = {
    "1-head-256": ([2, 0, 3, 1], 3, 6, 1, 256, 0, 0, 0.2),
    "4-heads-256-tail-group": ([3, 1, 2, 3, 0, 2, 1], 3, 8, 4, 256, 3, 0,
                               0.2),
    "6-heads-121": ([2, 3, 1, 4], 4, 7, 6, 121, 0, 0, 0.2),
    "4-heads-121-chunks": ([5, 2, 7, 0], 8, 9, 4, 121, 1, 3, 0.2),
    "6-heads-121-wide-chunked": ([1024, 5, 700], 1024, 1100, 6, 121, 0, 0,
                                 0.2),
    "4-heads-256-slope-0.01": ([3, 2, 4, 1], 4, 6, 4, 256, 0, 0, 0.01),
}


@pytest.mark.parametrize("case", list(_ATTN_CASES))
def test_gat_attn_matches_dense_reference(case):
    """Heads of 121 (padded to 128 inside the call) and 256; a tail group;
    a row block's slots in chunks, forced and (for a 1,024-wide ELL) chosen
    by `step_shape`, so that the running softmax crosses grid steps."""
    n_tiles, ell_w, n_ct, heads, hw, group, chunk, slope = _ATTN_CASES[case]
    blocks, col, nt = _mask_bricks(n_tiles, ell_w, n_ct, seed=len(case))
    rng = np.random.default_rng(heads * hw)
    z = rng.standard_normal((n_ct * 8, heads * hw)).astype(np.float32)
    s_src = rng.standard_normal((n_ct * 8, heads)).astype(np.float32)
    s_dst = rng.standard_normal((len(nt) * 8, heads)).astype(np.float32)
    if case.endswith("wide-chunked"):
        auto = step_shape(len(nt), ell_w, 8, 8, heads * 128, np.float32,
                          np.float32, np.float32, ring_width=heads * 128 + 128)
        assert auto[0] == 1 and auto[1] < ell_w
    zs = pack_sources(jnp.asarray(z), jnp.asarray(s_src), heads=heads,
                      head_width=hw, bk=8)
    out = _split_attn(jnp.asarray(blocks), jnp.asarray(col), jnp.asarray(nt),
                      zs, jnp.asarray(s_dst), heads=heads, head_width=hw,
                      negative_slope=slope, bm=8, bk=8, interpret=True,
                      rows=len(nt), group=group, chunk=chunk)
    out = np.asarray(out).reshape(len(nt) * 8, heads, -1)[:, :, :hw]
    ref = np.asarray(gat_attn_ref(blocks, col, nt, z, s_src, s_dst,
                                  heads=heads, head_width=hw, bm=8, bk=8,
                                  slope=slope))
    # A convex combination of z's rows: float32 rounding of its weights
    # and sums, on the scale of |z|.
    assert np.max(np.abs(out - ref)) <= 2e-6 * np.max(np.abs(z))
    # Rows that attend to nothing (empty row blocks, padding) give zeros.
    empty = np.repeat(np.asarray(n_tiles) == 0, 8)
    assert np.all(out[empty] == 0)


def _graph(n, seed, density=0.05):
    """Â-like values on A + I of a symmetric random graph: the dense
    matrix, its CSR, and the (rows, cols) edge list of A + I."""
    rng = np.random.default_rng(seed)
    adj = rng.random((n, n)) < density
    adj = adj | adj.T | np.eye(n, dtype=bool)
    dense = (adj * (0.1 + rng.random((n, n)))).astype(np.float32)
    rows, cols = np.nonzero(adj)
    return dense, csr_from_dense(dense), (jnp.asarray(rows),
                                          jnp.asarray(cols))


def _budget(a, n, width, share):
    est = plan_memory_dense_features(a, n, width, float("inf"))
    return int(est.m_b + est.m_c + share * a.nbytes())


def test_attend_over_segments_matches_one_segment():
    """The streamed attention gives the same rows whether the plan cuts
    the graph into several segments or none."""
    n, heads, hw = 200, 4, 16
    dense, a, _ = _graph(n, seed=3)
    rng = np.random.default_rng(4)
    z = jnp.asarray(rng.standard_normal((n, heads * hw)), jnp.float32)
    s_src = jnp.asarray(rng.standard_normal((n, heads)), jnp.float32)
    s_dst = jnp.asarray(rng.standard_normal((n, heads)), jnp.float32)
    outs = []
    for share in (0.3, 100.0):
        eng = AiresSpGEMM(AiresConfig(
            device_budget_bytes=_budget(a, n, heads * hw, share), bm=8, bk=8))
        outs.append(np.asarray(eng.attend(a, z, s_src, s_dst, heads, 0.2)))
        outs.append(eng.last_stream_stats.segments)
    many, n_many, one, n_one = outs
    assert n_many >= 3 and n_one == 1
    assert many.shape == (n, heads, hw)
    np.testing.assert_array_equal(many, one)
    want = gat._attend_dense(
        gat.GATConfig(heads=(heads,), head_dims=(hw,), concat=(True,),
                      skip=(False,)), 0, jnp.asarray(dense != 0), z, s_src,
        s_dst)
    assert _rel_gap(many, want) < TOL


def _serve(monkeypatch=None, n=160, seed=5, cfg=SMOKE):
    """A GAT request (SMOKE widths) and a GCN request served in one batch
    over a multi-segment plan; (GAT output, its reference, GCN output, its
    reference, batch report)."""
    from repro.runtime import EngineConfig, InferenceRequest, ServingEngine

    dense, a, edges = _graph(n, seed)
    params = gat_init(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    h0 = rng.standard_normal((n, cfg.feature_dim)).astype(np.float32)
    w = max(cfg.stream_width(layer) for layer in range(cfg.n_layers))
    engine = ServingEngine(EngineConfig(
        device_budget_bytes=_budget(a, n, w, 0.4), max_batch_features=w))
    engine.register_graph("g", a)
    ws = [rng.standard_normal((cfg.feature_dim, 16)).astype(np.float32),
          rng.standard_normal((16, 8)).astype(np.float32)]
    host = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    r_gat = engine.submit(InferenceRequest("g", h0, host, model=cfg))
    r_gcn = engine.submit(InferenceRequest("g", h0, ws))
    report = engine.run_batch()
    out = {r.request_id: r.output for r in report.results}
    gcn_ref = dense @ np.maximum(dense @ h0 @ ws[0], 0.0) @ ws[1]
    gat_ref = np.asarray(gat_reference(params, edges, jnp.asarray(h0), cfg))
    return out[r_gat], gat_ref, out[r_gcn], gcn_ref, report


def test_engine_serves_gat_beside_gcn():
    got, want, gcn_got, gcn_want, report = _serve()
    assert got.shape == want.shape == (160, SMOKE.head_dims[-1])
    assert _rel_gap(got, want) < TOL
    assert _rel_gap(gcn_got, gcn_want) < TOL
    # Three attention passes and the GCN's two, each over several segments.
    assert report.aggregation_passes == 5
    assert report.segments_streamed >= 5 * 2


def test_engine_uses_the_configured_negative_slope():
    """The served request follows its config's LeakyReLU slope: it matches
    the reference at that slope and not at the paper's 0.2."""
    cfg = dataclasses.replace(SMOKE, negative_slope=0.01)
    got, want, _, _, _ = _serve(cfg=cfg)
    assert _rel_gap(got, want) < TOL
    h0 = np.random.default_rng(5).standard_normal(
        (160, cfg.feature_dim)).astype(np.float32)
    _, _, edges = _graph(160, 5)
    at_02 = gat_reference(gat_init(cfg, jax.random.PRNGKey(5)), edges,
                          jnp.asarray(h0), SMOKE)
    assert _rel_gap(got, at_02) > 10 * TOL


def test_gat_request_streams_the_gcn_bricks():
    """GAT and GCN requests of one engine share the plan and its cached
    bricks: a GAT request after a GCN one uploads nothing new."""
    from repro.runtime import EngineConfig, InferenceRequest, ServingEngine

    cfg = SMOKE
    n = 120
    _, a, _ = _graph(n, seed=6)
    w = max(cfg.stream_width(layer) for layer in range(cfg.n_layers))
    engine = ServingEngine(EngineConfig(
        device_budget_bytes=_budget(a, n, w, 0.4), max_batch_features=w,
        cache_device_bytes=1 << 30))
    engine.register_graph("g", a)
    rng = np.random.default_rng(7)
    h0 = rng.standard_normal((n, cfg.feature_dim)).astype(np.float32)
    engine.submit(InferenceRequest(
        "g", h0, [rng.standard_normal((cfg.feature_dim, 8))
                  .astype(np.float32)]))
    first = engine.run_batch()
    params = [{k: np.asarray(v) for k, v in p.items()}
              for p in gat_init(cfg, jax.random.PRNGKey(1))]
    req = InferenceRequest("g", h0, params, model=cfg)
    assert engine.estimate_request_cost(req) > 0
    engine.submit(req)
    second = engine.run_batch()
    assert first.uploaded_bytes > 0
    assert second.uploaded_bytes == 0 and second.cache_hit_bytes > 0


def test_one_bfloat16_pass_is_refused(monkeypatch):
    """The projections computed in one bfloat16 pass (operands rounded to
    bfloat16, products summed in float32) land outside TOL."""
    original = gat.project.__wrapped__

    def one_pass(cfg, layer, p, h):
        bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        return original(cfg, layer, {k: bf16(v) for k, v in p.items()},
                        bf16(h))

    monkeypatch.setattr(gat, "project", jax.jit(one_pass,
                                                static_argnums=(0, 1)))
    got, want, _, _, _ = _serve()
    assert _rel_gap(got, want) > 10 * TOL


def test_config_has_the_published_widths():
    assert (CONFIG.feature_dim, CONFIG.heads, CONFIG.head_dims) == (
        256, (4, 4, 6), (256, 256, 121))
    assert CONFIG.concat == (True, True, False)
    assert CONFIG.skip == (False, True, False)
    assert [CONFIG.stream_width(layer) for layer in range(3)] == [
        1024, 1024, 726]
    assert CONFIG.out_width(2) == 121
    from repro.configs import arch_ids

    assert "gat_ppi" not in arch_ids()


def test_in_core_forward_matches_reference():
    cfg = SMOKE
    dense, _, edges = _graph(64, seed=8, density=0.1)
    params = gat_init(cfg, jax.random.PRNGKey(2))
    h0 = jnp.asarray(np.random.default_rng(9).standard_normal(
        (64, cfg.feature_dim)), jnp.float32)
    got = gat_forward(cfg, params, jnp.asarray(dense), h0)
    assert _rel_gap(got, gat_reference(params, edges, h0, cfg)) < TOL
