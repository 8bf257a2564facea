"""Gradient correctness of the differentiable streaming SpGEMM.

`AiresSpGEMM.__call__` carries a custom VJP whose backward streams the
transposed RoBW plan (dH = Aᵀ dX). Every test here checks `jax.grad`
through the *streamed* path against the dense `(A @ H)` reference gradient:
if they match, the transposed plan covers each nonzero exactly once and the
block-ELL backward kernel is exact.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import AiresConfig, AiresSpGEMM, FeatureSpec, gcn_epoch
from repro.io.tiers import PAPER_GPU_SYSTEM


def _engine(a, h_nbytes, frac=0.8, **kw):
    budget = int((a.nbytes() + 3 * h_nbytes) * frac) + 4096
    return AiresSpGEMM(AiresConfig(device_budget_bytes=budget,
                                   bm=8, bk=8, **kw))


def _case(make_sparse, n, m, f, density=0.25, seed=0, dtype=np.float32):
    # matrices come from the shared conftest factory; features are drawn
    # separately so the case is fully determined by (n, m, f, density, seed)
    a, dense = make_sparse(n, m, density=density, seed=seed)
    h = np.random.default_rng(seed + 1).standard_normal((m, f)).astype(dtype)
    return a, dense, h


# ≥3 shapes; (33, 57, 24) and (41, 23, 12) are ragged (n % bm != 0).
SHAPES = [(16, 16, 8), (40, 24, 16), (33, 57, 24), (41, 23, 12)]


@pytest.mark.parametrize("n,m,f", SHAPES)
def test_grad_matches_dense_f32(n, m, f, make_sparse):
    a, dense, h = _case(make_sparse, n, m, f, seed=n * m + f)
    eng = _engine(a, h.nbytes)

    def loss(h_):
        return jnp.sum(jnp.sin(eng(a, h_)))

    def loss_ref(h_):
        return jnp.sum(jnp.sin(jnp.asarray(dense) @ h_))

    g = jax.grad(loss)(jnp.asarray(h))
    g_ref = jax.grad(loss_ref)(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)
    assert eng.last_backward_stream_stats is not None
    assert eng.last_backward_stream_stats.segments >= 1
    assert eng.last_backward_stream_stats.uploaded_bytes > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_dtypes(dtype, make_sparse):
    a, dense, h_np = _case(make_sparse, 40, 40, 16, seed=7)
    eng = _engine(a, h_np.nbytes)
    h = jnp.asarray(h_np, dtype)

    g = jax.grad(lambda h_: jnp.sum(eng(a, h_)))(h)
    g_ref = jax.grad(
        lambda h_: jnp.sum(jnp.asarray(dense, dtype) @ h_))(h)
    assert g.dtype == dtype  # custom VJP must return the primal dtype
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_ref, np.float32), atol=atol)


def test_grad_streams_multiple_transposed_segments(make_sparse):
    """A tight budget must force the backward pass to stream ≥2 segments of
    the transposed plan — the out-of-core regime, not a degenerate single
    upload."""
    a, dense, h = _case(make_sparse, 64, 64, 16, density=0.3, seed=3)
    eng = _engine(a, h.nbytes, frac=0.35)

    g = jax.grad(lambda h_: jnp.sum(eng(a, h_) ** 2))(jnp.asarray(h))
    g_ref = jax.grad(
        lambda h_: jnp.sum((jnp.asarray(dense) @ h_) ** 2))(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-3)
    assert eng.last_stream_stats.segments >= 2, "forward should stream"
    assert eng.last_backward_stream_stats.segments >= 2, \
        "backward should stream the transposed plan"


def test_gcn_model_grads_out_of_core(make_sparse):
    """Full GCN param grads via gcn_loss with the streamed engine vs the
    dense in-core path — covers W and bias grads of every layer."""
    import dataclasses
    from repro.models import GCNConfig, gcn_init, gcn_loss
    from repro.sparse import csr_to_dense

    a, dense, h = _case(make_sparse, 40, 40, 16, seed=2)
    cfg = GCNConfig(feature_dim=16, hidden_dims=(16,), n_classes=4,
                    out_of_core=True)
    params = gcn_init(cfg, jax.random.PRNGKey(0))
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 4, size=(a.n_rows,)))
    eng = _engine(a, h.nbytes)
    h0 = jnp.asarray(h)

    g_ooc = jax.grad(lambda p: gcn_loss(cfg, p, a, h0, labels,
                                        engine=eng))(params)
    cfg_ic = dataclasses.replace(cfg, out_of_core=False)
    g_ic = jax.grad(lambda p: gcn_loss(cfg_ic, p, jnp.asarray(dense), h0,
                                       labels))(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_ooc[k]), np.asarray(g_ic[k]),
                                   atol=1e-4, err_msg=k)
    # one backward stream per layer boundary that needs dH
    assert len(eng.backward_stats_log) >= 1


def test_gcn_epoch_execute_reports_phase_stats(make_sparse):
    """Execute-mode epochs must report separate forward/backward
    StreamStats, with the backward really streaming transposed segments."""
    a, dense, h0 = _case(make_sparse, 48, 48, 16, density=0.3, seed=9)
    rng = np.random.default_rng(0)
    ws = [rng.standard_normal((16, 16)).astype(np.float32),
          rng.standard_normal((16, 8)).astype(np.float32)]
    budget = int((a.nbytes() + 3 * h0.nbytes) * 0.5) + 4096
    em = gcn_epoch(
        a, h0, ws, "aires", PAPER_GPU_SYSTEM, budget, mode="execute",
        engine_config=AiresConfig(device_budget_bytes=budget, bm=8, bk=8))
    assert len(em.forward_stream) == len(ws)
    assert len(em.backward_stream) == len(ws)
    for s in em.forward_stream + em.backward_stream:
        assert s.segments >= 1
        assert s.uploaded_bytes > 0
    assert em.wall_seconds > 0
    assert len(em.per_layer) == len(ws)
    assert len(em.per_layer_backward) == len(ws)
    assert em.epoch_makespan_s > 0 and np.isfinite(em.epoch_makespan_s)


def test_gcn_epoch_simulate_keeps_backward_factor(make_sparse):
    """Simulate mode still uses the paper's modeled backward multiplier."""
    a, _, _ = _case(make_sparse, 48, 48, 16, density=0.3, seed=9)
    feat = FeatureSpec(a.n_rows, 16, 4, 0.0)
    ws = [np.zeros((16, 16), np.float32)] * 2
    budget = int(2.5 * a.nbytes()) + (1 << 16)
    em1 = gcn_epoch(a, feat, ws, "aires", PAPER_GPU_SYSTEM, budget,
                    mode="simulate", backward_factor=1.0)
    em2 = gcn_epoch(a, feat, ws, "aires", PAPER_GPU_SYSTEM, budget,
                    mode="simulate", backward_factor=3.0)
    np.testing.assert_allclose(em2.epoch_makespan_s / em1.epoch_makespan_s,
                               2.0, rtol=1e-6)
    assert not em1.forward_stream and not em1.backward_stream


@pytest.mark.slow
def test_out_of_core_training_descends(make_sparse):
    """A few real out-of-core optimizer steps: loss must go down with every
    gradient coming through the streamed custom VJP."""
    from repro.models import GCNConfig, gcn_init
    from repro.train import gcn_train_loop

    a, dense, h = _case(make_sparse, 40, 40, 16, seed=4)
    cfg = GCNConfig(feature_dim=16, hidden_dims=(16,), n_classes=4,
                    out_of_core=True)
    params = gcn_init(cfg, jax.random.PRNGKey(0))
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 4, size=(a.n_rows,)))
    eng = _engine(a, h.nbytes)
    params, info = gcn_train_loop(cfg, eng, a, jnp.asarray(h), labels,
                                  params, n_epochs=8, lr=5e-2)
    losses = [l for _, l in info["history"]]
    assert losses[-1] < 0.8 * losses[0]
    # every epoch recorded both phases
    for ep in info["epochs"]:
        assert len(ep["forward_stream"]) == 2   # two layers
        assert all(s.segments >= 1 for s in ep["backward_stream"])
