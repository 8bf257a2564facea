"""Shared fixtures + markers for the AIRES test suite.

Tier split (see README "Testing"):
  * fast tier — `pytest -m "not slow"`: runs on every PR.
  * full tier — `pytest`: runs on main; adds the long streaming/training sweeps.

The `slow` marker is registered here (and in pyproject.toml) so the fast
subset never warns on unknown markers.
"""
import os
import sys

import numpy as np
import pytest

# The golden-equality tests reuse the benchmark configs (benchmarks.common
# builds the fig6 datasets/budgets); the benchmarks package lives at the
# repo root, which is not on sys.path when only PYTHONPATH=src is set.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (excluded from the PR-tier fast subset)")


@pytest.fixture(autouse=True, scope="session")
def _analyze_plans_by_default():
    """Static plan analysis is on for the whole suite: every plan any test
    interprets (`CostInterpreter` / `ExecuteInterpreter.run`) is checked by
    `repro.core.analysis` first, and an error-severity finding raises
    `PlanAnalysisError`. Production keeps the default off; tests that
    deliberately interpret a broken plan opt out with `analyze=False`. A
    real `AiresSpGEMM` stream interprets no plan, here as on the chip: its
    stream plans are checked where a test builds and analyzes them
    (`AiresSpGEMM.stream_plan`, `PassPipeline(strict=True)`)."""
    from repro.core import analysis

    previous = analysis.set_default_analyze(True)
    yield
    analysis.set_default_analyze(previous)


@pytest.fixture(scope="session")
def assert_metrics_match():
    """Check `ScheduleMetrics` fields against a golden record: integers,
    booleans and byte counts exactly, floats to a relative 1e-12. A real
    accounting change moves a modeled time by far more than that; the
    order of a float sum moves it by an ULP."""

    def check(metrics, want, fields, label):
        for field in fields:
            got, exp = getattr(metrics, field), want[field]
            if isinstance(exp, dict):
                assert set(got) == set(exp), f"{label}.{field}: keys differ"
                pairs = [(f"{field}[{k}]", got[k], exp[k]) for k in exp]
            else:
                pairs = [(field, got, exp)]
            for name, g, w in pairs:
                if isinstance(w, float):
                    ok = g == pytest.approx(w, rel=1e-12, abs=0.0)
                else:
                    ok = g == w
                assert ok, f"{label}.{name}: {g!r} != golden {w!r}"

    return check


@pytest.fixture(scope="session")
def make_sparse():
    """Factory for small random sparse matrices: (CSR, dense) pairs.

    Deterministic per (n, m, density, seed) so session-scoped reuse is safe.
    """
    from repro.sparse import csr_from_dense

    def _make(n, m, density=0.2, seed=0, dtype=np.float32):
        rng = np.random.default_rng(seed)
        dense = ((rng.random((n, m)) < density)
                 * rng.standard_normal((n, m))).astype(dtype)
        return csr_from_dense(dense), dense

    return _make


@pytest.fixture(scope="session")
def paper_graph():
    """A scaled paper dataset adjacency (normalized), shared across modules."""
    from repro.data import (
        SUITESPARSE_SPECS, generate_graph, normalized_adjacency, scaled_spec,
    )

    spec = scaled_spec(SUITESPARSE_SPECS["kV2a"], 2e-4)
    a = normalized_adjacency(generate_graph(spec, seed=3))
    a.validate()
    return a


@pytest.fixture(scope="session")
def paper_feats(paper_graph):
    rng = np.random.default_rng(0)
    return rng.standard_normal((paper_graph.n_rows, 16)).astype(np.float32)
