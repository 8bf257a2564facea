"""The benchmark's own graph generators and GCN normalization.

Both draw `n_edges` directed edges with uniformly random sources, then drop
parallel edges; a symmetric graph draws half as many and adds each one's
reverse, so that `n_edges` counts arcs, as an undirected graph's published
nonzero count does. The generators differ in where an edge's destination
lies:

  uniform  anywhere in [0, n): no locality (k-mer graphs, kV2a).
  banded   within +-span of the source, wrapped mod n (road graphs, rUSA).

Endpoints wrap; nothing is clipped, so no vertex collects the overflow of
the band as a hub. Â = D̂^-1/2 (A + I) D̂^-1/2 with D̂ the row degrees of
A + I (paper Eq. 2), as float32 CSR arrays sorted by row, then column.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Csr(NamedTuple):
    indptr: np.ndarray     # (n + 1,) int64
    indices: np.ndarray    # (nnz,) int64
    data: np.ndarray       # (nnz,) float32
    n: int

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def rows(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         np.diff(self.indptr))


def uniform_edges(n: int, m: int, rng: np.random.Generator):
    rows = rng.integers(0, n, size=m, dtype=np.int64)
    cols = rng.integers(0, n, size=m, dtype=np.int64)
    return rows, cols


def banded_edges(n: int, m: int, rng: np.random.Generator, band: int):
    """Destinations within +-n//band of the source, wrapped mod n."""
    rows = rng.integers(0, n, size=m, dtype=np.int64)
    span = max(1, n // band)
    offs = rng.integers(-span, span + 1, size=m, dtype=np.int64)
    return rows, np.mod(rows + offs, n)


GENERATORS = {"uniform": uniform_edges, "banded": banded_edges}


def _csr(n: int, rows: np.ndarray, cols: np.ndarray) -> Csr:
    """Unit-weight CSR of the distinct (row, col) pairs, sorted."""
    key = np.unique(rows * n + cols)
    rows, cols = key // n, key % n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return Csr(indptr, cols, np.ones(cols.shape[0], np.float32), n)


def generate(family: str, n: int, m: int, seed: int, symmetric: bool = False,
             **params) -> Csr:
    """Adjacency A (distinct edges, unit weights), of m arcs."""
    rng = np.random.default_rng(seed)
    rows, cols = GENERATORS[family](n, m // 2 if symmetric else m, rng,
                                    **params)
    if symmetric:
        rows, cols = np.concatenate([rows, cols]), np.concatenate([cols, rows])
    return _csr(n, rows, cols)


def normalize(a: Csr) -> Csr:
    """Â = D̂^-1/2 (A + I) D̂^-1/2, D̂ the row degrees of A + I."""
    n = a.n
    diag = np.arange(n, dtype=np.int64)
    s = _csr(n, np.concatenate([a.rows(), diag]),
             np.concatenate([a.indices, diag]))
    dinv = 1.0 / np.sqrt(np.diff(s.indptr).astype(np.float64))
    data = (dinv[s.rows()] * dinv[s.indices]).astype(np.float32)
    return Csr(s.indptr, s.indices, data, n)


def build(config: dict) -> Csr:
    """Â of a configuration: its vertex and edge counts and `graph`."""
    graph = config["graph"]
    params = {k: graph[k] for k in ("band", "symmetric") if k in graph}
    return normalize(generate(graph["family"], config["n_vertices"],
                              config["n_edges"], graph["graph_seed"],
                              **params))
