"""Plain float32 reference of the GAT the benchmark drives.

Imports nothing of the program and takes nothing it made: the edges are
A + I of the benchmark's own graph (the nonzeros of `bench.lib.graphs`'
Â), weights and inputs come from the harness's seed. Per layer z = h W;
for each row, its neighbours' logits LeakyReLU(a_dst·z_i + a_src·z_j), a
softmax over them and the weighted sum of their z; heads concatenated or
averaged, the skip, ELU between layers.

Rows are taken in order of degree and grouped by it, so that each group's
neighbours form a dense (rows, width) axis barely wider than the rows'
degrees: the softmax and the sum are reductions over that axis, and each
edge gathers its z row once (a scatter over the edges, or padding every
row to the widest, cost a TPU more per gathered row than the rest of a
request). A source's score a_src·z_j is taken from the gathered row. The
whole forward runs in that row order; `forward` takes and returns the
graph's own. `precision` is "highest" or the control "high", as in
`reference.py`, whose products it uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.reference import _mul, matmul

# Gathered slots a call takes, and rows a projection call takes: bounds
# the memory beside the served outputs.
SLOTS_PER_CALL = 1 << 15
ROWS_PER_MATMUL = 1 << 16


def _width(degree: int) -> int:
    """The group a row of this degree falls in: exact up to 4, then the
    next of 6, 8, 12, 16, 24, ..."""
    width = max(degree, 1)
    if width <= 4:
        return width
    k = (width - 1).bit_length() - 2
    return 3 << k if width <= 3 << k else 4 << k


class Pattern:
    """A + I with its rows in order of degree: `order[p]` is the row at
    position p; per group (width, first position, calls, rows a call, the
    neighbours' positions (calls * rows, width), which of them are real)."""

    def __init__(self, a):
        degree = np.diff(a.indptr)
        self.n = a.n
        self.order = np.argsort(degree, kind="stable")
        position = np.empty(a.n, np.int64)
        position[self.order] = np.arange(a.n)
        widths = np.array([_width(int(d)) for d in range(degree.max() + 1)])
        row_width = widths[degree[self.order]]
        self.groups = []
        for width in np.unique(row_width):
            lo, hi = np.searchsorted(row_width, [width, width + 1])
            rows = max(8, SLOTS_PER_CALL // int(width))
            calls = -(-(hi - lo) // rows)
            deg = degree[self.order[lo:hi]]
            row = np.repeat(np.arange(hi - lo), deg)
            slot = np.arange(len(row)) - np.repeat(np.cumsum(deg) - deg, deg)
            edge = np.repeat(a.indptr[self.order[lo:hi]], deg) + slot
            nbr = np.zeros((calls * rows, width), np.int32)
            live = np.zeros((calls * rows, width), bool)
            nbr[row, slot] = position[a.indices[edge]]
            live[row, slot] = True
            self.groups.append((int(width), int(lo), int(hi), rows, calls,
                                jnp.asarray(nbr), jnp.asarray(live)))
        self.rows_per_call = max(g[3] for g in self.groups)


@functools.partial(jax.jit, static_argnames=("rows", "heads", "precision",
                                             "slope"))
def _attend(z, a_src, a_dst, nbr, live, first, call, *, rows: int,
            heads: int, precision: str, slope: float):
    """Attention of `rows` rows from position `first` + call * rows:
    (rows, heads, F')."""
    nbr = jax.lax.dynamic_slice_in_dim(nbr, call * rows, rows)
    live = jax.lax.dynamic_slice_in_dim(live, call * rows, rows)[..., None]
    zj = z[nbr].reshape(*nbr.shape, heads, -1)          # (rows, width, K, F')
    zi = jax.lax.dynamic_slice_in_dim(z, first + call * rows, rows)
    s_dst = jnp.sum(_mul(zi.reshape(rows, heads, -1), a_dst[None],
                         precision), axis=-1)            # (rows, K)
    s_src = jnp.sum(_mul(zj, a_src[None, None], precision), axis=-1)
    e = s_dst[:, None, :] + s_src                        # (rows, width, K)
    e = jnp.where(live, jnp.where(e > 0, e, slope * e), -jnp.inf)
    top = jnp.max(e, axis=1, keepdims=True)
    w = jnp.where(live, jnp.exp(e - top), 0.0)
    x = jnp.sum(_mul(w[..., None], zj, precision), axis=1)
    return x / jnp.sum(w, axis=1)[..., None]


def _project(h, w, precision: str, pad: int):
    """z = h w, then at least `pad` rows of zeros, which the last call of a
    group reads for its rows past the group's. h is padded with zero rows
    to whole calls of ROWS_PER_MATMUL, so that a layer compiles one
    product."""
    total = -(-(h.shape[0] + pad) // ROWS_PER_MATMUL) * ROWS_PER_MATMUL
    h = jnp.pad(h, ((0, total - h.shape[0]), (0, 0)))
    return jnp.concatenate(
        [matmul(h[i:i + ROWS_PER_MATMUL], w, precision)
         for i in range(0, total, ROWS_PER_MATMUL)], axis=0)


def forward(config: dict, weights: list, pattern: Pattern, h0,
            precision: str = "highest") -> np.ndarray:
    """The GAT's logits for features h0 and per-layer weights
    {"w", "a_src", "a_dst"}."""
    n_layers = len(config["heads"])
    h = jnp.asarray(np.asarray(h0)[pattern.order])
    with jax.default_matmul_precision("highest"):
        for layer, p in enumerate(weights):
            k = config["heads"][layer]
            z = _project(h, jnp.asarray(p["w"]), precision,
                         pattern.rows_per_call)
            parts = []
            for width, lo, hi, rows, calls, nbr, live in pattern.groups:
                x = jnp.concatenate(
                    [_attend(z, jnp.asarray(p["a_src"]),
                             jnp.asarray(p["a_dst"]), nbr, live, lo, c,
                             rows=rows, heads=k, precision=precision,
                             slope=config["negative_slope"])
                     for c in range(calls)], axis=0)
                parts.append(x[:hi - lo])
            del z
            x = jnp.concatenate(parts, axis=0)
            del parts
            out = (x.reshape(x.shape[0], -1) if config["concat"][layer]
                   else jnp.mean(x, axis=1))
            del x
            if config["skip"][layer]:
                out = out + h
            h = jax.nn.elu(out) if layer < n_layers - 1 else out
            del out
    logits = np.empty(h.shape, np.float32)
    logits[pattern.order] = np.asarray(h)
    return logits
