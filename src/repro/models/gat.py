"""GAT, the graph attention network, as published for PPI.

Veličković et al., "Graph Attention Networks", ICLR 2018 (arXiv:1710.10903),
§3.3, inductive setting. Head k of a layer:

    z_j    = W^k h_j
    e_ij   = LeakyReLU_0.2(a_dst^k · z_i + a_src^k · z_j),  j in N(i) ∪ {i}
    α_ij   = softmax_j e_ij
    h'_i   = Σ_j α_ij z_j

LeakyReLU's negative slope is `GATConfig.negative_slope` (the paper's 0.2).
The heads of a layer are concatenated or averaged; a skip layer adds its
input before the activation (DGL's `GATConv(residual=True)`); every layer
but the last ends in ELU. Weights per layer: W (F_in, K·F'), a_src and a_dst
(K, F'), no biases.

Three paths share `project` and `combine`:
  * in core: the attention over a dense mask (small graphs, tests);
  * out of core: `AiresSpGEMM.attend` streams A's Block-ELL bricks as the
    mask (`kernels/gat_attn.py`); the serving engine runs a request this
    way, layer by layer (`runtime/engine.py`);
  * `gat_reference`: plain jax.numpy over an edge list, float32 at
    HIGHEST, with no kernel, cache or batching.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sparse.formats import CSR

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GATConfig:
    name: str = "gat_ppi"
    feature_dim: int = 256
    heads: Tuple[int, ...] = (4, 4, 6)          # K per layer
    head_dims: Tuple[int, ...] = (256, 256, 121)  # F' per layer
    concat: Tuple[bool, ...] = (True, True, False)  # else the heads' mean
    skip: Tuple[bool, ...] = (False, True, False)   # add the layer's input
    negative_slope: float = 0.2                     # LeakyReLU's, in e_ij
    dtype: str = "float32"

    @property
    def n_layers(self) -> int:
        return len(self.heads)

    def stream_width(self, layer: int) -> int:
        """Columns of z, which the layer's attention pass streams."""
        return self.heads[layer] * self.head_dims[layer]

    def out_width(self, layer: int) -> int:
        return (self.stream_width(layer) if self.concat[layer]
                else self.head_dims[layer])


def gat_init(cfg: GATConfig, key: jax.Array) -> List[Dict[str, jnp.ndarray]]:
    """Per layer {"w", "a_src", "a_dst"}, N(0, 1) scaled by fan-in."""
    dt = jnp.dtype(cfg.dtype)
    params, d_in = [], cfg.feature_dim
    for layer, (k, f) in enumerate(zip(cfg.heads, cfg.head_dims)):
        kw, ks, kd = jax.random.split(jax.random.fold_in(key, layer), 3)
        params.append({
            "w": (jax.random.normal(kw, (d_in, k * f)) * d_in ** -0.5
                  ).astype(dt),
            "a_src": (jax.random.normal(ks, (k, f)) * f ** -0.5).astype(dt),
            "a_dst": (jax.random.normal(kd, (k, f)) * f ** -0.5).astype(dt),
        })
        d_in = cfg.out_width(layer)
    return params


def scores(z, a):
    """a^k · z_j for every row and head: z (n, K·F'), a (K, F') -> (n, K).

    Per head from a column slice of z: reshaping z's lanes into (K, F')
    takes the TPU compiler over a minute at 478,800 rows when F' is 121."""
    k, f = a.shape
    return jnp.stack([jnp.sum(z[:, i * f:(i + 1) * f] * a[i], axis=1)
                      for i in range(k)], axis=1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def project(cfg: GATConfig, layer: int, p: dict, h):
    """z = h W (n, K·F'), and the source and destination scores (n, K)."""
    z = jnp.dot(h, p["w"], precision=HIGHEST)
    return z, scores(z, p["a_src"]), scores(z, p["a_dst"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def combine(cfg: GATConfig, layer: int, x, h_in):
    """The layer's output from its attention x (n, K, F'): heads
    concatenated or averaged, the skip, then ELU but after the last."""
    out = (x.reshape(x.shape[0], -1) if cfg.concat[layer]
           else jnp.mean(x, axis=1))
    if cfg.skip[layer]:
        out = out + h_in
    if layer < cfg.n_layers - 1:
        out = jax.nn.elu(out)
    return out


def leaky_relu(e, slope: float):
    return jnp.where(e > 0, e, slope * e)


def _attend_dense(cfg: GATConfig, layer: int, mask, z, s_src, s_dst):
    """Attention over a dense (n, n) mask: (n, K, F')."""
    k, f = cfg.heads[layer], cfg.head_dims[layer]
    e = leaky_relu(s_dst[:, None, :] + s_src[None, :, :],
                   cfg.negative_slope)
    alpha = jax.nn.softmax(jnp.where(mask[:, :, None], e, -jnp.inf), axis=1)
    return jnp.einsum("ijk,jkf->ikf", alpha, z.reshape(z.shape[0], k, f),
                      precision=HIGHEST)


def gat_forward(cfg: GATConfig, params, a, h0: jnp.ndarray,
                engine: Optional[object] = None) -> jnp.ndarray:
    """Logits (n, out_width of the last layer), before any sigmoid.

    `a` is a dense array whose nonzeros (A + I, or Â) are the mask, or a
    CSR streamed by `engine` (an `AiresSpGEMM`)."""
    h = h0
    for layer, p in enumerate(params):
        z, s_src, s_dst = project(cfg, layer, p, h)
        if engine is not None and isinstance(a, CSR):
            x = engine.attend(a, z, s_src, s_dst, cfg.heads[layer],
                              cfg.negative_slope)
        else:
            x = _attend_dense(cfg, layer, a != 0, z, s_src, s_dst)
        h = combine(cfg, layer, x, h)
    return h


def gat_reference(params, edges, h0, cfg: GATConfig = GATConfig()):
    """The plain reference: `edges` = (rows, cols) of A + I, rows sorted;
    per layer z = h W, then for each edge its logit, a `segment_max` and
    `segment_sum` softmax over the row's edges and the weighted sum."""
    rows, cols = edges
    n = h0.shape[0]
    h = h0
    with jax.default_matmul_precision("highest"):
        for layer, p in enumerate(params):
            k, f = cfg.heads[layer], cfg.head_dims[layer]
            z = jnp.dot(h, p["w"])
            z3 = z.reshape(n, k, f)
            e = leaky_relu(scores(z, p["a_dst"])[rows]
                           + scores(z, p["a_src"])[cols], cfg.negative_slope)
            top = jax.ops.segment_max(e, rows, num_segments=n,
                                      indices_are_sorted=True)
            w = jnp.exp(e - top[rows])
            den = jax.ops.segment_sum(w, rows, num_segments=n,
                                      indices_are_sorted=True)
            x = jax.ops.segment_sum(w[:, :, None] * z3[cols], rows,
                                    num_segments=n, indices_are_sorted=True)
            x = x / den[:, :, None]
            out = x.reshape(n, k * f) if cfg.concat[layer] else x.mean(axis=1)
            if cfg.skip[layer]:
                out = out + h
            h = jax.nn.elu(out) if layer < cfg.n_layers - 1 else out
    return h
