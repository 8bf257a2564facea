"""The paper's own architecture: a GCN trained with out-of-core SpGEMM.

Two execution paths:
  * in-core (dense jnp): used by smoke tests and the training example on
    small graphs — Eq. (4) per layer: H' = σ(Ã H W).
  * out-of-core (AIRES): aggregation X = Ã H runs through AiresSpGEMM
    (RoBW streaming + Pallas kernel) when cfg.out_of_core=True.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sparse.formats import CSR


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    name: str = "gcn_paper"
    feature_dim: int = 256       # paper §V-A
    hidden_dims: Tuple[int, ...] = (256, 256)
    n_classes: int = 32
    out_of_core: bool = False
    device_budget_bytes: int = 1 << 30
    dtype: str = "float32"


def gcn_init(cfg: GCNConfig, key: jax.Array) -> Dict[str, jnp.ndarray]:
    dt = jnp.dtype(cfg.dtype)
    dims = [cfg.feature_dim, *cfg.hidden_dims, cfg.n_classes]
    params = {}
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        params[f"w{i}"] = (jax.random.normal(sub, (din, dout))
                           * din ** -0.5).astype(dt)
        params[f"b{i}"] = jnp.zeros((dout,), dt)
    return params


def matmul_precision(dtype) -> Optional[jax.lax.Precision]:
    """Float32 matmuls run at full float32 (a TPU's default is one bfloat16
    pass on the MXU); narrower dtypes keep the default (None)."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)


def _aggregate(a_dense: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(a_dense, h, preferred_element_type=jnp.float32,
                   precision=matmul_precision(h.dtype)).astype(h.dtype)


def gcn_forward(cfg: GCNConfig, params, a, h0: jnp.ndarray,
                engine: Optional[object] = None) -> jnp.ndarray:
    """a: dense jnp array (in-core) or CSR (out-of-core with engine)."""
    n_layers = len([k for k in params if k.startswith("w")])
    precision = matmul_precision(cfg.dtype)
    h = h0
    for i in range(n_layers):
        if cfg.out_of_core and isinstance(a, CSR):
            assert engine is not None, "out-of-core path needs AiresSpGEMM"
            x = engine(a, h)                      # streamed Ã·H
        else:
            x = _aggregate(a, h)
        h = jnp.dot(x, params[f"w{i}"], precision=precision) + params[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def serve_combine(x: jnp.ndarray, ws, layer: int) -> jnp.ndarray:
    """A served request's combination after its aggregation x: x W_layer
    at full float32 (not the TPU's default single bfloat16 pass), relu
    between layers; the last layer is linear and has no bias."""
    h = jnp.dot(x, ws[layer], precision=jax.lax.Precision.HIGHEST)
    if layer < len(ws) - 1:
        h = jnp.maximum(h, 0.0)
    return h


def gcn_loss(cfg: GCNConfig, params, a, h0, labels,
             engine: Optional[object] = None) -> jnp.ndarray:
    logits = gcn_forward(cfg, params, a, h0, engine).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
