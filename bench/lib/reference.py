"""Plain float32 reference of the GCN the benchmark drives.

Imports nothing of the program and takes nothing it made: Â comes from
`bench.lib.graphs`, weights and inputs from the harness's seed. Â·H is a
`segment_sum` over Â's edges; the combination is a dense matmul.

`precision` is "highest" (full float32, what the configurations state) or
"high": every product in three bfloat16 passes (a_hi·b_hi + a_hi·b_lo +
a_lo·b_hi, float32 accumulation), emulated so that it is the same on any
backend. "high" is the
control: the next precision below the stated one, which a comparison that
decides `correct` has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    """x = hi + lo + rest, hi and lo bfloat16 values held in float32.

    `reduce_precision` rounds where a cast pair could be folded away: XLA
    on the TPU may keep excess precision through f32 -> bf16 -> f32."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST)
    # Products of bfloat16 values are exact in float32 at HIGHEST.
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    dot = lambda x, y: jnp.dot(x, y, precision=HIGHEST)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _mul(v, h, precision: str):
    """Edge value times feature row, elementwise."""
    if precision == "highest":
        return v * h
    v_hi, v_lo = _split(v)
    h_hi, h_lo = _split(h)
    return v_hi * h_hi + (v_hi * h_lo + v_lo * h_hi)


def aggregate(edges, h, precision: str = "highest"):
    """Â·H: rows, cols, vals of Â (rows sorted)."""
    rows, cols, vals = edges
    return jax.ops.segment_sum(_mul(vals[:, None], h[cols], precision), rows,
                               num_segments=h.shape[0],
                               indices_are_sorted=True)


def gcn_forward(params, edges, h0, n_layers: int, precision: str = "highest",
                bias: bool = True):
    """H' = relu(Â H W + b) for every layer but the last, which is linear."""
    h = h0
    for i in range(n_layers):
        h = matmul(aggregate(edges, h, precision), params[f"w{i}"], precision)
        if bias:
            h = h + params[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def cross_entropy(logits, labels, mask=None):
    """Mean over the nodes (over the masked ones, where a mask is given)."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = logz - gold
    if mask is None:
        return jnp.mean(loss)
    return jnp.sum(jnp.where(mask, loss, 0.0)) / jnp.sum(mask)


def adamw(params, grads, state, lr, b1, b2, eps, weight_decay):
    """Decoupled weight decay Adam with bias correction."""
    step = state["step"] + 1
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + weight_decay * p), params, m, v)
    return new, {"m": m, "v": v, "step": step}


def adamw_init(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree_util.tree_map(zeros, params),
            "v": jax.tree_util.tree_map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


@functools.partial(jax.jit, static_argnames=("n_layers", "precision"))
def loss_and_grads(params, edges, h0, labels, mask, n_layers: int,
                   precision: str):
    return jax.value_and_grad(lambda p: cross_entropy(
        gcn_forward(p, edges, h0, n_layers, precision), labels, mask))(params)


@functools.partial(jax.jit, static_argnames=("n_layers", "precision"))
def serve_forward(weights, edges, h0, n_layers: int, precision: str):
    """The serving engine's request: no biases, relu between layers."""
    params = {f"w{i}": w for i, w in enumerate(weights)}
    return gcn_forward(params, edges, h0, n_layers, precision, bias=False)


def train_steps(params, edges, h0, labels, n_steps: int, n_layers: int,
                hyper: dict, precision: str = "highest", mask=None):
    """Losses of the first `n_steps` steps, the first step's gradients and
    the parameters after the last step."""
    update = jax.jit(lambda p, g, s: adamw(p, g, s, **hyper))
    state = adamw_init(params)
    losses, first_grads = [], None
    for _ in range(n_steps):
        loss, grads = loss_and_grads(params, edges, h0, labels, mask,
                                     n_layers, precision)
        losses.append(loss)
        first_grads = grads if first_grads is None else first_grads
        params, state = update(params, grads, state)
    return losses, first_grads, params
