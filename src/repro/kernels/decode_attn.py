"""Flash-decode GQA attention kernel (serve_step hot spot).

One new query token attends to a long KV cache. Grid (batch, kv_head,
kv_blocks) with the KV-block reduction innermost; online-softmax running
max/denominator live in VMEM scratch, so the (S × d) cache streams through
VMEM exactly once — memory-bound roofline behaviour, which is what decode_*
shapes measure.

KV layout (B, n_kv_heads, S, d): head-dim minor, sequence second-minor —
the collective-friendly layout used across the framework.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lens_ref[b]
    base = s * bs

    @pl.when(base < kv_len)
    def _block():
        q = q_ref[0, 0]          # (group, d)
        k = k_ref[0, 0]          # (bs, d)
        v = v_ref[0, 0]          # (bs, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (group, bs)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < kv_len, logits, -jnp.inf)

        m_prev = m_ref[...]                       # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)               # (group, bs)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_s", "interpret"),
)
def decode_attention_pallas(
    q: jax.Array,        # (B, n_kv, group, d) — GQA-grouped query
    k: jax.Array,        # (B, n_kv, S_pad, d)
    v: jax.Array,        # (B, n_kv, S_pad, d)
    lens: jax.Array,     # (B,) int32 valid KV length per sequence
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b_sz, n_kv, group, d = q.shape
    s_pad = k.shape[2]
    assert s_pad % block_s == 0, (s_pad, block_s)
    n_s = s_pad // block_s
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_decode_kernel, bs=block_s, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b_sz, n_kv, n_s),
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda b, h, s, lens_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_s, d),
                             lambda b, h, s, lens_ref: (b, h, s, 0)),
                pl.BlockSpec((1, 1, block_s, d),
                             lambda b, h, s, lens_ref: (b, h, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d),
                                   lambda b, h, s, lens_ref: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b_sz, n_kv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, q, k, v)
    return out
