"""Flash-attention prefill kernel (causal, optional sliding window).

The post-hillclimb roofline shows train/prefill cells memory-bound, with
the S×S score materialization the largest HBM stream (EXPERIMENTS §Perf
iter 5). This kernel keeps scores in VMEM: grid (B, H, S/bq, S/bk) with the
KV-block loop innermost, online-softmax running stats in scratch — the
standard TPU flash schedule. Causal blocks above the diagonal are skipped
via @pl.when (no DMA waste thanks to block-index masking in the index map
being monotone).

Used by the LM stack in place of the lax.map chunked path on real TPUs;
validated in interpret mode against ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, scale: float, causal: bool,
                  window: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = kj * bk

    # Causal/window block culling: process only blocks that intersect the
    # allowed region q_pos >= k_pos (> q_pos - window).
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        run = run & (k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, -jnp.inf)

        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Rows with no valid entries keep m = -inf; exp(-inf - -inf) guard:
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "window", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,   # (B, H, S, d)
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int = 512,
    block_k: int = 512,
    causal: bool = True,
    window: int = 0,       # 0 = no sliding window
    interpret: bool = False,
) -> jax.Array:
    b, h, s, d = q.shape
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    grid = (b, h, s // block_q, s // block_k)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_flash_kernel, bq=block_q, bk=block_k,
                               scale=scale, causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, kj: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, kj: (b_, h_, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, kj: (b_, h_, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, qi, kj: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
