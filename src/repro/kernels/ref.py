"""Pure-jnp oracles for every Pallas kernel (ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bcsr_spmm_ref(blocks, col_tile, n_tiles, h, *, bm: int, bk: int):
    """Densify block-ELL then matmul — exact semantics of the kernel."""
    n_rb, ell_w = blocks.shape[0], blocks.shape[1]
    k_pad, f_pad = h.shape
    n_ct = k_pad // bk
    a_dense = jnp.zeros((n_rb * bm, k_pad), dtype=jnp.float32)
    for rb in range(n_rb):
        for s in range(ell_w):
            t = col_tile[rb, s]
            valid = (s < n_tiles[rb]) & (t >= 0)
            tile = jnp.where(valid, blocks[rb, s].astype(jnp.float32), 0.0)
            t_safe = jnp.clip(t, 0, n_ct - 1)
            a_dense = jax.lax.dynamic_update_slice(
                a_dense,
                jax.lax.dynamic_slice(
                    a_dense, (rb * bm, t_safe * bk), (bm, bk)) + tile,
                (rb * bm, t_safe * bk),
            )
    return jnp.dot(a_dense, h.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def gat_attn_ref(blocks, col_tile, n_tiles, z, s_src, s_dst, *, heads: int,
                 head_width: int, bm: int, bk: int, slope: float = 0.2):
    """Dense masked softmax attention over the densified bricks' pattern:
    z (K_pad, heads * head_width), s_src (K_pad, heads), s_dst
    (n_rb * bm, heads) -> (n_rb * bm, heads, head_width); a row with no
    nonzero gives zeros."""
    ones = jnp.where(blocks != 0, 1.0, 0.0)
    live = bcsr_spmm_ref(ones, col_tile, n_tiles,
                         jnp.eye(z.shape[0], dtype=jnp.float32),
                         bm=bm, bk=bk) > 0                  # (rows, K_pad)
    e = s_dst[:, None, :] + s_src[None, :, :]              # (rows, K_pad, h)
    e = jnp.where(e > 0, e, slope * e)
    e = jnp.where(live[:, :, None], e, -jnp.inf)
    m = jnp.max(e, axis=1, keepdims=True)
    p = jnp.where(live[:, :, None], jnp.exp(e - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    den = jnp.sum(p, axis=1)                                # (rows, h)
    zh = z.reshape(z.shape[0], heads, head_width)
    out = jnp.einsum("ijh,jhf->ihf", p, zh,
                     precision=jax.lax.Precision.HIGHEST)
    return out / jnp.where(den > 0, den, 1.0)[:, :, None]


def fused_gcn_layer_ref(blocks, col_tile, n_tiles, h, w, b, *, bm: int, bk: int):
    x = bcsr_spmm_ref(blocks, col_tile, n_tiles, h, bm=bm, bk=bk)
    return jnp.maximum(x @ w.astype(jnp.float32) + b.astype(jnp.float32), 0.0)


def decode_attention_ref(q, k, v, lens):
    """(B, n_kv, group, d) GQA decode attention with per-seq valid lengths."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    s_pad = k.shape[2]
    pos = jnp.arange(s_pad)[None, None, None, :]
    mask = pos < lens[:, None, None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_ref(q, k, v, causal=True, window=0):
    """(B, H, S, d) causal/windowed attention oracle."""
    s_len = q.shape[2]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(s_len)[:, None]
    k_pos = jnp.arange(s_len)[None, :]
    mask = jnp.ones((s_len, s_len), bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
