"""Expert-parallel MoE dispatch with explicit all-to-all (shard_map).

§Perf next-iteration module (EXPERIMENTS §Perf): the GSPMD dispatch in
layers.moe_ffn routes tokens through a logically-global (E·C, d) gather
that XLA materializes per device (~0.5 TiB/chip on kimi-k2 train). This
version makes the routing explicit per device:

  1. tokens live on (data, model)-sharded devices; experts are partitioned
     over the model axis (E_loc = E / |model| per rank);
  2. each device routes its local tokens, compacts them into per-destination
     buffers (n_model, cap, d) with the same histogram-rank trick;
  3. one `all_to_all` over the model axis delivers each rank the tokens for
     ITS experts; local batched FFN; a second all_to_all returns outputs;
  4. combine with the saved top-k weights.

Dispatch memory is bounded by n_model × cap_local × d per device
(~0.3 GiB/chip/layer on kimi-k2) and the wire cost is exactly two
all-to-alls of that buffer — the GShard schedule.

Requires E % |model axis| == 0 (kimi-k2: 384 % 16 ✓); callers fall back to
layers.moe_ffn otherwise (mixtral's 8 experts on 16-way TP keep the
tensor-parallel-inside-expert path).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig


def _rank_in_group(group_ids: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """Rank of each element within its group (histogram + sorted-order)."""
    n = group_ids.shape[0]
    order = jnp.argsort(group_ids, stable=True)
    hist = jnp.bincount(group_ids, length=n_groups)
    starts = jnp.cumsum(hist) - hist
    ranks_sorted = jnp.arange(n) - starts[group_ids[order]]
    return jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)


def _local_moe(cfg: ArchConfig, p, xf, model_axis: str):
    """Per-device body (runs inside shard_map over the model axis).

    xf: (t_loc, d) local tokens; p: expert weights with E_loc experts local
    plus a replicated router.
    """
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    n_ranks = jax.lax.axis_size(model_axis)
    e_loc = e // n_ranks

    probs = jax.nn.softmax(
        (xf @ p["w_router"]).astype(jnp.float32), axis=-1)      # (t, E)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce) / k

    flat_e = top_e.reshape(-1)                                   # (t·k,)
    flat_w = top_p.reshape(-1)
    tok_id = jnp.repeat(jnp.arange(t), k)
    dest = flat_e // e_loc                                       # model rank
    # capacity per destination rank (static): tokens*k spread over ranks
    cap = max(1, int(cfg.capacity_factor * t * k / n_ranks))
    cap = ((cap + 7) // 8) * 8

    pos = _rank_in_group(dest, n_ranks)
    keep = pos < cap
    slot = jnp.where(keep, dest * cap + pos, n_ranks * cap)

    send_x = jnp.zeros((n_ranks * cap, d), xf.dtype).at[slot].set(
        xf[tok_id], mode="drop").reshape(n_ranks, cap, d)
    send_eid = jnp.full((n_ranks * cap,), 0, jnp.int32).at[slot].set(
        (flat_e % e_loc).astype(jnp.int32), mode="drop").reshape(n_ranks, cap)
    send_valid = jnp.zeros((n_ranks * cap,), jnp.bool_).at[slot].set(
        keep, mode="drop").reshape(n_ranks, cap)

    # Exchange: rank r receives, from every peer, tokens for r's experts.
    recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, model_axis, 0, 0, tiled=False)
    recv_valid = jax.lax.all_to_all(send_valid, model_axis, 0, 0, tiled=False)

    rx = recv_x.reshape(n_ranks * cap, d)
    reid = recv_eid.reshape(-1)
    rvalid = recv_valid.reshape(-1)

    # Batched local expert FFN via per-expert gather of weights: for each
    # incoming token select its expert's weights (E_loc small per rank).
    wg = p["w_gate"]                                             # (E_loc,d,f)
    wu = p["w_up"]
    wd = p["w_down"]
    h = jax.nn.silu(jnp.einsum("td,tdf->tf", rx, wg[reid])) * \
        jnp.einsum("td,tdf->tf", rx, wu[reid])
    out_tok = jnp.einsum("tf,tfd->td", h, wd[reid])
    out_tok = jnp.where(rvalid[:, None], out_tok, 0).astype(xf.dtype)

    # Return outputs to the senders.
    back = jax.lax.all_to_all(out_tok.reshape(n_ranks, cap, d),
                              model_axis, 0, 0, tiled=False)
    back = back.reshape(n_ranks * cap, d)

    gathered = back[jnp.clip(slot, 0, n_ranks * cap - 1)] * \
        (flat_w * keep)[:, None].astype(xf.dtype)
    out = jnp.zeros((t, d), xf.dtype).at[tok_id].add(gathered)
    return out, aux


def moe_ffn_shard_map(cfg: ArchConfig, p: Dict[str, jnp.ndarray],
                      x: jnp.ndarray, mesh, data_axes: Tuple[str, ...],
                      model_axis: str = "model"):
    """x (B, S, D) → (out, aux). Expert weights must be (E, d, f) arrays;
    they are consumed model-axis-sharded on dim 0 inside shard_map."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    b, s, d = x.shape
    assert cfg.n_experts % mesh.shape[model_axis] == 0, \
        "E must divide the model axis; use layers.moe_ffn otherwise"

    def body(xl, wr, wg, wu, wd):
        t_loc = xl.shape[0] * xl.shape[1]
        out, aux = _local_moe(
            cfg, {"w_router": wr, "w_gate": wg, "w_up": wu, "w_down": wd},
            xl.reshape(t_loc, d), model_axis)
        aux = jax.lax.pmean(aux, model_axis)
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(xl.shape), aux

    out, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axes, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(data_axes, None, None), P()),
        check_rep=False,
    )(x, p["w_router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux
