"""Train-step builder + fault-tolerant training loop.

`make_train_step` returns a jit-able (params, opt_state, batch) → (loss,
params, opt_state) closure with optional gradient accumulation and int8
error-feedback gradient compression (applied before the DP reduction when
running under shard_map; under plain pjit/GSPMD the quantize/dequantize
pair still bounds the wire format of the reduce).

`train_loop` drives steps with checkpoint/restart via repro.checkpoint and
the runtime supervisor's retry policy.

`make_gcn_train_step` / `gcn_train_loop` are the out-of-core counterparts
for the paper's GCN workload: gradients flow through `AiresSpGEMM`'s custom
VJP, so every optimizer step really streams A forward and Aᵀ backward.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.transformer import lm_loss
from repro.train.compression import compress_grads, decompress_grads, ef_init
from repro.train.optim import make_optimizer
from repro.trace import span


@dataclasses.dataclass
class TrainLoopConfig:
    optimizer: str = "adamw"
    lr: float = 3e-4
    grad_accum: int = 1
    compress: bool = False         # int8 EF gradient compression
    checkpoint_every: int = 50
    max_steps: int = 200
    mesh_axes: Optional[bool] = None


def make_train_step(cfg: ArchConfig, loop_cfg: TrainLoopConfig,
                    loss_fn: Optional[Callable] = None):
    loss_fn = loss_fn or (
        lambda params, batch: lm_loss(
            cfg, params, batch["tokens"], batch["labels"],
            vision_embeds=batch.get("vision_embeds"),
            audio_embeds=batch.get("audio_embeds"),
            mesh_axes=loop_cfg.mesh_axes))
    _, opt_update = make_optimizer(loop_cfg.optimizer, lr=loop_cfg.lr)

    def micro_grads(params, batch):
        return jax.value_and_grad(loss_fn)(params, batch)

    def train_step(params, opt_state, batch, ef=None):
        if loop_cfg.grad_accum > 1:
            # Microbatch over the leading axis: batch arrays are
            # (accum, local_batch, ...). lax.scan keeps the HLO compact.
            def body(carry, micro):
                acc_loss, acc_grads = carry
                loss, grads = micro_grads(params, micro)
                acc_grads = jax.tree_util.tree_map(
                    jnp.add, acc_grads, grads)
                return (acc_loss + loss, acc_grads), ()

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.float32(0.0), zeros), batch)
            loss = loss / loop_cfg.grad_accum
            grads = jax.tree_util.tree_map(
                lambda g: g / loop_cfg.grad_accum, grads)
        else:
            loss, grads = micro_grads(params, batch)

        new_ef = ef
        if loop_cfg.compress and ef is not None:
            q, scales, new_ef = compress_grads(grads, ef)
            grads = decompress_grads(q, scales)

        params, opt_state = opt_update(params, grads, opt_state)
        return loss, params, opt_state, new_ef

    return train_step


def make_gcn_train_step(cfg, engine, a, h0, labels,
                        optimizer: str = "adamw", lr: float = 1e-2,
                        **opt_kwargs):
    """Out-of-core GCN train step (the paper's actual workload).

    cfg is a `repro.models.gcn.GCNConfig` with out_of_core=True, `engine` an
    `AiresSpGEMM`, `a` host CSR. The returned step is NOT wrapped in jit:
    the streaming pipeline runs host-side (device_put + per-segment Pallas
    dispatch), and jit would freeze its per-epoch accounting. Returns
    (init_opt, step) with step(params, opt_state) -> (loss, params,
    opt_state).
    """
    from repro.models.gcn import gcn_loss
    from repro.train.optim import make_optimizer as _mk

    init_opt, opt_update = _mk(optimizer, lr=lr, **opt_kwargs)

    def step(params, opt_state):
        with span("train.step"):
            loss, grads = jax.value_and_grad(
                lambda p: gcn_loss(cfg, p, a, h0, labels,
                                   engine=engine))(params)
            with span("train.update"):
                params, opt_state = opt_update(params, grads, opt_state)
        return loss, params, opt_state

    return init_opt, step


def gcn_train_loop(cfg, engine, a, h0, labels, params, n_epochs: int,
                   optimizer: str = "adamw", lr: float = 1e-2,
                   log_every: int = 1):
    """Drive true out-of-core GCN epochs; returns (params, info).

    info carries the loss history and the per-epoch forward/backward
    `StreamStats` logs from the engine — the real counterpart of
    `gcn_epoch(mode="execute")` accounting, here under an actual optimizer.
    """
    init_opt, step = make_gcn_train_step(cfg, engine, a, h0, labels,
                                         optimizer=optimizer, lr=lr)
    opt_state = init_opt(params)
    history = []
    epochs = []
    t0 = time.perf_counter()
    for epoch in range(n_epochs):
        engine.reset_stats_logs()
        loss, params, opt_state = step(params, opt_state)
        epochs.append({
            "forward_stream": list(engine.forward_stats_log),
            "backward_stream": list(reversed(engine.backward_stats_log)),
        })
        if epoch % log_every == 0:
            history.append((epoch, float(loss)))
    jax.block_until_ready(loss)
    return params, {"history": history, "epochs": epochs,
                    "seconds": time.perf_counter() - t0}


def train_loop(cfg: ArchConfig, loop_cfg: TrainLoopConfig, params, opt_state,
               batches, checkpointer=None, start_step: int = 0,
               log_every: int = 10, ef=None):
    """Simple driver: checkpoint every N steps, resumable from start_step."""
    step_fn = jax.jit(make_train_step(cfg, loop_cfg))
    if loop_cfg.compress and ef is None:
        ef = ef_init(params)
    history = []
    t0 = time.perf_counter()
    for step, batch in enumerate(batches, start=start_step):
        if step >= loop_cfg.max_steps:
            break
        loss, params, opt_state, ef = step_fn(params, opt_state, batch, ef)
        if step % log_every == 0:
            history.append((step, float(loss)))
        if checkpointer is not None and step and \
                step % loop_cfg.checkpoint_every == 0:
            checkpointer.save(step, params, opt_state, ef=ef)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0
    return params, opt_state, {"history": history, "seconds": elapsed,
                               "ef": ef}
