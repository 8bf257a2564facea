"""Model zoo: the paper's GCN, GAT + the 10 assigned LM-family architectures.

Pure-JAX functional models: params are pytrees of jnp arrays, every forward
is a jit-able function of (config, params, batch). One composable
transformer stack covers dense/GQA/SWA/softcap/MoE/M-RoPE variants;
recurrent blocks (mLSTM, sLSTM, RG-LRU) plug into the same block list.
"""
from repro.models.config import ArchConfig, BlockKind
from repro.models.transformer import (
    init_params,
    forward,
    encode,
    lm_loss,
    init_decode_state,
    decode_step,
    param_count,
)
from repro.models.gcn import GCNConfig, gcn_init, gcn_forward, gcn_loss
from repro.models.gat import GATConfig, gat_init, gat_forward, gat_reference

__all__ = [
    "ArchConfig", "BlockKind",
    "init_params", "forward", "encode", "lm_loss", "init_decode_state",
    "decode_step", "param_count",
    "GCNConfig", "gcn_init", "gcn_forward", "gcn_loss",
    "GATConfig", "gat_init", "gat_forward", "gat_reference",
]
