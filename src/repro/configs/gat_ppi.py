"""GAT as published for PPI (Veličković et al., ICLR 2018, arXiv:1710.10903
§3.3): layers 1-2 are K = 4 heads of F' = 256, concatenated to 1,024, then
ELU; layer 2 has a skip connection; layer 3 is K = 6 heads of 121 outputs,
averaged. Served out of core through `ServingEngine` and
`AiresSpGEMM.attend` (the `rUSA.gat_serve` benchmark cell).

Departures from the paper: input width 256, the AIRES paper's feature
width (§V-A), in place of PPI's 50 input features; the skip is added
before the ELU, as DGL's GATConv(residual=True) does (the paper does not
say where); no biases (the serving engine's requests carry none); outputs
are logits, before the multi-label sigmoid.

Not part of the LM-arch registry (`_ARCH_IDS`), as `gcn_paper` is not.
"""
from repro.models.gat import GATConfig

CONFIG = GATConfig(
    name="gat_ppi",
    feature_dim=256,
    heads=(4, 4, 6),
    head_dims=(256, 256, 121),
    concat=(True, True, False),
    skip=(False, True, False),
    negative_slope=0.2,
)

SMOKE = GATConfig(
    name="gat_ppi_smoke",
    feature_dim=32,
    heads=(2, 2, 3),
    head_dims=(16, 16, 8),
    concat=(True, True, False),
    skip=(False, True, False),
    negative_slope=0.2,
)
