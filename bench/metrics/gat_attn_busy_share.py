"""% of the traced part in which the GAT attention kernel ran."""
from bench.lib.readers import kernel_busy_share


def read(record):
    return kernel_busy_share(record, "gat_attn")
