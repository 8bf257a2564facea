"""% of the traced part in which the SpMM kernel ran on the device."""
from bench.lib.readers import kernel_busy_share


def read(record):
    return kernel_busy_share(record, "bcsr_spmm")
