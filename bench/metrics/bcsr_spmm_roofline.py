"""% of the Block-ELL SpMM kernel's roofline in the traced part."""
from bench.lib.readers import kernel_roofline


def read(record):
    return kernel_roofline(record, "bcsr_spmm")
