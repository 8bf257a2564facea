"""% of the chip's bf16 peak: model operations of the traced part over its
length (training: forward plus a backward of twice its operations)."""
from bench.lib.readers import mfu


def read(record):
    return mfu(record)
