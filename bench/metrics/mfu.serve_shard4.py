"""% of the peak of the chips the cell holds: the model operations of the
traced part over its length and over the peak of every chip of the mesh
(four), not of the one that computes."""
from bench.lib.readers import mfu


def read(record):
    value = mfu(record)
    if value is None or "chips" not in record["traced"]:
        return None
    return value / record["traced"]["chips"]
