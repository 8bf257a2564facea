"""GB crossing host to device per unit of the cell's work (an epoch or a
request): uploads plus promotions."""
from bench.lib.readers import per_unit


def read(record):
    return per_unit(record, "bus_bytes", "units", 1e-9)
