"""GB a request that crossed the inter-chip path to or from the sharded
cache's device tier (`BatchReport.ici_bytes`: remote-shard hits and
placements on other chips). The cell's engine models ICI as all-to-all,
one hop between any two chips, so each transfer's bytes count once."""
from bench.lib.readers import per_unit


def read(record):
    if "ici_bytes" not in record.get("counters", {}):
        return None
    return per_unit(record, "ici_bytes", "units", 1e-9)
