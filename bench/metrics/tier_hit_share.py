"""% of requested brick bytes served from the device tier of the segment
cache: cache hits less host-tier promotions, over uploads plus hits."""
from bench.lib.readers import per_unit


def read(record):
    return per_unit(record, "tier_hit_bytes", "requested_brick_bytes", 100.0)
