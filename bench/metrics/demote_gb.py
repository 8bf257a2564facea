"""GB the segment cache copied device to host (demotions) per unit of the
cell's work; None where the program counts no demotions."""


def read(record):
    c = record.get("counters", {})
    if "demoted_bytes" not in c or not c.get("units"):
        return None
    return c["demoted_bytes"] / c["units"] * 1e-9
