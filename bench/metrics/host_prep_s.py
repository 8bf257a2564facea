"""Host preparation of the streamed plans (RoBW, Block-ELL densify), s:
the harness span around planning every direction the cell streams."""


def read(record):
    return record.get("spans", {}).get("host_prep")
