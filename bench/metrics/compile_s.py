"""Backend compile seconds in set-up (JAX monitoring events)."""


def read(record):
    return record.get("spans", {}).get("compile")
