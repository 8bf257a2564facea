"""% of the traced part in which no operation ran on the device."""
from bench.lib.readers import idle_share


def read(record):
    return idle_share(record)
