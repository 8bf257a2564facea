"""% of the traced part in which the device sat idle while the host read a
streamed brick's largest column tile (`aires.kernel.sync`)."""
from bench.lib.spans import span_idle_share


def read(record):
    return span_idle_share(record, lambda name: name == "aires.kernel.sync")
