"""% of the traced part in which the device sat idle under the segment
cache's probe, promotion, store or demotion (`aires.cache.*`)."""
from bench.lib.spans import CACHE_PREFIX, span_idle_share


def read(record):
    return span_idle_share(record,
                           lambda name: name.startswith(CACHE_PREFIX))
