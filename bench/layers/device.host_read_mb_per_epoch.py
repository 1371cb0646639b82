"""Device: megabytes (1e6 bytes) those blocking reads moved per traced
epoch, from the ``host_read_bytes`` counter the pool records on each
``pool.apply`` span (``TenantStats.host_read_bytes``)."""
from bench import spans


def read(obs):
    c = spans.counters(obs)
    return None if c is None else c["host_read_bytes"] / 1e6
