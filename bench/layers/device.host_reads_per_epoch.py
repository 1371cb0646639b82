"""Device: blocking device-to-host reads per traced epoch (normalize,
commit, compaction, the level loop's queue sizes, end-of-run scalars and
the collected output), from the ``host_reads`` counter the pool records
on each ``pool.apply`` span (``TenantStats.host_reads``)."""
from bench import spans


def read(obs):
    c = spans.counters(obs)
    return None if c is None else c["host_reads"]
