"""Dataflow (``core/bigjoin.py``): level steps the host loop dispatched per
traced epoch, from the ``level_steps`` counter the pool records on each
``pool.apply`` span (``TenantStats.level_steps``)."""
from bench import spans


def read(obs):
    c = spans.counters(obs)
    return None if c is None else c["level_steps"]
