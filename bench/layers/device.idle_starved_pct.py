"""Device: the share of the traced window in which the device sat idle
while the pool's apply thread was outside ``pool.apply``: no prepared
batch, so the feeder or the prep stage set the pace (``bench/spans.py``)."""
from bench import spans


def read(obs):
    split = spans.idle_split(obs)
    return None if split is None else split["starved"]
