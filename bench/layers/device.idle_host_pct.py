"""Device: the share of the traced window in which the device sat idle
while the pool's apply thread was inside ``pool.apply`` but in no read:
Python, dispatch and host numpy of the epoch (``bench/spans.py``)."""
from bench import spans


def read(obs):
    split = spans.idle_split(obs)
    return None if split is None else split["host"]
