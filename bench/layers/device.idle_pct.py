"""Device: the share of the traced window in which no operation ran,
1 - (union of busy intervals) / window, averaged over the cell's chips."""


def read(obs):
    if obs.busy_s <= 0 or obs.window_s <= 0:
        return None
    return 100.0 * (1.0 - obs.busy_s / obs.window_s)
