"""Dataflow (``core/bigjoin.py``): device milliseconds per epoch of the
level-step and seed-step programs, without the membership kernel's own
time inside them."""

DATAFLOW = {"step", "seed_step"}


def read(obs):
    if not obs.devices or obs.epochs <= 0:
        return None
    total = obs.module_seconds(lambda m: m in DATAFLOW)
    member = obs.op_seconds(lambda op: op == "probe_call",
                            lambda m: m in DATAFLOW)
    if total <= 0:
        return None
    return 1e3 * (total - member) / obs.epochs
