"""Kernel (``kernels/intersect`` membership probe, ``probe_call``): device
milliseconds per epoch of its calls."""


def read(obs):
    if not obs.devices or obs.epochs <= 0:
        return None
    t = obs.op_seconds(lambda op: op == "probe_call")
    return 1e3 * t / obs.epochs if t > 0 else None
