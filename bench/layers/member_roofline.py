"""Kernel: the membership probe's share of its roofline.  The least time
its calls could take is their bytes (``bench/kernels.member_bytes``, from
each call's shapes) over the chip's HBM bandwidth; the share is that over
the calls' measured device time.  The kernel does no FLOP-bound work, so
HBM bytes are its only roof."""
from bench.kernels import member_bytes


def read(obs):
    calls = obs.ops(lambda op: op == "probe_call")
    seconds = sum(t for _, t in calls)
    if not calls or seconds <= 0:
        return None
    need = sum(member_bytes(name) for name, _ in calls) \
        / obs.peaks()["hbm_bytes_per_s"]
    return 100.0 * need / seconds
