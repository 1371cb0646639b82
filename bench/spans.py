"""The program's own spans and counters in a traced window.

The pool's apply thread opens ``pool.apply`` around each served epoch and
``read.<site>`` around each blocking device-to-host read in it
(``repro.core.hostread``); on each ``pool.apply`` span it records that
epoch's counters as span metadata: ``level_steps``, ``host_reads`` and
``host_read_bytes``.  The apply thread is the host line that carries the
``pool.apply`` spans.  A program without these spans gives no line, and
every reader here then finds nothing to read.

Idle split: the device's idle time in the traced window (the same busy
intervals as ``device.idle_pct``) is cut by what the apply thread was
doing: inside a ``read.*`` span, inside ``pool.apply`` but in no read
(Python, dispatch, host numpy), or outside ``pool.apply`` (no prepared
batch: the feeder or prep sets the pace).  The three add up to the idle
share.  The window is placed on the trace's clock by its close: the end
of the feeder's last ``feeder.wait`` (the last answer), else of the last
``pool.apply``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np

from bench import trace

APPLY = "pool.apply"
READ = "read."
WAIT = "feeder.wait"
COUNTERS = ("level_steps", "host_reads", "host_read_bytes")


def apply_line(obs) -> Optional[trace.Line]:
    """The host line with the most ``pool.apply`` spans, or None."""
    best, most = None, 0
    for ln in obs.hosts:
        n = ln.names.count(APPLY)
        if n > most:
            best, most = ln, n
    return best


def _clipped(start, end, t0: float, t1: float):
    """The union of the intervals, cut to [t0, t1]."""
    s, e = trace.merge(np.maximum(start, t0), np.minimum(end, t1))
    keep = e > s
    return s[keep], e[keep]


def _length(iv) -> float:
    s, e = iv
    return float((e - s).sum())


def _overlap(a, b) -> float:
    """Length of the intersection of two unions of disjoint intervals."""
    both = trace.merge(np.concatenate([a[0], b[0]]),
                       np.concatenate([a[1], b[1]]))
    return _length(a) + _length(b) - _length(both)


def window_close(obs, line: trace.Line) -> float:
    ends = [ln.end[[n == WAIT for n in ln.names]] for ln in obs.hosts]
    ends = [e for e in ends if e.size]
    if ends:
        return float(max(e.max() for e in ends))
    return float(line.end[[n == APPLY for n in line.names]].max())


def idle_split(obs) -> Optional[Dict[str, float]]:
    """Percent of the traced window the device sat idle while the apply
    thread was in a read (``read``), elsewhere in ``pool.apply``
    (``host``), or outside it (``starved``); averaged over devices."""
    line = apply_line(obs)
    if line is None or not obs.devices or obs.window_s <= 0:
        return None
    t1 = window_close(obs, line)
    t0 = t1 - obs.window_s * 1e9
    is_read = np.array([n.startswith(READ) for n in line.names], bool)
    is_apply = np.array([n == APPLY for n in line.names], bool)
    reads = _clipped(line.start[is_read], line.end[is_read], t0, t1)
    work = is_read | is_apply
    applying = _clipped(line.start[work], line.end[work], t0, t1)
    out = {"read": 0.0, "host": 0.0, "starved": 0.0}
    for d in obs.devices:
        busy = _clipped(*d.busy(), t0, t1)
        idle = (t1 - t0) - _length(busy)
        idle_read = _length(reads) - _overlap(reads, busy)
        idle_apply = _length(applying) - _overlap(applying, busy)
        out["read"] += idle_read
        out["host"] += idle_apply - idle_read
        out["starved"] += idle - idle_apply
    scale = 100.0 / (t1 - t0) / len(obs.devices)
    return {k: v * scale for k, v in out.items()}


@functools.lru_cache(maxsize=2)
def _span_stats(path: str, index: int) -> List[dict]:
    """Metadata of the ``pool.apply`` spans on the ``index``-th line of the
    trace file (in ``trace.load_lines`` order); only that line is read."""
    import gzip
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    k = 0
    for plane in ProfileData.from_serialized_xspace(data).planes:
        for ln in plane.lines:
            if k == index:
                return [dict(ev.stats) for ev in ln.events
                        if ev.name == APPLY]
            k += 1
    return []


def apply_stats(obs) -> List[dict]:
    """The per-epoch counters recorded on the traced ``pool.apply`` spans,
    read back from the run's trace file (the loaded lines keep no span
    metadata)."""
    from bench import cell
    line = apply_line(obs)
    if line is None:
        return []
    index = next(i for i, ln in enumerate(obs.lines) if ln is line)
    return _span_stats(trace.find_xplane(cell.TRACE_DIR), index)


def per_epoch(stats: List[dict]) -> Optional[Dict[str, float]]:
    """Mean of each counter over the epochs that recorded all of them."""
    rows = [s for s in stats if all(k in s for k in COUNTERS)]
    if not rows:
        return None
    return {k: sum(float(s[k]) for s in rows) / len(rows) for k in COUNTERS}


def counters(obs) -> Optional[Dict[str, float]]:
    return per_epoch(apply_stats(obs))
