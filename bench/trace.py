"""From a profiler trace of the window to per-layer numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each device is one plane (``/device:TPU:<n>``); its ``XLA Modules`` line
holds one event per executed program (named after the jitted function,
``jit_<name>(<id>)``) and its ``XLA Ops`` line one event per operation in
it.  Host threads are lines of the ``/host:CPU`` plane; the feeder's own
``TraceAnnotation`` spans and JAX's dispatch events are there.

The reduction:

- busy time of a device is the union of its op intervals (its module
  intervals where a trace has no op line); idle = window - busy;
- each op is assigned to the module whose interval holds it, so a layer's
  time is the time of its programs, and a kernel's time that of its ops;
- every idle gap between busy intervals is named by the host event that
  overlaps it most (innermost span first), for the breakdown.

``Observed`` is what the per-layer readers (``bench/layers/*.py``) see.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Line:
    plane: str
    name: str
    names: List[str]
    start: np.ndarray  # ns
    dur: np.ndarray  # ns

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return found[-1]


def load_lines(path: str) -> List[Line]:
    """Every line of a trace file (``.xplane.pb``, or gzipped)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    lines = []
    for plane in pd.planes:
        for line in plane.lines:
            names, start, dur = [], [], []
            for ev in line.events:
                names.append(ev.name)
                start.append(ev.start_ns)
                dur.append(ev.duration_ns)
            lines.append(Line(plane.name, line.name, names,
                              np.asarray(start, np.float64),
                              np.asarray(dur, np.float64)))
    return lines


def merge(start: np.ndarray, end: np.ndarray):
    """Union of intervals: merged (start, end) arrays, sorted."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    ms = s[idx]
    me = np.maximum.reduceat(e, idx)
    me = np.maximum.accumulate(me)
    return ms, me


def is_device_plane(name: str) -> bool:
    return re.match(r"^/device:TPU:\d+$", name) is not None


MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
OP_NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)?(?: =|$)")


def module_base(name: str) -> str:
    """``jit__commit_fold(123)`` -> ``_commit_fold``."""
    return MODULE_NAME.match(name).group(1)


@functools.lru_cache(maxsize=1 << 16)  # a trace repeats few op texts
def op_base(name: str) -> str:
    """``%probe_call.5 = (s32[..]) custom-call(..)`` -> ``probe_call``."""
    m = OP_NAME.match(name)
    return m.group(1) if m else name[:40]


def self_times(line: Line) -> np.ndarray:
    """Each event's duration less the events nested inside it (ops of a
    loop body sit inside the loop's event on the same line)."""
    order = np.lexsort((-line.dur, line.start))
    own = line.dur.copy()
    stack = []  # (end, index)
    for j in order:
        s, e = line.start[j], line.start[j] + line.dur[j]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= line.dur[j]
        stack.append((e, j))
    return np.maximum(own, 0.0)


@dataclasses.dataclass
class DeviceTrace:
    plane: str
    modules: Optional[Line]
    ops: Optional[Line]
    op_module: np.ndarray  # index of the module holding each op, -1 none

    def busy(self):
        src = self.ops if self.ops is not None and self.ops.names \
            else self.modules
        if src is None:
            return np.zeros(0), np.zeros(0)
        return merge(src.start, src.end)


def assign_ops(modules: Line, ops: Line) -> np.ndarray:
    if modules is None or ops is None or not modules.names:
        return np.full(0 if ops is None else len(ops.names), -1)
    order = np.argsort(modules.start, kind="stable")
    ms, me = modules.start[order], modules.end[order]
    i = np.searchsorted(ms, ops.start, side="right") - 1
    ok = (i >= 0) & (ops.start < me[np.maximum(i, 0)] + 1)
    return np.where(ok, order[np.maximum(i, 0)], -1)


def devices_of(lines: List[Line]) -> List[DeviceTrace]:
    by_plane: Dict[str, Dict[str, Line]] = {}
    for ln in lines:
        if is_device_plane(ln.plane):
            by_plane.setdefault(ln.plane, {})[ln.name] = ln
    out = []
    for plane in sorted(by_plane):
        ls = by_plane[plane]
        mods, ops = ls.get(MODULE_LINE), ls.get(OP_LINE)
        out.append(DeviceTrace(plane, mods, ops, assign_ops(mods, ops)))
    return out


def host_lines(lines: List[Line]) -> List[Line]:
    return [ln for ln in lines if ln.plane.startswith("/host:")]


def name_gap(t0: float, t1: float, hosts: List[Line]) -> str:
    """What the host was doing in the device gap [t0, t1]: the shortest
    host event that covers at least half of it, else the one that covers
    most of it."""
    best, best_dur = None, np.inf
    most, most_ov = "unattributed", 0.0
    for ln in hosts:
        if not ln.names:
            continue
        ov = np.minimum(ln.end, t1) - np.maximum(ln.start, t0)
        for j in np.flatnonzero(ov > 0):
            if ov[j] >= 0.5 * (t1 - t0) and ln.dur[j] < best_dur:
                best, best_dur = ln.names[j], ln.dur[j]
            if ov[j] > most_ov:
                most, most_ov = ln.names[j], ov[j]
    return best if best is not None else most


class Observed:
    """What a traced run saw: the window's own counts and the reduced
    device trace.  Layer readers take their numbers from here."""

    def __init__(self, res: dict, cell: dict, lines: List[Line]):
        self.cell = cell
        w = res["window"]
        self.epochs = int(w["epochs_traced"])
        self.prep_ms = list(w["prep_ms"])
        self.window_compiles = int(w["compiles"])
        self.window_s = float(w["traced_s"])
        self.device_kind = res["device"]["kind"]
        self.lines = lines
        self.devices = devices_of(lines)
        self.hosts = host_lines(lines)
        busy = [self._busy_ns(d) for d in self.devices]
        self.busy_s = float(np.mean(busy)) / 1e9 if busy else 0.0

    @classmethod
    def load(cls, res: dict, cell: dict) -> "Observed":
        return cls(res, cell, load_lines(find_xplane(res["trace_dir"])))

    @staticmethod
    def _busy_ns(d: DeviceTrace) -> float:
        s, e = d.busy()
        return float((e - s).sum())

    # -- helpers for the readers ------------------------------------------
    def module_seconds(self, keep: Callable[[str], bool]) -> float:
        """Device seconds of the programs whose base name ``keep`` accepts,
        summed over devices and divided by the device count."""
        tot = 0.0
        for d in self.devices:
            if d.modules is None:
                continue
            sel = np.array([keep(module_base(n)) for n in d.modules.names],
                           bool)
            tot += float(d.modules.dur[sel].sum()) if sel.size else 0.0
        return tot / 1e9 / max(len(self.devices), 1)

    def ops(self, keep_op: Callable[[str], bool],
            keep_module: Callable[[str], bool] = lambda m: True):
        """(name, seconds) of every op ``keep_op`` accepts (by its base
        name) inside a program ``keep_module`` accepts, on all devices."""
        out = []
        for d in self.devices:
            if d.ops is None:
                continue
            mods = d.modules.names if d.modules is not None else []
            for j, n in enumerate(d.ops.names):
                if not keep_op(op_base(n)):
                    continue
                m = d.op_module[j]
                if keep_module(module_base(mods[m]) if m >= 0 else ""):
                    out.append((n, d.ops.dur[j] / 1e9))
        return out

    def op_seconds(self, keep_op: Callable[[str], bool],
                   keep_module: Callable[[str], bool] = lambda m: True
                   ) -> float:
        """Device seconds of those ops, per device."""
        tot = sum(t for _, t in self.ops(keep_op, keep_module))
        return tot / max(len(self.devices), 1)

    def peaks(self) -> dict:
        with open(os.path.join(HERE, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           "in bench/peaks.json")
        return table[self.device_kind]

    # -- the breakdown --------------------------------------------------------
    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (self time, grouped as
        ``<program>/<op>``), and the longest idle gaps of the first device
        named by what the host was doing."""
        per_op: Dict[str, float] = {}
        for d in self.devices:
            src = d.ops if d.ops is not None and d.ops.names else d.modules
            if src is None:
                continue
            mods = d.modules.names if d.modules is not None else []
            own = self_times(src)
            for j, n in enumerate(src.names):
                m = d.op_module[j] if src is d.ops else -1
                key = (module_base(mods[m]) + "/" if m >= 0 else "") + \
                    op_base(n)
                per_op[key] = per_op.get(key, 0.0) + own[j] / 1e9
        n_dev = max(len(self.devices), 1)
        ops = sorted(((k, v / n_dev) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.devices:
            s, e = self.devices[0].busy()
            if s.size > 1:
                g0, g1 = e[:-1], s[1:]
                for j in np.argsort(g0 - g1)[:top]:
                    gaps.append([name_gap(g0[j], g1[j], self.hosts),
                                 float(g1[j] - g0[j]) / 1e9])
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": gaps}
