"""One run of one cell: set-up, the timed window, then the check.

The served path is the one ``python -m repro.launch.serve --stream`` drives:
one ``SessionPool`` tenant admitted with ``coalesce=1``, batches sent with
``tenant.submit(...)`` and answers taken with ``.result()``.  A feeder in a
closed loop keeps ``in_flight`` batches outstanding, so the pool's host prep
of one batch overlaps the device epoch of the one before.

After the window every warm-up and window epoch is replayed through the
numpy reference (``bench/reference.py``): each epoch's normalized inserts
and deletes, its signed triangle delta, and at the end the whole live edge
set must be equal.  The reference runs after the window, the device peak
and the pool having been read and closed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import shutil
import statistics
import sys
import time
from typing import Callable, List, Optional

import numpy as np

from bench import graph, traffic
from bench.reference import Reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# an answer due in the window may come late; wait this long past its close
LATE_S = 60.0
# seconds of the window a traced run records
TRACE_S = 15.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Sent:
    """One submitted batch and what came back for it."""

    rows: np.ndarray
    w: np.ndarray
    t_sent: float
    phase: str  # "warmup" | "window"
    t_done: Optional[float] = None
    ins: Optional[np.ndarray] = None
    dels: Optional[np.ndarray] = None
    count_delta: Optional[int] = None
    epoch: Optional[int] = None
    error: Optional[str] = None


def _take(ticket, sent: Sent, timeout=None) -> None:
    try:
        res = ticket.result(timeout)
    except TimeoutError:
        sent.error = "no answer"
        return
    except Exception as e:  # the program failed this batch: a wrong answer
        sent.error = f"{type(e).__name__}: {e}"
        sent.t_done = time.perf_counter()
        return
    sent.t_done = time.perf_counter()
    sent.ins = graph.pack(res.ins[:, 0], res.ins[:, 1])
    sent.dels = graph.pack(res.dels[:, 0], res.dels[:, 1])
    sent.count_delta = int(sum(d.count_delta for d in res.deltas.values()))
    sent.epoch = int(res.epoch)


class Ahead:
    """The stream with the next batch generated ahead of its send, so the
    feeder makes it while the device works.  A batch made and not yet
    sent waits here for the next loop: every batch made is sent, in
    order."""

    def __init__(self, stream):
        self.stream = stream
        self.batch = None

    def fill(self) -> None:
        if self.batch is None:
            self.batch = self.stream.next()

    def take(self):
        self.fill()
        batch, self.batch = self.batch, None
        return batch


def drive(submit: Callable, feed: Ahead, in_flight: int, phase: str,
          batches: Optional[int] = None, until: Optional[float] = None,
          annotate=None) -> List[Sent]:
    """Closed loop: keep ``in_flight`` batches outstanding; stop sending
    after ``batches`` batches or at time ``until``.  Answers of batches
    sent before ``until`` are awaited up to ``LATE_S`` past it."""
    span = annotate or (lambda name: contextlib.nullcontext())
    out: List[Sent] = []
    pending = collections.deque()
    with span("feeder.generate"):
        feed.fill()

    def may_send():
        if batches is not None:
            return len(out) < batches
        return time.perf_counter() < until

    while True:
        while len(pending) < in_flight and may_send():
            rows, w = feed.take()
            with span("feeder.submit"):
                sent = Sent(rows, w, time.perf_counter(), phase)
                ticket = submit(rows, w)
            out.append(sent)
            pending.append((ticket, sent))
            with span("feeder.generate"):
                feed.fill()
        if not pending:
            return out
        ticket, sent = pending.popleft()
        timeout = None if until is None else \
            max(until + LATE_S - time.perf_counter(), 0.0)
        with span("feeder.wait"):
            _take(ticket, sent, timeout)


def window_metrics(sent: List[Sent], t0: float, t1: float) -> dict:
    """End-to-end numbers of one window that opens at ``t0`` and closes at
    the first answer at or after ``t1``: the rate over every row answered
    up to the close, over the time to it, and latencies over all those
    answers.  Closing at an answer leaves no part of a batch's time in the
    window uncounted."""
    done = sorted((s for s in sent if s.phase == "window"
                   and s.error is None and s.t_done is not None),
                  key=lambda s: s.t_done)
    late = [i for i, s in enumerate(done) if s.t_done >= t1]
    t_close = t1
    if late:
        done = done[:late[0] + 1]
        t_close = done[-1].t_done
    lat = [(s.t_done - s.t_sent) * 1e3 for s in done]
    rows = sum(s.rows.shape[0] for s in done)
    out = {"updates_per_s": rows / (t_close - t0), "answered": len(done),
           "closed_s": t_close - t0}
    if len(lat) >= 2:
        q = statistics.quantiles(lat, n=100, method="inclusive")
        out["delta_p50_ms"] = statistics.median(lat)
        out["delta_p90_ms"] = q[89]
    return out


def verify(keys: np.ndarray, nv: int, sent: List[Sent], final_live):
    """Replay every epoch through the reference and count disagreements,
    each to be compared with its limit of 0.  Returns (the counts, the
    number of batches whose answer was missing or wrong)."""
    ref = Reference(keys, nv)
    checks = collections.OrderedDict(
        unanswered=0, epoch_order=0, normalize_mismatch=0,
        delta_mismatch=0, live_set_mismatch=0)
    failed = 0
    last_epoch = None
    for s in sent:
        ins, dels, delta = ref.epoch(s.rows, s.w)
        if s.error is not None:
            checks["unanswered"] += 1
            failed += 1
            continue
        bad = {
            "epoch_order": last_epoch is not None
            and s.epoch != last_epoch + 1,
            "normalize_mismatch": not (np.array_equal(np.sort(s.ins), ins)
                                       and np.array_equal(np.sort(s.dels),
                                                          dels)),
            "delta_mismatch": s.count_delta != delta}
        last_epoch = s.epoch
        for k, v in bad.items():
            checks[k] += int(v)
        failed += any(bad.values())
    want = ref.live_keys()
    got = np.unique(np.asarray(final_live, np.int64))
    checks["live_set_mismatch"] = int(
        np.setxor1d(want, got, assume_unique=True).size)
    return checks, failed


def device_record(devices, count: int) -> dict:
    """The device as JAX reports it, with the peak bytes in use on the
    fullest of the cell's ``devices``; ``count`` is how many JAX sees."""
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": count, "memory_peak_bytes": peak}


class CompileMeter:
    """XLA/Mosaic compiles, from JAX's own monitoring events: every program
    built (``count``, ``seconds``, persistent-cache reads included) and
    those read back from the persistent cache (``hits``)."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.count, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **kw) -> None:
        if event == self.BUILD:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == self.HIT:
            self.hits += 1


def run_cell(cfg: dict, mix: dict, seed: int, seconds: float,
             trace: bool = False, fault: Optional[Callable] = None,
             t_start: Optional[float] = None) -> dict:
    """One run.  Returns the result record (see ``bench/run.py``).
    ``t_start`` is when set-up began (the process start, for the CLI).
    ``fault``, for the tests, is called with the admitted tenant before the
    warm-up, so that it can break the timed path underneath."""
    import jax
    from repro.core import compilestats
    from repro.serve import SessionPool

    if t_start is None:
        t_start = time.perf_counter()
    meter = CompileMeter()
    chips = int(cfg["layout"]["chips"])
    devices = jax.devices()[:chips]
    keys = graph.rmat_graph(cfg, seed)
    nv = 1 << int(cfg["scale"])
    t_graph = time.perf_counter()
    stream = traffic.make_stream(mix, keys, nv, seed)
    feed = Ahead(stream)
    if chips == 1:
        pool_kw = {"local": True}
    else:
        from jax.sharding import Mesh
        from repro.core.distributed import AXIS
        pool_kw = {"local": False, "mesh": Mesh(np.array(devices), (AXIS,))}
    pool = SessionPool(
        update_batch=stream.batch, prewarm=False,
        balance=bool(cfg["serve"]["balance"]), **pool_kw)
    try:
        tenant = pool.admit("bench", graph.unpack(keys),
                            queries=[cfg["query"]],
                            coalesce=int(cfg["serve"]["coalesce"]))
        t_admit = time.perf_counter()
        if fault is not None:
            fault(tenant)
        in_flight = int(mix["in_flight"])
        sent = drive(tenant.submit, feed, in_flight, "warmup",
                     batches=int(mix["warmup_batches"]))
        t_warm = time.perf_counter()
        log("warm-up latencies (ms): " + " ".join(
            f"{(s.t_done - s.t_sent) * 1e3:.0f}" for s in sent
            if s.t_done is not None))
        log(f"set-up: graph {t_graph - t_start:.2f}s "
            f"({keys.size:,} edges), admission {t_admit - t_graph:.2f}s, "
            f"warm-up {len(sent)} batches {t_warm - t_admit:.2f}s; "
            f"{meter.count} programs built in {meter.seconds:.2f}s, "
            f"{meter.hits} of them from the compile cache")

        c0, b0 = compilestats.snapshot(), meter.count
        n_prep0 = len(tenant.stats.prep_ms)
        window, traced_s, n_traced = [], None, 0
        t0 = time.perf_counter()
        t1 = t0 + seconds
        if trace:
            # the trace holds the first TRACE_S of the window: the batches
            # sent in it, each answered whole, then the window goes on
            # untraced (a trace of the whole window takes minutes to read)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python calls would swamp it
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            window = drive(tenant.submit, feed, in_flight, "window",
                           until=t0 + min(seconds, TRACE_S),
                           annotate=jax.profiler.TraceAnnotation)
            traced_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            n_traced = len(window)
            n_prep_traced = len(tenant.stats.prep_ms) - n_prep0
        if time.perf_counter() < t1:
            window += drive(tenant.submit, feed, in_flight, "window",
                            until=t1)
        window_compiles = compilestats.since(c0)
        window_backend = meter.count - b0
        prep_ms = list(tenant.stats.prep_ms[n_prep0:])
        if trace:
            prep_ms = prep_ms[:n_prep_traced]
        sent += window
        dev = device_record(devices, len(jax.devices()))
        final_live = graph.pack(*tenant.session.edges.T.astype(np.int64))
    finally:
        pool.close(drain=False)
    setup_s = t0 - t_start
    e2e = window_metrics(sent, t0, t1)
    log(f"window: {e2e['answered']} answers in {e2e['closed_s']:.2f}s, "
        f"{window_compiles} compile events, {window_backend} programs "
        f"built; peak {dev['memory_peak_bytes']:,} B")
    t_v = time.perf_counter()
    checks, failed = verify(keys, nv, sent, final_live)
    log(f"reference check of {len(sent)} epochs: "
        f"{time.perf_counter() - t_v:.2f}s")
    return {
        "setup_s": setup_s, "e2e": e2e, "device": dev, "checks": checks,
        "attempted": len(sent), "failed": failed,
        "window": {"t0": t0, "t1": t1, "seconds": seconds,
                   "epochs": e2e["answered"], "prep_ms": prep_ms,
                   "epochs_traced": n_traced, "traced_s": traced_s,
                   "compiles": window_compiles,
                   "backend_compiles": window_backend},
        "trace_dir": TRACE_DIR if trace else None,
        "programs": {"built": meter.count, "seconds": meter.seconds,
                     "cache_hits": meter.hits},
    }
