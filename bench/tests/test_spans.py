"""The readers of the program's own spans and counters (``bench/spans.py``)
on synthetic trace lines, and the counter readers on a traced CPU run of
the harness's own cell function."""
import importlib.util
import os

import numpy as np
import pytest

from bench import cell, spans, trace
from bench.tests.helpers import tiny

LAYERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layers")
IDLE = ("device.idle_read_pct", "device.idle_host_pct",
        "device.idle_starved_pct")
COUNTS = ("dataflow.steps_per_epoch", "device.host_reads_per_epoch",
          "device.host_read_mb_per_epoch")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(LAYERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def line(plane, name, events):
    """A trace line from (name, start, end) triples, in nanoseconds."""
    names = [n for n, _, _ in events]
    start = np.array([s for _, s, _ in events], np.float64)
    end = np.array([e for _, _, e in events], np.float64)
    return trace.Line(plane, name, names, start, end - start)


def observed(lines, window_ns=100.0, epochs=2):
    res = {"window": {"epochs_traced": epochs, "prep_ms": [0.1] * epochs,
                      "compiles": 0, "traced_s": window_ns / 1e9},
           "device": {"kind": "TPU v5 lite"}}
    return trace.Observed(res, {"name": "synthetic"}, lines)


BUSY = [(10, 20), (40, 60), (80, 95)]


def device_lines(busy=BUSY, plane="/device:TPU:0"):
    return [line(plane, trace.MODULE_LINE,
                 [("jit_step(1)", s, e) for s, e in busy]),
            line(plane, trace.OP_LINE,
                 [("%fusion.1 = s32[8] fusion()", s, e) for s, e in busy])]


def host_lines():
    """The apply thread: two epochs, three reads (idle 5, idle 10, busy);
    the feeder's last answer closes the window at 100."""
    apply = line("/host:CPU", "python3", [
        ("pool.apply", 5, 30), ("store.normalize", 18, 26),
        ("read.normalize", 20, 25), ("np.asarray(jax.Array)", 20, 25),
        ("pool.apply", 35, 90), ("dataflow.run", 36, 89),
        ("read.queues", 60, 70), ("read.collect", 85, 88)])
    feeder = line("/host:CPU", "python3", [
        ("feeder.wait", 2, 30), ("feeder.wait", 31, 100)])
    prep = line("/host:CPU", "python3", [("pool.prep", 0, 4),
                                         ("pool.prep", 31, 33)])
    return [feeder, apply, prep]


def test_idle_split_by_what_the_apply_thread_did():
    obs = observed(device_lines() + host_lines())
    got = {name: reader(name)(obs) for name in IDLE}
    # idle 55 of 100: reads 5 + 10; in pool.apply otherwise 25; outside
    # it [0, 5], [30, 35] and [95, 100]
    assert got == pytest.approx({"device.idle_read_pct": 15.0,
                                 "device.idle_host_pct": 25.0,
                                 "device.idle_starved_pct": 15.0})
    assert sum(got.values()) == pytest.approx(
        reader("device.idle_pct")(obs))


def test_idle_split_sums_to_the_idle_share_on_two_devices():
    busy2 = [(0, 50), (70, 72)]
    obs = observed(device_lines() + device_lines(busy2, "/device:TPU:1")
                   + host_lines())
    got = [reader(name)(obs) for name in IDLE]
    assert all(v >= 0 for v in got)
    assert sum(got) == pytest.approx(reader("device.idle_pct")(obs))


def test_window_closes_at_the_last_apply_without_a_feeder():
    lines = device_lines() + host_lines()[1:]
    obs = observed(lines, window_ns=90.0)  # [0, 90]: the last apply's end
    got = {name: reader(name)(obs) for name in IDLE}
    idle = 90.0 - (10 + 20 + 10)
    assert sum(got.values()) == pytest.approx(100.0 * idle / 90.0)
    assert got["device.idle_read_pct"] == pytest.approx(100.0 * 15 / 90)


def test_readers_find_nothing_without_program_spans():
    """The parent program records no ``pool.apply``: every reader returns
    None and none raises."""
    feeder = host_lines()[0]
    obs = observed(device_lines() + [feeder])
    for name in IDLE + COUNTS:
        assert reader(name)(obs) is None
    # and without a device, the idle split has nothing to cut
    assert reader(IDLE[0])(observed(host_lines())) is None


def test_counter_readers_average_over_epochs(monkeypatch):
    stats = [{"level_steps": 10, "host_reads": 40,
              "host_read_bytes": 3_000_000},
             {"level_steps": 20, "host_reads": 60,
              "host_read_bytes": 5_000_000},
             {"other": 1}]  # an apply span without the counters: skipped
    monkeypatch.setattr(spans, "apply_stats", lambda obs: stats)
    obs = observed(device_lines() + host_lines())
    got = [reader(name)(obs) for name in COUNTS]
    assert got == pytest.approx([15.0, 50.0, 4.0])
    assert spans.per_epoch([]) is None


def test_counter_readers_on_a_traced_cpu_run(tmp_path, monkeypatch):
    """Through the harness: the counters the pool records on its spans are
    found in the trace file and match ``TenantStats``."""
    monkeypatch.setattr(cell, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(cell, "TRACE_S", 1.0)
    cfg, mix = tiny()
    res = cell.run_cell(cfg, mix, seed=2**31 + 11, seconds=1.0, trace=True)
    assert res["checks"] == {k: 0 for k in res["checks"]}
    obs = trace.Observed.load(res, {"name": "tiny"})
    n = len(spans.apply_stats(obs))
    assert n == res["window"]["epochs_traced"] > 0
    steps, reads, mb = (reader(name)(obs) for name in COUNTS)
    assert steps > 0 and reads > steps and mb > 0
