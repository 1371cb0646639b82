"""The trace reduction on a small trace recorded on the chip: a traced run
of the one-chip cell's path at scale 10 with 64-row batches (its host
metadata plane stripped to keep the file small)."""
import importlib.util
import os

import numpy as np
import pytest

from bench import kernels, trace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "tiny-b64-scale10.xplane.pb.gz")
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
EPOCHS = 2


@pytest.fixture(scope="module")
def obs():
    lines = trace.load_lines(DATA)
    starts = [ln.start.min() for ln in lines if ln.names]
    ends = [ln.end.max() for ln in lines if ln.names]
    res = {"window": {"epochs_traced": EPOCHS, "prep_ms": [0.2, 0.3],
                      "compiles": 0,
                      "traced_s": (max(ends) - min(starts)) / 1e9},
           "device": {"kind": "TPU v5 lite"}}
    return trace.Observed(res, {"name": "tiny"}, lines)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(LAYERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_one_device_with_programs_and_ops(obs):
    assert [d.plane for d in obs.devices] == ["/device:TPU:0"]
    d = obs.devices[0]
    names = {trace.module_base(n) for n in d.modules.names}
    assert {"step", "seed_step", "_commit_fold_impl",
            "_normalize_core"} <= names
    assert (d.op_module >= 0).mean() > 0.99  # ops sit inside programs


def test_busy_is_the_union_of_op_intervals(obs):
    d = obs.devices[0]
    s, e = d.busy()
    assert np.all(s[1:] > e[:-1])  # disjoint, sorted
    total = float((e - s).sum())
    assert total <= float(d.ops.dur.sum())
    assert 0 < obs.busy_s <= obs.window_s
    # every op lies inside the union
    i = np.searchsorted(s, d.ops.start, side="right") - 1
    assert np.all(d.ops.end <= e[i] + 1e-6)


def test_self_times_partition_the_busy_time(obs):
    d = obs.devices[0]
    own = trace.self_times(d.ops)
    s, e = d.busy()
    assert own.sum() == pytest.approx(float((e - s).sum()), rel=1e-6)


def test_member_bytes_from_the_call_shapes(obs):
    calls = obs.ops(lambda op: op == "probe_call")
    assert calls
    for name, _ in calls:
        b = kernels.member_bytes(name)
        # [Bp, 1] outputs, R regions of W words: Bp*R*W segment rows of
        # 128 int32 words, plus the small operands
        head = name.split(" custom-call(")[1]
        shapes = kernels._shapes(head.split("), custom_call_target")[0])
        R, Bp, _ = shapes[1][1]
        W = shapes[2][1][0]
        assert b >= Bp * R * W * 128 * 4
        assert b < Bp * R * W * 128 * 4 * 1.1


def test_layer_readers(obs):
    store = reader("store.device_ms_per_epoch")(obs)
    flow = reader("dataflow.device_ms_per_epoch")(obs)
    member = reader("member.device_ms_per_epoch")(obs)
    roof = reader("member_roofline")(obs)
    idle = reader("device.idle_pct")(obs)
    assert store > 0 and flow > 0 and member > 0
    assert 0 < roof <= 100
    assert 0 < idle < 100
    assert reader("pool.prep_ms")(obs) == pytest.approx(0.25)
    assert reader("window_compiles")(obs) == 0
    # the programs' time splits into the layers without double counting
    mods = obs.module_seconds(lambda m: True) * 1e3 / EPOCHS
    assert store + flow + member <= mods + 1e-9


def test_readers_find_nothing_in_a_trace_without_devices(obs):
    empty = trace.Observed(
        {"window": {"epochs_traced": 2, "prep_ms": [], "compiles": 0,
                    "traced_s": 1.0}, "device": {"kind": "TPU v5 lite"}},
        {"name": "none"}, [ln for ln in obs.lines
                           if not trace.is_device_plane(ln.plane)])
    for name in ("store.device_ms_per_epoch", "dataflow.device_ms_per_epoch",
                 "member.device_ms_per_epoch", "member_roofline",
                 "device.idle_pct", "pool.prep_ms"):
        assert reader(name)(empty) is None


def test_breakdown(obs):
    b = obs.breakdown()
    ops, gaps = b["device_ops"], b["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    secs = [v for _, v in ops]
    assert secs == sorted(secs, reverse=True)
    assert any(k == "step/probe_call" for k, _ in ops)
    assert all(isinstance(n, str) and v > 0 for n, v in gaps)


def test_unknown_device_kind_is_an_error(obs):
    obs2 = trace.Observed(
        {"window": {"epochs_traced": 2, "prep_ms": [], "compiles": 0,
                    "traced_s": 1.0}, "device": {"kind": "TPU v9"}},
        {"name": "x"}, obs.lines)
    with pytest.raises(KeyError):
        obs2.peaks()
