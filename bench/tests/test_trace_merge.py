"""The interval arithmetic of the trace reduction."""
import numpy as np

from bench import trace


def test_merge_unions_overlaps_and_keeps_gaps():
    s = np.array([5.0, 0.0, 2.0, 10.0, 12.0, 11.0])
    e = np.array([6.0, 3.0, 4.0, 13.0, 14.0, 11.5])
    ms, me = trace.merge(s, e)
    assert ms.tolist() == [0.0, 5.0, 10.0]
    assert me.tolist() == [4.0, 6.0, 14.0]


def test_merge_nested_intervals():
    ms, me = trace.merge(np.array([0.0, 1.0, 2.0]),
                         np.array([10.0, 2.0, 3.0]))
    assert ms.tolist() == [0.0] and me.tolist() == [10.0]


def test_module_base_names():
    assert trace.module_base("jit__commit_fold(123)") == "_commit_fold"
    assert trace.module_base("jit_step") == "step"
    assert trace.module_base("shard_map_x") == "shard_map_x"


def test_ops_are_assigned_to_the_module_that_holds_them():
    mods = trace.Line("p", trace.MODULE_LINE, ["a", "b"],
                      np.array([0.0, 100.0]), np.array([50.0, 50.0]))
    ops = trace.Line("p", trace.OP_LINE, ["x", "y", "z"],
                     np.array([10.0, 120.0, 70.0]),
                     np.array([5.0, 5.0, 5.0]))
    assert trace.assign_ops(mods, ops).tolist() == [0, 1, -1]
