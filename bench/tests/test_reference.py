"""The numpy reference against a brute-force count, and its normalize."""
import numpy as np
import pytest

from bench import graph, traffic
from bench.reference import Reference
from bench.tests.helpers import tiny


def brute_triangles(keys: np.ndarray, nv: int) -> int:
    """tri(a,b,c) :- e(a,b), e(b,c), e(a,c), counted over a dense
    adjacency matrix."""
    adj = np.zeros((nv, nv), np.int64)
    rows = graph.unpack(keys)
    adj[rows[:, 0], rows[:, 1]] = 1
    return int(np.einsum("ab,bc,ac->", adj, adj, adj))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_epoch_deltas_equal_brute_force(seed):
    cfg, mix = tiny(scale=7, batch=48)
    keys = graph.rmat_graph(cfg, seed)
    nv = 1 << cfg["scale"]
    stream = traffic.make_stream(mix, keys, nv, seed)
    ref = Reference(keys, nv)
    live = set(keys.tolist())
    total = brute_triangles(keys, nv)
    for _ in range(6):
        rows, w = stream.next()
        ins, dels, delta = ref.epoch(rows, w)
        live -= set(dels.tolist())
        live |= set(ins.tolist())
        now = brute_triangles(np.array(sorted(live), np.int64), nv)
        assert delta == now - total
        total = now
    assert np.array_equal(ref.live_keys(), np.array(sorted(live)))


def test_normalize_nets_a_dirty_batch():
    k = graph.pack([0, 1, 2], [1, 2, 0])  # a 3-cycle: no tri(a,b,c)
    ref = Reference(np.sort(k), 8)
    rows = np.array([[0, 1], [3, 4], [3, 4], [5, 5], [1, 2], [6, 7],
                     [6, 7], [2, 0], [0, 2]])
    w = np.array([1, 1, 1, 1, -1, 1, -1, 0, 1])
    ins, dels = ref.normalize(rows, w)
    # (0,1) live re-insert: no-op; (3,4) net +2: insert; (5,5) self-loop;
    # (1,2) live delete; (6,7) nets to 0; (2,0) zero weight; (0,2) new
    assert ins.tolist() == graph.pack([0, 3], [2, 4]).tolist()
    assert dels.tolist() == graph.pack([1], [2]).tolist()


def test_triangles_through_counts_each_triangle_once():
    # a, b, c = 0, 1, 2 with all three edges: one triangle (0, 1, 2)
    k = np.sort(graph.pack([0, 1, 0], [1, 2, 2]))
    ref = Reference(k, 4)
    assert ref.triangles_through(k) == 1
    assert ref.triangles_through(k[:1]) == 1
    assert ref.triangles_through(graph.pack([2], [3])) == 0
