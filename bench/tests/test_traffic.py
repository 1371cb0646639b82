"""The traffic generator: a function of the seed, live count flat, the mix
as the file states it."""
import numpy as np
import pytest

from bench import graph, traffic
from bench.reference import Reference
from bench.tests.helpers import tiny


def batches(seed, n=5, **kw):
    cfg, mix = tiny(**kw)
    keys = graph.rmat_graph(cfg, seed)
    s = traffic.make_stream(mix, keys, 1 << cfg["scale"], seed)
    return keys, s, [s.next() for _ in range(n)]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_same_seed_same_stream(seed):
    k1, _, b1 = batches(seed)
    k2, _, b2 = batches(seed)
    assert np.array_equal(k1, k2)
    for (r1, w1), (r2, w2) in zip(b1, b2):
        assert np.array_equal(r1, r2) and np.array_equal(w1, w2)
    k3, _, b3 = batches(seed + 1)
    assert not np.array_equal(b1[0][0], b3[0][0])


def test_live_count_flat_and_mix_shares():
    keys, s, _ = batches(11, n=0, batch=40)
    ref = Reference(keys, s.nv)
    for _ in range(8):
        rows, w = s.next()
        assert rows.shape == (40, 2) and rows.dtype == np.int32
        ins, dels = ref.normalize(rows, w)
        # the dirty rows net away; the rest changes the live set exactly
        assert ins.size == s.n_ins and dels.size == s.n_del
        assert (w > 0).sum() == s.n_ins + s.n_re
        ref.apply(ins, dels)
        assert len(ref.out) == keys.size == len(s.live) == s.n
        assert np.array_equal(ref.live_keys(), s.live.sorted())


def test_inserts_follow_degree():
    """Preferential attachment: insert sources are drawn per live edge
    endpoint, so high-degree vertices are sources far above uniform."""
    keys, s, bs = batches(2, n=20, scale=10, batch=256)
    deg = np.bincount(graph.unpack(keys).ravel(), minlength=s.nv)
    hubs = set(np.argsort(deg)[-s.nv // 100:].tolist())
    src = np.concatenate([r[w > 0][:, 0] for r, w in bs])
    share = np.mean([v in hubs for v in src.tolist()])
    assert share > 0.1  # uniform sources would give about 0.01
