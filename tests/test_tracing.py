"""The served epoch path's own spans and read counters: every blocking
device-to-host read goes through ``repro.core.hostread.read``, each epoch
opens its spans in order on the pool's apply thread, and the pool sums the
reads and level steps per epoch into ``TenantStats``."""
import sys

import numpy as np
import jax
from jax._src.array import ArrayImpl

from bench import trace
from repro.core import hostread
from repro.data.synthetic import EdgeUpdateStream, uniform_graph
from repro.serve import SessionPool

NV, NE, BATCH = 24, 160, 16


def _batches(n, seed=7):
    """``n`` update batches of one stream, each from the live set the
    batches before it leave (the reference session's own)."""
    from repro.api import GraphSession
    g = uniform_graph(NV, NE, seed=1)
    ref = GraphSession(g, local=True, update_batch=64)
    stream = EdgeUpdateStream(NV, BATCH, insert_frac=0.5, seed=seed)
    out = []
    for step in range(n):
        upd, w = stream.batch_at(step, live=ref.edges)
        ref.update(upd, w)
        out.append((upd, w))
    return g, out


def _serve(g, batches):
    """One triangle tenant in a pipelined pool; returns (its stats, the
    epoch results)."""
    with SessionPool(local=True, update_batch=64, prewarm=False) as pool:
        h = pool.admit("t", g, queries=("triangle",), coalesce=1)
        results = [h.submit(upd, w).result(timeout=600)
                   for upd, w in batches]
        return h.stats, results


def test_spans_nest_on_the_apply_thread(tmp_path):
    g, batches = _batches(4)
    with SessionPool(local=True, update_batch=64, prewarm=False) as pool:
        h = pool.admit("t", g, queries=("triangle",), coalesce=1)
        h.submit(*batches[0]).result(timeout=600)  # compiles outside
        with jax.profiler.trace(str(tmp_path)):
            for upd, w in batches[1:]:
                h.submit(upd, w).result(timeout=600)
    lines = [ln for ln in trace.load_lines(trace.find_xplane(str(tmp_path)))
             if ln.plane.startswith("/host:")]
    apply = [ln for ln in lines if "pool.apply" in ln.names]
    assert len(apply) == 1
    ln = apply[0]
    names = np.array(ln.names, object)
    epochs = np.flatnonzero(names == "pool.apply")
    assert len(epochs) == 3

    def inside(j, name):
        """Spans called ``name`` (or starting with it) inside span j."""
        sel = np.array([n == name or (name.endswith(".")
                                      and n.startswith(name))
                        for n in ln.names])
        sel &= (ln.start >= ln.start[j]) & (ln.end <= ln.end[j])
        return np.flatnonzero(sel)

    for j in epochs:
        for name, want in (("store.normalize", 1), ("dataflow.run", 3),
                           ("store.commit", 1)):
            spans = inside(j, name)
            assert len(spans) == want, name
            for k in spans:
                assert len(inside(k, "read.")) >= 1, name
    prep = [x for x in lines if "pool.prep" in x.names]
    assert prep and all(x is not ln for x in prep)


def test_every_read_goes_through_the_helper(monkeypatch):
    """Count device-array conversions made during served epochs outside
    ``hostread.read``: jax's own conversion methods, and ``np.asarray`` /
    ``np.array``, which read a CPU array through the buffer protocol
    without calling ``__array__``."""
    g, batches = _batches(4)
    seen = {"inside": 0, "outside": []}
    helper = hostread.read.__code__

    def in_helper():
        f = sys._getframe(2)
        while f is not None:
            if f.f_code is helper:
                return True
            f = f.f_back
        return False

    def counting(owner, name, device_only=False):
        orig = getattr(owner, name)

        def wrapper(x, *a, **kw):
            if not device_only or isinstance(x, jax.Array):
                if in_helper():
                    seen["inside"] += 1
                else:
                    seen["outside"].append(name)
            return orig(x, *a, **kw)
        monkeypatch.setattr(owner, name, wrapper)

    with SessionPool(local=True, update_batch=64, prewarm=False) as pool:
        h = pool.admit("t", g, queries=("triangle",), coalesce=1)
        h.submit(*batches[0]).result(timeout=600)  # compiles outside
        reads0 = h.stats.host_reads
        for name in ("__array__", "__int__", "__index__", "__bool__"):
            counting(ArrayImpl, name)
        for name in ("asarray", "array"):
            counting(np, name, device_only=True)
        for upd, w in batches[1:]:
            h.submit(upd, w).result(timeout=600)
        monkeypatch.undo()
        reads = h.stats.host_reads - reads0
    assert seen["outside"] == []
    assert seen["inside"] >= reads > 0


def test_counters_are_per_epoch_sums_and_repeat():
    g, batches = _batches(4)
    a, results = _serve(g, batches)
    b, _ = _serve(g, batches)
    runs = [r for res in results for d in res.deltas.values()
            for r in d.per_dq]
    assert a.level_steps == sum(r.steps for r in runs) > 0
    assert a.host_reads > sum(r.host_reads for r in runs) > 0
    assert a.host_read_bytes > sum(r.host_read_bytes for r in runs) > 0
    for key in ("level_steps", "host_reads", "host_read_bytes"):
        assert getattr(a, key) == getattr(b, key), key
        assert a.as_dict()[key] == getattr(a, key)


def test_render_shows_the_counters():
    from repro.serve.stats import ServeStats, TenantStats
    t = TenantStats(name="x", level_steps=12, host_reads=30,
                    host_read_bytes=2_500_000)
    text = ServeStats(tenants={"x": t}).render()
    assert "12 level steps, 30 host reads (2.5 MB)" in text


def test_helper_counts_device_arrays_only():
    counts = hostread.Reads()
    x = jax.numpy.arange(6, dtype=jax.numpy.int32)
    assert hostread.read("collect", counts, np.asarray, x).tolist() == \
        list(range(6))
    assert hostread.read("scalars", counts, int, 5) == 5
    assert (counts.host_reads, counts.host_read_bytes) == (1, 24)


def test_helper_reads_under_a_disallowing_guard():
    counts = hostread.Reads()
    x = jax.numpy.ones(3, dtype=jax.numpy.int32)
    with jax.transfer_guard_device_to_host("disallow"):
        assert hostread.read("scalars", counts, int, x.sum()) == 3
    assert counts.host_reads == 1


def test_store_reads_count_into_store_stats():
    from repro.core.delta import RegionStore
    g, batches = _batches(2)
    store = RegionStore(g)
    seen = [store.stats.host_reads]
    ins, dels = store.normalize(*batches[0])
    seen.append(store.stats.host_reads)
    store.begin_epoch(ins, dels)
    seen.append(store.stats.host_reads)
    store.commit(ins, dels)
    seen.append(store.stats.host_reads)
    store._maybe_compact(force=True)
    seen.append(store.stats.host_reads)
    normalize, _stage, commit, compact = np.diff(seen)
    assert normalize == 6  # two counts, four row pulls
    assert commit > 0 and compact > 0
    assert store.stats.host_read_bytes > 0
