"""Device-resident RegionStore: fold primitives, compaction, transfer guard.

Four contracts, all from the delta-proportional epoch design (DESIGN.md §6):

- **fold algebra**: the jitted sorted-merge/diff/intersect folds
  (`csr.merge_index` etc) match numpy set semantics bit-exactly, including
  capacity padding, empty operands, narrow/wide key dtypes, and the
  vmapped per-shard path;
- **mode parity**: the device-resident store and the legacy host store are
  interchangeable — identical signed outputs, identical live edge sets,
  identical compaction accounting — over adversarial streams;
- **compaction**: ratio-threshold and eager re-insertion compactions fire
  when (and only when) they should, and a >= 50-epoch stream stays
  bit-exact across compaction boundaries while ``StoreStats.compactions``
  advances;
- **no full-graph work on the warm path**: with ``STRICT_TRANSFERS`` the
  jitted normalize/commit steps run under ``jax.transfer_guard("disallow")``
  — any host<->device copy raises — and a build spy proves the only index
  builds on a warm epoch are delta-sized staging, never a rebuild of base.
"""
import numpy as np
import pytest

import jax

from repro.compat import primitive_names
from repro.core import csr
from repro.core import delta as D
from repro.core import query as Q
from repro.core.bigjoin import BigJoinConfig
from repro.core.delta import DeltaBigJoin, RegionStore, delta_oracle

from tests.test_delta import canon
from tests.test_delta_stream import _start_edges, apply_net, random_batch

CFG = BigJoinConfig(batch=128, seed_chunk=128, out_capacity=1 << 15)


# ---------------------------------------------------------------------------
# fold primitives vs numpy set semantics
# ---------------------------------------------------------------------------

def _kvset(idx):
    n = int(np.asarray(idx.n).sum())
    if np.asarray(idx.n).ndim:  # sharded: flatten live prefixes
        ns = np.asarray(idx.n)
        ks = np.concatenate([np.asarray(idx.key)[k][:ns[k]]
                             for k in range(ns.shape[0])])
        vs = np.concatenate([np.asarray(idx.val)[k][:ns[k]]
                             for k in range(ns.shape[0])])
        return set(zip(ks.tolist(), vs.tolist()))
    return set(zip(np.asarray(idx.key)[:n].tolist(),
                   np.asarray(idx.val)[:n].tolist()))


def _lex_sorted(idx):
    n = int(idx.n)
    k = np.asarray(idx.key)[:n]
    v = np.asarray(idx.val)[:n]
    if n < 2:
        return True
    dk, dv = np.diff(k.astype(np.int64)), np.diff(v.astype(np.int64))
    return bool(((dk > 0) | ((dk == 0) & (dv > 0))).all())


@pytest.mark.parametrize("narrow", [True, False], ids=["i32", "i64"])
def test_fold_primitives_match_set_ops(narrow):
    rng = np.random.default_rng(0)
    for trial in range(15):
        na, nb = int(rng.integers(0, 70)), int(rng.integers(0, 40))
        ta = rng.integers(0, 40, (na, 2)).astype(np.int32)
        tb = rng.integers(0, 40, (nb, 2)).astype(np.int32)
        a = csr.build_index(ta, (0,), 1, narrow=narrow)
        b = csr.build_index(tb, (0,), 1, narrow=narrow)
        A, B = _kvset(a), _kvset(b)
        m = csr.merge_index(a, b, 512)
        d = csr.diff_index(a, b, int(a.capacity))
        x = csr.intersect_index(a, b, int(a.capacity))
        assert _kvset(m) == A | B and _lex_sorted(m), trial
        assert _kvset(d) == A - B and _lex_sorted(d), trial
        assert _kvset(x) == A & B and _lex_sorted(x), trial
        # sentinel padding: everything past n is the sentinel
        for out in (m, d, x):
            n = int(out.n)
            sent = csr.SENTINEL32 if narrow else csr.SENTINEL
            assert (np.asarray(out.key)[n:] == sent).all()


def test_sharded_fold_matches_unsharded():
    rng = np.random.default_rng(1)
    w = 4
    ta = rng.integers(0, 60, (150, 2)).astype(np.int32)
    tb = rng.integers(0, 60, (30, 2)).astype(np.int32)
    sa = csr.build_sharded_index(ta, (0,), 1, w)
    sb = csr.build_sharded_index(tb, (0,), 1, w, capacity=1)
    la = csr.build_index(ta, (0,), 1)
    lb = csr.build_index(tb, (0,), 1)
    vm = jax.jit(jax.vmap(lambda x, y: csr.merge_index(x, y, 512)))(sa, sb)
    vd = jax.jit(jax.vmap(
        lambda x, y: csr.diff_index(x, y, int(sa.key.shape[1]))))(sa, sb)
    assert _kvset(vm) == _kvset(la) | _kvset(lb)
    assert _kvset(vd) == _kvset(la) - _kvset(lb)
    # ownership is preserved by shard-local folds
    ns = np.asarray(vm.n)
    for k in range(w):
        keys = np.asarray(vm.key)[k][:ns[k]].astype(np.int64)
        assert (csr.shard_of(keys, w) == k).all()


# ---------------------------------------------------------------------------
# device store vs legacy host store: interchangeable
# ---------------------------------------------------------------------------

def test_device_store_matches_legacy_store_stream():
    q = Q.triangle()
    nv = 14
    edges = _start_edges(nv, 80, 3)
    dev = DeltaBigJoin(q, edges, cfg=CFG, device_resident=True)
    leg = DeltaBigJoin(q, edges, cfg=CFG, device_resident=False)
    assert dev.store.device_resident and not leg.store.device_resident
    rng = np.random.default_rng(4)
    cur = edges.copy()
    for step in range(8):
        upd, w = random_batch(rng, nv, cur, 12)
        a = dev.apply(upd, w)
        b = leg.apply(upd, w)
        assert canon(a.tuples, a.weights) == canon(b.tuples, b.weights), step
        assert a.count_delta == b.count_delta
        np.testing.assert_array_equal(dev.edges, leg.edges)
        cur = apply_net(cur, upd, w)
        np.testing.assert_array_equal(dev.edges, cur)


def test_store_normalize_parity_and_noops():
    edges = _start_edges(12, 60, 5)
    dev = RegionStore(edges, device_resident=True)
    leg = RegionStore(edges, device_resident=False)
    rng = np.random.default_rng(6)
    upd, w = random_batch(rng, 12, edges, 16)
    di, dd = dev.normalize(upd, w)
    li, ld = leg.normalize(upd, w)
    np.testing.assert_array_equal(di, li)
    np.testing.assert_array_equal(dd, ld)
    # absent deletes / live inserts / self-loops net to an exact no-op
    live = edges[:4]
    noop = np.concatenate([live, np.array([[7, 7], [900, 901]], np.int32)])
    wn = np.concatenate([np.ones(4, np.int32), np.ones(1, np.int32),
                         -np.ones(1, np.int32)])
    for store in (dev, leg):
        i, d = store.normalize(noop, wn)
        assert i.size == 0 and d.size == 0


# ---------------------------------------------------------------------------
# compaction: threshold, eager re-insertion, long-stream differential
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("device", [True, False], ids=["device", "legacy"])
def test_compaction_threshold_behavior(device):
    """committed > ratio * |base| triggers compaction at exactly the epoch
    the running committed size crosses the threshold, for every ensured
    projection."""
    base_edges = np.stack([np.arange(100, dtype=np.int32),
                           np.arange(100, dtype=np.int32) + 1000], 1)
    store = RegionStore(base_edges, compact_ratio=0.35,
                        device_resident=device)
    store.ensure("edge", (0,), 1)
    store.ensure("edge", (1,), 0)
    nproj = len(store.projections)
    fresh = np.stack([np.arange(40, dtype=np.int32) + 500,
                      np.arange(40, dtype=np.int32) + 2000], 1)
    trips = []
    for e in range(4):  # committed grows 10, 20, 30, 40 vs 0.35*100 = 35
        ins = fresh[e * 10:(e + 1) * 10]
        empty = ins[:0]
        store.begin_epoch(ins, empty)
        store.commit(ins, empty)
        trips.append(store.stats.compactions)
    assert trips == [0, 0, 0, nproj]  # fires only once 40 > 35
    for reg in store.projections.values():
        assert reg.cins.shape[0] == 0 and reg.cdel.shape[0] == 0
        assert reg.base.shape[0] == 140


@pytest.mark.parametrize("device", [True, False], ids=["device", "legacy"])
def test_eager_compaction_on_reinsert_after_committed_delete(device):
    q = Q.triangle()
    edges = _start_edges(14, 70, 8)
    engine = DeltaBigJoin(q, edges, cfg=CFG, compact_ratio=1e9,  # never
                          device_resident=device)
    victim = edges[:6]
    cur = engine.edges.copy()
    engine.apply(victim, -np.ones(6, np.int32))
    assert engine.store.stats.compactions == 0  # ratio can't fire
    cur = apply_net(cur, victim, -np.ones(6, np.int32))
    # re-inserting the committed deletes MUST force-compact every projection
    res = engine.apply(victim, np.ones(6, np.int32))
    assert engine.store.stats.compactions == len(engine.projections)
    after = apply_net(cur, victim, np.ones(6, np.int32))
    ot, ow = delta_oracle(q, cur, after)
    assert canon(res.tuples, res.weights) == canon(ot, ow)
    for reg in engine.projections.values():
        assert reg.cdel.shape[0] == 0  # the overlap source is gone


@pytest.mark.parametrize("device", [True, False], ids=["device", "legacy"])
def test_50_epoch_stream_bitexact_across_compactions(device):
    """>= 50 epochs with an aggressive ratio: compactions keep firing and
    every epoch's signed output stays bit-exact vs the recompute oracle."""
    q = Q.triangle()
    nv = 12
    edges = _start_edges(nv, 60, 9)
    engine = DeltaBigJoin(q, edges, cfg=CFG, compact_ratio=0.05,
                          device_resident=device)
    rng = np.random.default_rng(10)
    cur = edges.copy()
    compactions_seen = [0]
    for step in range(52):
        upd, w = random_batch(rng, nv, cur, 8)
        res = engine.apply(upd, w)
        after = apply_net(cur, upd, w)
        np.testing.assert_array_equal(engine.edges, after)
        ot, ow = delta_oracle(q, cur, after)
        assert canon(res.tuples, res.weights) == canon(ot, ow), step
        compactions_seen.append(engine.store.stats.compactions)
        cur = after
    assert engine.store.stats.epochs >= 30  # noise batches may no-op
    # compaction fired repeatedly along the stream, not just once at the end
    assert engine.store.stats.compactions >= 3 * len(engine.projections)
    mid = compactions_seen[len(compactions_seen) // 2]
    assert 0 < mid < engine.store.stats.compactions


# ---------------------------------------------------------------------------
# the warm path: no transfers inside the folds, no full-index rebuilds
# ---------------------------------------------------------------------------

def test_warm_commit_no_host_rebuild_or_transfer(monkeypatch):
    q = Q.triangle()
    nv = 14
    edges = _start_edges(nv, 90, 11)
    engine = DeltaBigJoin(q, edges, cfg=CFG)
    rng = np.random.default_rng(12)
    cur = edges.copy()
    for _ in range(3):  # warm-up epochs (compiles the folds + dataflows)
        upd, w = random_batch(rng, nv, cur, 10)
        engine.apply(upd, w)
        cur = apply_net(cur, upd, w)

    # spy every index build: a warm epoch may stage delta-sized uncommitted
    # regions, but must never rebuild a full-graph index
    built_sizes = []
    real_build, real_sharded = csr.build_index, csr.build_sharded_index

    def spy_build(tuples, *a, **k):
        built_sizes.append(np.asarray(tuples).shape[0])
        return real_build(tuples, *a, **k)

    def spy_sharded(tuples, *a, **k):
        built_sizes.append(np.asarray(tuples).shape[0])
        return real_sharded(tuples, *a, **k)

    monkeypatch.setattr(D, "build_index", spy_build)  # delta's direct ref
    monkeypatch.setattr(csr, "build_index", spy_build)
    monkeypatch.setattr(csr, "build_sharded_index", spy_sharded)
    # every jitted store step now runs under transfer_guard("disallow")
    monkeypatch.setattr(D, "STRICT_TRANSFERS", True)

    store = engine.store
    lb_before = store._lb
    bases_before = {p: reg.d_base for p, reg in store.projections.items()}
    pulls_before = store.stats.mirror_pulls
    applied = 0
    while applied < 2:
        upd, w = random_batch(rng, nv, cur, 10)
        res = engine.apply(upd, w)
        cur = apply_net(cur, upd, w)
        if res.per_dq:  # skip net-zero no-ops: we want real commits
            applied += 1

    monkeypatch.setattr(D, "STRICT_TRANSFERS", False)
    # builds during warm epochs are delta-sized staging only
    assert built_sizes, "staging builds expected"
    assert max(built_sizes) <= 64, built_sizes
    # the compacted base was neither rebuilt nor re-uploaded
    assert store._lb is lb_before
    for p, reg in store.projections.items():
        assert reg.d_base is bases_before[p]
    # and the warm loop never materialized a host mirror
    assert store.stats.mirror_pulls == pulls_before
    np.testing.assert_array_equal(engine.edges, cur)  # mirror still exact


def test_commit_fold_jaxpr_is_pure_device_compute():
    """The commit fold lowers to pure device compute: no host callbacks,
    no transfers anywhere in its jaxpr."""
    edges = _start_edges(10, 40, 13)
    store = RegionStore(edges)
    store.ensure("edge", (0,), 1)
    reg = next(iter(store.projections.values()))
    ins = np.array([[50, 51], [52, 53]], np.int32)
    reg.set_uncommitted(ins, ins[:0])
    closed = jax.make_jaxpr(
        lambda ba, ci, cd, ui, ud: D._commit_fold(
            ba, ci, cd, ui, ud, cins_cap=128, cdel_cap=128, sharded=False)
    )(reg.d_base, reg.d_cins, reg.d_cdel, reg.d_uins, reg.d_udel)
    bad = {"pure_callback", "io_callback", "debug_callback", "callback",
           "infeed", "outfeed", "device_put"}
    seen = primitive_names(closed)
    assert not (seen & bad), seen & bad


def test_mirror_pull_accounting():
    """.edges / region rows are the ONLY mirror pulls; apply() itself never
    materializes host state in device mode."""
    q = Q.triangle()
    edges = _start_edges(12, 60, 14)
    engine = DeltaBigJoin(q, edges, cfg=CFG)
    store = engine.store
    rng = np.random.default_rng(15)
    upd, w = random_batch(rng, 12, edges, 10)
    before = store.stats.mirror_pulls
    engine.apply(upd, w)
    assert store.stats.mirror_pulls == before
    _ = engine.edges  # explicit debug pull
    assert store.stats.mirror_pulls == before + 1
    _ = engine.edges  # cached until the next commit
    assert store.stats.mirror_pulls == before + 1


def test_sharded_live_lsm_memory_linearity():
    """The store-level live-edge LSM shards like the projections: every
    packed key owned by exactly one worker, shard sizes summing to |E| —
    no O(|E|) array on a single worker."""
    w = 4
    edges = _start_edges(20, 120, 18)
    store = RegionStore(edges, shard_w=w)
    store.ensure("edge", (0,), 1)
    rng = np.random.default_rng(19)
    cur = edges.copy()
    for _ in range(6):
        upd, wts = random_batch(rng, 20, cur, 10)
        ins, dels = store.normalize(upd, wts)
        if ins.size or dels.size:
            store.begin_epoch(ins, dels)
            store.commit(ins, dels)
        cur = apply_net(cur, upd, wts)
        np.testing.assert_array_equal(store.edges, cur)
        total = 0
        for region in (store._lb, store._lc_ins, store._lc_del):
            ns = np.asarray(region.n)
            assert ns.shape == (w,)
            for k in range(w):
                keys = np.asarray(region.key)[k][:ns[k]]
                assert (csr.shard_of(keys, w) == k).all()
            total += int(ns.sum())
        # base + cins - cdel == |live| (cancellation keeps regions disjoint)
        nb, nci, ncd = (int(np.asarray(n).sum()) for n in store._n_live)
        assert nb + nci - ncd == cur.shape[0]
        assert total == nb + nci + ncd


def test_large_vertex_ids_roundtrip_device_store():
    """Packed keys of edges with src >= 2^30 approach int64-max; the int64
    sentinel must stay strictly above ALL of them (regression: a 2^62
    sentinel silently classified such edges as padding)."""
    big = 1 << 30
    edges = np.array([[big, 5], [big + 7, big + 9], [2, 3]], np.int32)
    dev = RegionStore(edges, device_resident=True)
    leg = RegionStore(edges, device_resident=False)
    upd = np.array([[big, 6], [big, 5], [big + 7, big + 9]], np.int32)
    w = np.array([1, -1, -1], np.int32)
    di, dd = dev.normalize(upd, w)
    li, ld = leg.normalize(upd, w)
    np.testing.assert_array_equal(di, li)
    np.testing.assert_array_equal(dd, ld)
    assert di.shape[0] == 1 and dd.shape[0] == 2  # not silently dropped
    for store in (dev, leg):
        store.begin_epoch(di, dd)
        store.commit(di, dd)
    np.testing.assert_array_equal(dev.edges, leg.edges)
    assert (dev.edges == np.array([[2, 3], [big, 6]], np.int32)).all()


def test_legacy_commit_tolerates_absent_deletes():
    """Raw commit() with a delete of an absent edge must not positionally
    remove a different live edge (regression: np.delete on unverified
    searchsorted positions)."""
    edges = np.array([[2, 3], [5, 6]], np.int32)
    for device in (True, False):
        store = RegionStore(edges, device_resident=device)
        absent = np.array([[2, 4]], np.int32)
        store.begin_epoch(absent[:0], absent)
        store.commit(absent[:0], absent)
        np.testing.assert_array_equal(store.edges, edges)


def test_raw_commit_without_begin_epoch_stays_consistent():
    """commit() without a prior begin_epoch must self-stage, so projections
    and the live LSM fold the same batch in both store modes."""
    edges = _start_edges(12, 50, 21)
    ins = np.array([[200, 201]], np.int32)
    dels = edges[:1].copy()
    for device in (True, False):
        store = RegionStore(edges, device_resident=device)
        store.ensure("edge", (0,), 1)
        store.commit(ins, dels)  # raw: no begin_epoch
        want = np.unique(np.concatenate(
            [edges[1:], ins]), axis=0)
        np.testing.assert_array_equal(store.edges, want)
        reg = next(iter(store.projections.values()))
        committed = (reg.base.shape[0] + reg.cins.shape[0]
                     - reg.cdel.shape[0])
        assert committed == want.shape[0]  # projections saw the same batch
        # a raw "insert" of an already-live edge must net out, not
        # duplicate rows (legacy) or poison cins ∩ base = ∅ (device)
        store.commit(want[:1].copy(), want[:0])
        np.testing.assert_array_equal(store.edges, want)
        store._maybe_compact(force=True)  # invariant audit must hold
        np.testing.assert_array_equal(store.edges, want)


def test_projection_ensured_mid_epoch_sees_staged_batch():
    """ensure() between begin_epoch and commit must stage the open batch on
    the new projection, or the commit fold would lose the epoch's delta
    (the legacy path folds the args and was already correct)."""
    edges = _start_edges(12, 50, 20)
    ins = np.array([[100, 101], [102, 103]], np.int32)
    dels = edges[:2].copy()
    for device in (True, False):
        store = RegionStore(edges, device_resident=device)
        store.ensure("edge", (0,), 1)
        i, d = store.normalize(
            np.concatenate([ins, dels]),
            np.concatenate([np.ones(2, np.int32), -np.ones(2, np.int32)]))
        store.begin_epoch(i, d)
        late = store.ensure("edge", (1,), 0)  # mid-epoch registration
        # the staged batch is visible through the "new" version already
        assert int(np.asarray(late.d_uins.n).sum()) == ins.shape[0]
        store.commit(i, d)
        want = apply_net(edges, np.concatenate([ins, dels]),
                         np.concatenate([np.ones(2, np.int32),
                                         -np.ones(2, np.int32)]))
        np.testing.assert_array_equal(store.edges, want)
        # the late projection's committed regions caught the delta
        assert sorted(map(tuple, late.cins.tolist())) == \
            sorted(map(tuple, ins.tolist()))
        assert sorted(map(tuple, late.cdel.tolist())) == \
            sorted(map(tuple, dels.tolist()))


def test_legacy_normalize_uses_packed_cache(monkeypatch):
    """Satellite: the host fallback probes the incrementally-maintained
    sorted packed cache — _pack2 is never re-run over the full edge set."""
    edges = _start_edges(40, 500, 16)
    store = RegionStore(edges, device_resident=False)
    store.ensure("edge", (0,), 1)
    sizes = []
    real = D._pack2

    def spy(a, b):
        sizes.append(np.asarray(a).shape[0])
        return real(a, b)

    rng = np.random.default_rng(17)
    cur = store.edges.copy()
    for _ in range(4):
        upd, w = random_batch(rng, 40, cur, 12)
        monkeypatch.setattr(D, "_pack2", spy)  # spy normalize only: the
        ins, dels = store.normalize(upd, w)    # legacy COMMIT still probes
        monkeypatch.setattr(D, "_pack2", real)  # base (that's why the
        if ins.size or dels.size:               # device store exists)
            store.begin_epoch(ins, dels)
            store.commit(ins, dels)
        cur = apply_net(cur, upd, w)
    assert sizes and max(sizes) <= 40  # batch-sized packs only
    # the cache tracks the live set exactly
    np.testing.assert_array_equal(
        store._packed_live,
        np.sort(real(store.edges[:, 0], store.edges[:, 1])))
    np.testing.assert_array_equal(store.edges, cur)


@pytest.mark.parametrize("cols,lo,hi", [(2, 0, 60), (2, -2**31, 2**31 - 1),
                                        (3, 0, 9), (4, -3, 3)])
def test_unique_rows_matches_np_unique(cols, lo, hi):
    rng = np.random.default_rng(cols * 7 + hi % 97)
    rows = rng.integers(lo, hi, (5000, cols), endpoint=True).astype(np.int32)
    np.testing.assert_array_equal(csr.unique_rows(rows),
                                  np.unique(rows, axis=0))
    wide = rows.astype(np.int64) << 20
    np.testing.assert_array_equal(csr.unique_rows(wide),
                                  np.unique(wide, axis=0))
