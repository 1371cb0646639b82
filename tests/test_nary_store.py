"""Multi-relation, n-ary engine lockdown (ISSUE 5).

Five contracts:

- **composite keys**: 3/4-column keys pack into the (hi, lo) int64 pair,
  probe via the fixed-depth two-word lex search, and flow through the
  sorted-merge folds, the sharded builds (ownership by combined word), and
  the host oracle — bit-exact against python set semantics;
- **one packer**: ``csr.pack_key`` is the only packing implementation —
  ``bigjoin._pack_cols`` and ``generic_join._NpIndex`` delegate, and no
  ``NotImplementedError`` remains on >2-key-column or non-edge paths;
- **validation**: wrong-arity / negative-id / non-integer batches raise
  loudly instead of being reshaped into garbage;
- **n-ary store**: adversarial ``tri``-relation streams (dups, degenerate
  rows, net-zero batches, reinserts after committed deletes) match a numpy
  set-semantics oracle, local AND hash-sharded w ∈ {2, 4}, device AND
  legacy modes, with the warm-path build/transfer spies of
  test_region_store.py carried over;
- **§5.4 end-to-end**: 4-clique-tri over a streamed tri relation is
  bit-exact against the edge-only 4-clique — statically, incrementally,
  and distributed (in-process mesh + subprocess w ∈ {2, 4}).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compat import primitive_names
from repro.core import csr
from repro.core import delta as D
from repro.core import query as Q
from repro.core.bigjoin import (BigJoinConfig, build_indices, run_bigjoin,
                                seed_tuples_for, _pack_cols)
from repro.core.delta import (DeltaBigJoin, RegionStore, delta_oracle,
                              rows_isin)
from repro.core.generic_join import generic_join
from repro.core.plan import make_delta_plan, make_plan
from repro.core.query import delta_queries

from tests.test_delta import canon
from tests.test_delta_stream import _device_count, _mesh, apply_net

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CFG = BigJoinConfig(batch=128, seed_chunk=128, out_capacity=1 << 15)

QUAD_E = Q.Query("quad-e", 4, (Q.Atom("quad", (0, 1, 2, 3)),
                               Q.Atom("edge", (2, 3))))


def _rand_rel(rng, nv, n, arity):
    return rng.integers(0, nv, (n, arity)).astype(np.int32)


def _kvset(idx):
    """Live (key[, lo], val) entries of an IndexData as a python set."""
    ns = np.asarray(idx.n)
    if ns.ndim:  # sharded: flatten live prefixes
        parts = []
        for k in range(ns.shape[0]):
            cols = [np.asarray(idx.key)[k][:ns[k]]]
            if idx.lo is not None:
                cols.append(np.asarray(idx.lo)[k][:ns[k]])
            cols.append(np.asarray(idx.val)[k][:ns[k]])
            parts.append(set(zip(*[c.tolist() for c in cols])))
        return set().union(*parts) if parts else set()
    n = int(ns)
    cols = [np.asarray(idx.key)[:n]]
    if idx.lo is not None:
        cols.append(np.asarray(idx.lo)[:n])
    cols.append(np.asarray(idx.val)[:n])
    return set(zip(*[c.tolist() for c in cols]))


def _pack_set(rows, nk):
    """Expected (hi[, lo], val) set of [N, nk+1] tuples."""
    rows = np.unique(np.asarray(rows, np.int32), axis=0)
    key = csr.pack_key(tuple(rows[:, i] for i in range(nk)))
    val = rows[:, nk]
    if isinstance(key, tuple):
        return set(zip(key[0].tolist(), key[1].tolist(), val.tolist()))
    return set(zip(key.astype(np.int64).tolist(), val.tolist()))


# ---------------------------------------------------------------------------
# composite (hi, lo) keys through csr
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nk", [3, 4])
def test_composite_build_member_range_match_sets(nk):
    rng = np.random.default_rng(0)
    t = _rand_rel(rng, 15, 300, nk + 1)
    idx = csr.build_index(t, tuple(range(nk)), nk)
    assert idx.composite and idx.lo is not None
    # single-column hi words (nk=3) narrow to int32; packed pairs stay int64
    assert idx.key.dtype == (jnp.int32 if csr.single_word_hi(nk)
                             else jnp.int64)
    # membership: random probes + every live tuple
    probes = np.concatenate([_rand_rel(rng, 17, 200, nk + 1), t[:50]])
    qk = csr.pack_key(tuple(probes[:, i] for i in range(nk)))
    got = np.asarray(csr.index_member(
        idx, (jnp.asarray(qk[0]), jnp.asarray(qk[1])),
        jnp.asarray(probes[:, nk])))
    live = set(map(tuple, t.tolist()))
    want = np.array([tuple(r) in live for r in probes.tolist()])
    np.testing.assert_array_equal(got, want)
    # ranges: distinct-extension counts per composite prefix
    from collections import Counter
    cnt = Counter(tuple(r[:nk]) for r in set(map(tuple, t.tolist())))
    _, c = csr.index_range(idx, (jnp.asarray(qk[0]), jnp.asarray(qk[1])))
    np.testing.assert_array_equal(
        np.asarray(c), [cnt.get(tuple(r[:nk]), 0) for r in probes.tolist()])
    # lex-sorted by (key, lo, val), sentinel padding after n
    n = int(idx.n)
    k = np.asarray(idx.key)[:n]
    lo = np.asarray(idx.lo)[:n]
    v = np.asarray(idx.val)[:n].astype(np.int64)
    trip = np.stack([k, lo, v], 1)
    assert (np.diff([tuple(r) for r in trip.tolist()], axis=0) != 0).any(1) \
        .all() if n > 1 else True
    hi_sent = csr.SENTINEL32 if idx.key.dtype == jnp.int32 else csr.SENTINEL
    assert (np.asarray(idx.key)[n:] == hi_sent).all()
    assert (np.asarray(idx.lo)[n:] == csr.SENTINEL).all()
    # pack/unpack roundtrip
    np.testing.assert_array_equal(csr.unpack_key(qk, nk), probes[:, :nk])


@pytest.mark.parametrize("nk", [3, 4])
def test_composite_fold_primitives_match_set_ops(nk):
    rng = np.random.default_rng(1)
    for trial in range(10):
        ta = _rand_rel(rng, 9, int(rng.integers(0, 80)), nk + 1)
        tb = _rand_rel(rng, 9, int(rng.integers(0, 50)), nk + 1)
        a = csr.build_index(ta, tuple(range(nk)), nk)
        b = csr.build_index(tb, tuple(range(nk)), nk)
        A, B = _pack_set(ta, nk), _pack_set(tb, nk)
        m = csr.merge_index(a, b, 1024)
        d = csr.diff_index(a, b, int(a.capacity))
        x = csr.intersect_index(a, b, int(a.capacity))
        assert _kvset(m) == A | B, trial
        assert _kvset(d) == A - B, trial
        assert _kvset(x) == A & B, trial


def test_composite_sharded_ownership_and_linearity():
    rng = np.random.default_rng(2)
    t = _rand_rel(rng, 12, 400, 4)
    w = 4
    sh = csr.build_sharded_index(t, (0, 1, 2), 3, w)
    local = csr.build_index(t, (0, 1, 2), 3)
    ns = np.asarray(sh.n)
    assert int(ns.sum()) == int(local.n)  # memory linearity
    assert _kvset(sh) == _kvset(local)  # exactly-once, nothing dropped
    for k in range(w):
        keys = np.asarray(sh.key)[k][:ns[k]]
        los = np.asarray(sh.lo)[k][:ns[k]]
        np.testing.assert_array_equal(csr.shard_of((keys, los), w),
                                      np.full(int(ns[k]), k, np.int32))
    # vmapped folds stay shard-local and match the unsharded union
    t2 = _rand_rel(rng, 12, 60, 4)
    sb = csr.build_sharded_index(t2, (0, 1, 2), 3, w, capacity=1)
    vm = jax.jit(jax.vmap(lambda x, y: csr.merge_index(x, y, 1024)))(sh, sb)
    assert _kvset(vm) == _pack_set(t, 3) | _pack_set(t2, 3)


def test_one_shared_packer_no_notimplemented():
    """bigjoin._pack_cols and the host _NpIndex delegate to csr.pack_key;
    3-4 column keys return the (hi, lo) pair instead of raising."""
    rng = np.random.default_rng(3)
    prefix = jnp.asarray(_rand_rel(rng, 50, 40, 4))
    pk = _pack_cols(prefix, [0, 1, 2], jnp.int64)
    assert isinstance(pk, tuple) and len(pk) == 2
    ref = csr.pack_key(tuple(np.asarray(prefix)[:, i] for i in range(3)))
    np.testing.assert_array_equal(np.asarray(pk[0]), ref[0])
    np.testing.assert_array_equal(np.asarray(pk[1]), ref[1])
    from repro.core.generic_join import _NpIndex
    t = _rand_rel(rng, 10, 120, 4)
    npi = _NpIndex(t, (0, 1, 2), 3)
    assert npi.lo is not None
    qs = np.concatenate([t[:30], _rand_rel(rng, 12, 50, 4)])
    qk = csr.pack_key(tuple(qs[:, i] for i in range(3)))
    live = set(map(tuple, t.tolist()))
    want = np.array([tuple(r) in live for r in qs.tolist()])
    np.testing.assert_array_equal(npi.member(qk, qs[:, 3]), want)
    with pytest.raises(ValueError, match="at most 4"):
        csr.pack_key(tuple(np.zeros(2, np.int32) for _ in range(5)))


# ---------------------------------------------------------------------------
# input validation (the old silent reshape(-1, 2) mangling)
# ---------------------------------------------------------------------------

def test_store_rejects_bad_batches():
    store = RegionStore(np.array([[0, 1], [1, 2]], np.int32))
    with pytest.raises(ValueError, match="arity 2"):
        store.normalize(np.zeros((3, 3), np.int32), np.ones(3, np.int32))
    with pytest.raises(ValueError, match="negative id"):
        store.normalize(np.array([[1, -4]], np.int32),
                        np.ones(1, np.int32))
    with pytest.raises(TypeError, match="integer"):
        store.normalize(np.array([[1.5, 2.0]]), np.ones(1, np.int32))
    with pytest.raises(ValueError, match="weights"):
        store.normalize(np.array([[1, 2]], np.int32),
                        np.ones(3, np.int32))
    with pytest.raises(ValueError, match="int32"):
        store.normalize(np.array([[1, 2 ** 31]], np.int64),
                        np.ones(1, np.int32))
    with pytest.raises(KeyError, match="unknown relation"):
        store.normalize({"tri": (np.zeros((1, 3), np.int32),
                                 np.ones(1, np.int32))})


def test_session_update_rejects_bad_batches():
    from repro.api import GraphSession
    sess = GraphSession(np.array([[0, 1], [1, 2]], np.int32), local=True)
    with pytest.raises(ValueError, match="arity 2"):
        sess.update(np.zeros((2, 3), np.int32))
    with pytest.raises(ValueError, match="negative id"):
        sess.update(np.array([[-1, 2]], np.int32))
    with pytest.raises(TypeError, match="integer"):
        sess.update(np.array([[0.5, 1.0]]))


def test_add_relation_validation():
    store = RegionStore(np.array([[0, 1]], np.int32))
    with pytest.raises(ValueError, match="already exists"):
        store.add_relation("edge", np.zeros((0, 2), np.int32))
    with pytest.raises(ValueError, match="arity"):
        store.add_relation("penta", np.zeros((2, 5), np.int32))
    store.add_relation("tri", np.zeros((0, 3), np.int32), arity=3)
    assert store.arity_of("tri") == 3
    # a still-empty declaration may be re-seeded (register-before-
    # materialize, the serve --stream flow) — once, and arity-checked
    with pytest.raises(ValueError, match="arity 3"):
        store.add_relation("tri", np.zeros((2, 4), np.int32))
    store.add_relation("tri", np.array([[1, 2, 3]], np.int32))
    assert store.num_tuples("tri") == 1
    with pytest.raises(ValueError, match="already exists"):
        store.add_relation("tri", np.array([[4, 5, 6]], np.int32))
    # explicit arity contradicting the rows' width must not regroup rows
    with pytest.raises(ValueError, match="arity=4"):
        store.add_relation("quad", np.zeros((4, 3), np.int32), arity=4)
    with pytest.raises(ValueError, match="2..4"):
        store.add_relation("lbl", np.array([[3], [5]], np.int32))


def test_empty_batches_are_noops_not_dtype_errors():
    store = RegionStore(np.array([[0, 1], [1, 2]], np.int32))
    ins, dels = store.normalize([], None)  # plain empty list: float64 array
    assert ins.size == 0 and dels.size == 0
    ins, dels = store.normalize(np.zeros((0, 2)), None)  # float empty
    assert ins.size == 0 and dels.size == 0


def test_dict_batch_rejects_top_level_weights_and_float_weights():
    store = RegionStore({"edge": np.array([[0, 1]], np.int32),
                         "tri": np.array([[1, 2, 3]], np.int32)})
    rows = np.array([[1, 2, 3]], np.int32)
    with pytest.raises(ValueError, match="their own weights"):
        store.normalize({"tri": rows}, -np.ones(1, np.int32))
    with pytest.raises(TypeError, match="integer"):
        store.normalize({"tri": (rows, -np.ones(1))})  # float weights
    # and the dict entry's weights are actually honored
    out = store.normalize({"tri": (rows, -np.ones(1, np.int32))})
    assert out["tri"][1].shape[0] == 1  # a real delete, not a +1 no-op


def test_register_then_seed_relation_flow():
    """register() auto-declares 'tri' empty; add_relation may then seed it
    (the serve --stream ordering), and projections ensured against the
    empty declaration are rebuilt from the seeded rows."""
    from repro.api import GraphSession
    e = np.array([[0, 1], [1, 2], [0, 2], [0, 3], [1, 3], [2, 3]],
                 np.int32)
    sess = GraphSession(e, local=True, batch=128, out_capacity=1 << 14)
    c4t = sess.register("4-clique-tri")
    assert c4t.count() == 0  # tri auto-declared empty
    tris, _ = sess.register("triangle").enumerate()
    sess.add_relation("tri", tris)  # re-seed the empty declaration
    assert c4t.count() == sess.register("4-clique").count() == 1


# ---------------------------------------------------------------------------
# n-ary store: adversarial stream differential vs numpy set semantics
# ---------------------------------------------------------------------------

def apply_net_nary(live, upd, w):
    """Reference semantics: degenerate rows dropped, per-tuple net weight,
    net>0 inserts if absent, net<0 deletes if present."""
    upd = np.asarray(upd, np.int32)
    w = np.asarray(w, np.int64)
    keep = ~D._degenerate_rows(upd)
    upd, w = upd[keep], w[keep]
    uniq, inv = np.unique(upd, axis=0, return_inverse=True)
    net = np.zeros(uniq.shape[0], np.int64)
    np.add.at(net, inv.reshape(-1), w)
    exists = rows_isin(uniq, live) if live.size else \
        np.zeros(uniq.shape[0], bool)
    add = uniq[(net > 0) & ~exists]
    rem = uniq[(net < 0) & exists]
    kept = live[~rows_isin(live, rem)] if rem.size else live
    out = np.concatenate([kept, add]) if add.size else kept
    return np.unique(out, axis=0) if out.size else out.reshape(0,
                                                               upd.shape[1])


def random_batch_nary(rng, nv, live, size, arity=3):
    """Dirty n-ary batches: dups, degenerate rows, live-tuple inserts,
    absent deletes, contradictory duplicates, occasional exact-no-op."""
    flavor = rng.integers(0, 5)
    if flavor == 0 and live.shape[0]:  # nets to an exact no-op
        rows = live[rng.integers(0, live.shape[0], max(size // 2, 1))]
        dup = np.concatenate([rows, rows])
        w = np.concatenate([np.ones(rows.shape[0], np.int32),
                            -np.ones(rows.shape[0], np.int32)])
        dg = np.tile(np.arange(2, dtype=np.int32)[:, None], (1, arity))
        return (np.concatenate([dup, dg]),
                np.concatenate([w, np.ones(2, np.int32)]))
    n_ins = int(rng.integers(0, size + 1))
    n_del = int(rng.integers(0, size // 2 + 1))
    ins = _rand_rel(rng, nv, n_ins, arity)
    parts, wparts = [ins], [np.ones(n_ins, np.int32)]
    if n_del:
        n_live = min(n_del, live.shape[0])
        if n_live:
            parts.append(live[rng.choice(live.shape[0], n_live,
                                         replace=False)])
            wparts.append(-np.ones(n_live, np.int32))
        parts.append(_rand_rel(rng, nv, n_del - n_live + 1, arity))
        wparts.append(-np.ones(n_del - n_live + 1, np.int32))
    if flavor == 2 and n_ins:  # weight piles on duplicate rows
        k = rng.integers(0, n_ins)
        parts.append(ins[k:k + 1].repeat(3, 0))
        wparts.append(np.ones(3, np.int32))
    return np.concatenate(parts), np.concatenate(wparts)


@pytest.mark.parametrize("shard_w", [0, 2, 4], ids=["local", "w2", "w4"])
@pytest.mark.parametrize("device", [True, False], ids=["device", "legacy"])
def test_nary_store_stream_differential(device, shard_w):
    if shard_w and not device:
        pytest.skip("legacy host store has no sharded mode")
    rng = np.random.default_rng(10 + shard_w)
    nv = 12
    tri0 = np.unique(_rand_rel(rng, nv, 90, 3), axis=0)
    store = RegionStore({"tri": tri0}, shard_w=shard_w,
                        compact_ratio=0.3, device_resident=device)
    store.ensure("tri", (0, 1), 2)
    store.ensure("tri", (0, 2), 1)
    cur = tri0.copy()
    for step in range(20):
        upd, w = random_batch_nary(rng, nv, cur, 10)
        out = store.normalize({"tri": (upd, w)})
        ins, dels = out["tri"]
        ref_after = apply_net_nary(cur, upd, w)
        if ins.size or dels.size:
            store.begin_epoch(out)
            store.commit(out)
        np.testing.assert_array_equal(store.relation_rows("tri"),
                                      ref_after, err_msg=f"epoch {step}")
        # normalize's own contract: ins ∉ live, dels ⊆ live
        assert not rows_isin(ins, cur).any()
        assert rows_isin(dels, cur).all()
        # bijective projections track the relation exactly
        for reg in store.projections.values():
            rows = np.unique(np.concatenate(
                [D._diff_rows(reg.base, reg.cdel), reg.cins]), axis=0) \
                if (reg.cins.size or reg.cdel.size) else reg.base
            np.testing.assert_array_equal(rows, ref_after)
        cur = ref_after
    if device:
        assert store.stats.live_compactions + store.stats.compactions > 0


from tests.test_delta_stream import given, settings, st  # noqa: E402


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=10, deadline=None)
def test_nary_store_stream_differential_hypothesis(seed):
    """Hypothesis-driven variant: random seeds, random compaction ratios,
    same numpy set-semantics oracle (auto-skips without hypothesis)."""
    rng = np.random.default_rng(seed)
    nv = int(rng.integers(5, 14))
    tri0 = np.unique(_rand_rel(rng, nv, int(rng.integers(10, 80)), 3),
                     axis=0)
    store = RegionStore({"tri": tri0},
                        compact_ratio=float(rng.choice([0.01, 0.5, 50.0])))
    store.ensure("tri", (0, 1), 2)
    cur = tri0.copy()
    for _ in range(4):
        upd, w = random_batch_nary(rng, nv, cur, 8)
        out = store.normalize({"tri": (upd, w)})
        if any(a.size or b.size for a, b in out.values()):
            store.begin_epoch(out)
            store.commit(out)
        cur = apply_net_nary(cur, upd, w)
        np.testing.assert_array_equal(store.relation_rows("tri"), cur)


def test_nary_sharded_memory_linearity_and_ownership():
    rng = np.random.default_rng(20)
    w, nv = 4, 14
    tri0 = np.unique(_rand_rel(rng, nv, 140, 3), axis=0)
    store = RegionStore({"tri": tri0}, shard_w=w)
    store.ensure("tri", (0, 1), 2)
    cur = tri0.copy()
    for _ in range(6):
        upd, wts = random_batch_nary(rng, nv, cur, 12)
        out = store.normalize({"tri": (upd, wts)})
        if any(a.size or b.size for a, b in out.values()):
            store.begin_epoch(out)
            store.commit(out)
        cur = apply_net_nary(cur, upd, wts)
        st = store._rels["tri"]
        total = 0
        for region in (st.lb, st.lc_ins, st.lc_del):
            ns = np.asarray(region.n)
            assert ns.shape == (w,)
            for k in range(w):
                keys = np.asarray(region.key)[k][:ns[k]]
                los = np.asarray(region.lo)[k][:ns[k]]
                assert (csr.shard_of((keys, los), w) == k).all()
            total += int(ns.sum())
        nb, nci, ncd = (int(np.asarray(n).sum()) for n in st.n_live)
        assert nb + nci - ncd == cur.shape[0]
        assert total == nb + nci + ncd
        np.testing.assert_array_equal(store.relation_rows("tri"), cur)


def test_reinsert_after_committed_delete_tri():
    rng = np.random.default_rng(21)
    tri0 = np.unique(_rand_rel(rng, 10, 70, 3), axis=0)
    q = Q.four_clique_tri()
    eng = DeltaBigJoin(q, {"tri": tri0}, cfg=CFG,
                       compact_ratio=1e9)  # ratio can never fire
    victim = tri0[:6]
    cur = tri0.copy()
    for wsign in (-1, 1, -1):
        wv = wsign * np.ones(victim.shape[0], np.int32)
        res = eng.apply({"tri": (victim, wv)})
        after = apply_net_nary(cur, victim, wv)
        ot, ow = delta_oracle(q, {"tri": cur}, {"tri": after})
        assert canon(res.tuples, res.weights) == canon(ot, ow)
        cur = after
    # the re-insertion forced an eager compaction (overlap prevention)
    assert eng.store.stats.compactions + \
        eng.store.stats.live_compactions > 0


# ---------------------------------------------------------------------------
# warm-path spies: delta-sized staging only, pure-device folds
# ---------------------------------------------------------------------------

def test_nary_warm_commit_no_host_rebuild_or_transfer(monkeypatch):
    rng = np.random.default_rng(22)
    nv = 12
    tri0 = np.unique(_rand_rel(rng, nv, 120, 3), axis=0)
    q = Q.four_clique_tri()
    eng = DeltaBigJoin(q, {"tri": tri0}, cfg=CFG)
    cur = tri0.copy()
    for _ in range(3):  # warm up compiles
        upd, w = random_batch_nary(rng, nv, cur, 8)
        eng.apply({"tri": (upd, w)})
        cur = apply_net_nary(cur, upd, w)

    built_sizes = []
    real_build, real_sharded = csr.build_index, csr.build_sharded_index

    def spy_build(tuples, *a, **k):
        built_sizes.append(np.asarray(tuples).shape[0])
        return real_build(tuples, *a, **k)

    def spy_sharded(tuples, *a, **k):
        built_sizes.append(np.asarray(tuples).shape[0])
        return real_sharded(tuples, *a, **k)

    monkeypatch.setattr(D, "build_index", spy_build)
    monkeypatch.setattr(csr, "build_index", spy_build)
    monkeypatch.setattr(csr, "build_sharded_index", spy_sharded)
    monkeypatch.setattr(D, "STRICT_TRANSFERS", True)

    store = eng.store
    st = store._rels["tri"]
    lb_before = st.lb
    bases_before = {p: r.d_base for p, r in store.projections.items()
                    if not r.derived}
    pulls_before = store.stats.mirror_pulls
    applied = 0
    while applied < 2:
        upd, w = random_batch_nary(rng, nv, cur, 8)
        res = eng.apply({"tri": (upd, w)})
        cur = apply_net_nary(cur, upd, w)
        if res.per_dq:
            applied += 1
    monkeypatch.setattr(D, "STRICT_TRANSFERS", False)
    assert built_sizes and max(built_sizes) <= 64, built_sizes
    assert st.lb is lb_before  # base LSM merged, never rebuilt
    for p, r in store.projections.items():
        if not r.derived:
            assert r.d_base is bases_before[p]
    assert store.stats.mirror_pulls == pulls_before
    np.testing.assert_array_equal(store.relation_rows("tri"), cur)


def test_composite_commit_fold_jaxpr_is_pure_device_compute():
    """The tri relation's LIVE-set LSM keys on the full (hi, lo) composite
    row; its commit fold must still lower to pure device compute."""
    rng = np.random.default_rng(23)
    tri0 = np.unique(_rand_rel(rng, 10, 50, 3), axis=0)
    store = RegionStore({"tri": tri0})
    st = store._rels["tri"]
    ins = np.array([[20, 21, 22], [23, 24, 25]], np.int32)
    ui = D._packed_index(ins, 0, 3)
    ud = D._packed_index(ins[:0], 0, 3)
    assert st.lb.lo is not None and ui.lo is not None  # composite regions
    closed = jax.make_jaxpr(
        lambda ba, ci, cd, ui, ud: D._commit_fold(
            ba, ci, cd, ui, ud, cins_cap=128, cdel_cap=128, sharded=False)
    )(st.lb, st.lc_ins, st.lc_del, ui, ud)
    bad = {"pure_callback", "io_callback", "debug_callback", "callback",
           "infeed", "outfeed", "device_put"}
    seen = primitive_names(closed)
    assert not (seen & bad), seen & bad


# ---------------------------------------------------------------------------
# 3-col composite keys through the full static + delta stack (quad relation)
# ---------------------------------------------------------------------------

def test_quad_static_parity():
    rng = np.random.default_rng(30)
    quad = np.unique(_rand_rel(rng, 8, 300, 4), axis=0)
    edge = np.unique(_rand_rel(rng, 8, 50, 2), axis=0)
    plan = make_plan(QUAD_E)
    assert any(len(b.key_attrs) == 3
               for lv in plan.levels for b in lv.bindings)
    rels = {"quad": quad, "edge": edge}
    res = run_bigjoin(plan, build_indices(plan, rels),
                      seed_tuples_for(plan, rels), cfg=CFG)
    ref_t, ref_c = generic_join(QUAD_E, rels, plan=plan)
    assert res.count == ref_c
    assert set(map(tuple, res.tuples.tolist())) == \
        set(map(tuple, ref_t.tolist()))


def test_quad_delta_plans_cover_widths():
    """dQ seeded from the 4-ary atom covers every attribute (zero-level
    direct output); dQ seeded from the edge atom walks 3-col-key levels."""
    plans = [make_delta_plan(dq) for dq in delta_queries(QUAD_E)]
    widths = sorted(p.seed_width for p in plans)
    assert widths == [2, 4]
    assert any(len(p.levels) == 0 for p in plans)


def test_quad_stream_differential():
    rng = np.random.default_rng(31)
    nv = 7
    quad0 = np.unique(_rand_rel(rng, nv, 120, 4), axis=0)
    edge0 = np.unique(_rand_rel(rng, nv, 30, 2), axis=0)
    eng = DeltaBigJoin(QUAD_E, {"quad": quad0, "edge": edge0}, cfg=CFG)
    cur = {"quad": quad0, "edge": edge0}
    for step in range(10):
        qu, qw = random_batch_nary(rng, nv, cur["quad"], 8, arity=4)
        eu, ew = random_batch_nary(rng, nv, cur["edge"], 6, arity=2)
        res = eng.apply({"quad": (qu, qw), "edge": (eu, ew)})
        after = {"quad": apply_net_nary(cur["quad"], qu, qw),
                 "edge": apply_net_nary(cur["edge"], eu, ew)}
        ot, ow = delta_oracle(QUAD_E, cur, after)
        assert canon(res.tuples, res.weights) == canon(ot, ow), step
        np.testing.assert_array_equal(eng.store.relation_rows("quad"),
                                      after["quad"])
        np.testing.assert_array_equal(eng.store.relation_rows("edge"),
                                      after["edge"])
        cur = after


def test_single_atom_delta_is_direct_output():
    """A single-atom standing query (monitor the relation itself): the
    delta plan's seed covers every attribute and outputs directly."""
    rng = np.random.default_rng(32)
    tri0 = np.unique(_rand_rel(rng, 9, 40, 3), axis=0)
    ident = Q.Query("tri-id", 3, (Q.Atom("tri", (0, 1, 2)),))
    eng = DeltaBigJoin(ident, {"tri": tri0}, cfg=CFG)
    assert all(len(p.levels) == 0 for p in eng.plans)
    cur = tri0.copy()
    for step in range(6):
        upd, w = random_batch_nary(rng, 9, cur, 8)
        res = eng.apply({"tri": (upd, w)})
        after = apply_net_nary(cur, upd, w)
        ot, ow = delta_oracle(ident, {"tri": cur}, {"tri": after})
        assert canon(res.tuples, res.weights) == canon(ot, ow), step
        cur = after


# ---------------------------------------------------------------------------
# §5.4 end-to-end: 4-clique-tri ≡ 4-clique, local / mesh / subprocess
# ---------------------------------------------------------------------------

def _tri_pipeline(session, rng, nv, epochs, check_every=True):
    """Drive the two-relation session; assert per-epoch bit-exact parity of
    4-clique-tri (tri plan) vs 4-clique (edge plan)."""
    from tests.test_delta_stream import random_batch
    live = session.edges
    for step in range(epochs):
        upd, w = random_batch(rng, nv, live, 12)
        r1 = session.update(upd, w)
        td = r1.deltas["triangle"]
        t_upd = td.tuples if td.tuples is not None else \
            np.zeros((0, 3), np.int32)
        t_w = td.weights if td.weights is not None else \
            np.zeros(0, np.int32)
        r2 = session.update({"tri": (t_upd, t_w)})
        live = r1.advance(live)
        if check_every:
            a, b = r1.deltas["4-clique"], r2.deltas["4-clique-tri"]
            assert canon(b.tuples, b.weights) == \
                canon(a.tuples, a.weights), step


def _fresh_session(edges, **kw):
    from repro.api import GraphSession
    sess = GraphSession(edges, batch=128, out_capacity=1 << 16, **kw)
    tri = sess.register("triangle")
    sess.register("4-clique")
    tri0, _ = tri.enumerate()
    sess.add_relation("tri", tri0)
    sess.register("4-clique-tri")
    return sess


def test_four_clique_tri_session_local_20_epochs():
    from repro.api import oracle_count
    rng = np.random.default_rng(40)
    nv = 16
    e = np.unique(_rand_rel(rng, nv, 110, 2), axis=0)
    e = e[e[:, 0] != e[:, 1]]
    sess = _fresh_session(e, local=True)
    c4, c4t = sess["4-clique"], sess["4-clique-tri"]
    assert c4t.count() == c4.count() == oracle_count("4-clique", e)
    _tri_pipeline(sess, rng, nv, epochs=20)
    assert c4t.net_change == c4.net_change
    ref = oracle_count("4-clique", sess.edges)
    assert c4.net_change == ref - oracle_count("4-clique", e)
    # static re-evaluation off the SAME maintained store (exercises the
    # derived tri projections of the static plan, post-stream)
    assert c4t.count() == c4.count() == ref


@pytest.mark.parametrize("w", [2, 4])
def test_four_clique_tri_session_mesh(w):
    if _device_count() < w:
        pytest.skip(f"needs {w} devices (CI runs with 4 virtual devices)")
    rng = np.random.default_rng(41)
    nv = 14
    e = np.unique(_rand_rel(rng, nv, 90, 2), axis=0)
    e = e[e[:, 0] != e[:, 1]]
    sess = _fresh_session(e, mesh=_mesh(w))
    assert not sess.local and sess.w == w
    _tri_pipeline(sess, rng, nv, epochs=5)
    assert sess["4-clique-tri"].net_change == sess["4-clique"].net_change


def run_check(*args, timeout=1200):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.core._nary_dist_check", *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_subprocess_w2_four_clique_tri_20_batches():
    r = run_check("--workers", "2", "--nv", "20", "--ne", "110",
                  "--batches", "20", "--batch-size", "12")
    assert r["all_exact"] and r["workers"] == 2 and r["batches"] == 20


@pytest.mark.slow
def test_subprocess_w4_four_clique_tri_20_batches():
    r = run_check("--workers", "4", "--nv", "20", "--ne", "110",
                  "--batches", "20", "--batch-size", "12")
    assert r["all_exact"] and r["workers"] == 4


# ---------------------------------------------------------------------------
# derived (non-covering) projections: lossy images stay correct
# ---------------------------------------------------------------------------

def test_derived_projection_survives_shared_support():
    """Two tri tuples sharing an (a1, a3) pair: deleting ONE of them must
    not kill the pair in the derived a1->a3 projection — the classic
    many-to-one trap an incremental set fold would get wrong."""
    tri0 = np.array([[1, 2, 3], [1, 9, 3], [4, 5, 6]], np.int32)
    store = RegionStore({"tri": tri0})
    reg = store.ensure("tri", (0,), 2)  # ignores the middle column
    assert reg.derived
    vi = reg.versioned("old")
    qk = jnp.asarray(np.array([1], np.int64))
    qv = jnp.asarray(np.array([3], np.int32))
    assert bool(np.asarray(vi.member(qk, qv))[0])
    # delete (1, 2, 3); (1, 9, 3) still supports the pair (1 -> 3)
    batch = {"tri": (tri0[:1], -np.ones(1, np.int32))}
    out = store.normalize(batch)
    store.begin_epoch(out)
    new_vi = reg.versioned("new")
    assert bool(np.asarray(new_vi.member(qk, qv))[0])
    store.commit(out)
    vi2 = reg.versioned("old")
    assert bool(np.asarray(vi2.member(qk, qv))[0])
    # deleting the second supporter finally clears the pair
    batch2 = {"tri": (np.array([[1, 9, 3]], np.int32),
                      -np.ones(1, np.int32))}
    out2 = store.normalize(batch2)
    store.begin_epoch(out2)
    store.commit(out2)
    assert not bool(np.asarray(reg.versioned("old").member(qk, qv))[0])
