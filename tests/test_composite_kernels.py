"""Composite-key kernels through the full n-ary engine (ISSUE 10).

Three contracts:

- **differential**: a RegionStore streaming mixed-sign deltas over a
  narrow-composite (tri, int32 hi), a wide-composite (quad, int64 pair) and
  a single-word (edge) relation IN ONE STORE commits bit-exactly with the
  fused Pallas fold vs the jnp chain, local and hash-sharded w ∈ {2, 4},
  and matches the numpy set-semantics recompute oracle every epoch;
- **structure**: each relation's commit fold lowers to exactly ONE
  ``pallas_call`` and zero host round-trips (no callbacks / device_put) —
  the fused-fold launch budget of DESIGN.md §10;
- **transfer guard**: a warm composite engine epoch (quad-e plan) runs
  under ``jax.transfer_guard("disallow")`` on the fused kernel path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import csr
from repro.core import delta as D
from repro.core.delta import DeltaBigJoin, RegionStore
from repro.kernels import count_pallas_calls

from tests.test_delta import canon
from tests.test_nary_store import (CFG, QUAD_E, _kvset, _rand_rel,
                                   apply_net_nary, random_batch_nary)
from repro.core.delta import delta_oracle


def _mixed_store(rng, nv, shard_w):
    rels = {"tri": np.unique(_rand_rel(rng, nv, 80, 3), axis=0),
            "quad": np.unique(_rand_rel(rng, nv, 60, 4), axis=0),
            "edge": np.unique(_rand_rel(rng, nv, 40, 2), axis=0)}
    store = RegionStore({k: v.copy() for k, v in rels.items()},
                        shard_w=shard_w, compact_ratio=0.4)
    store.ensure("tri", (0, 1), 2)
    store.ensure("quad", (0, 1, 2), 3)
    store.ensure("edge", (0,), 1)
    return rels, store


def _region_triples(store):
    for name, r in store._rels.items():
        yield f"live:{name}", ((r.lb, "base"), (r.lc_ins, "cins"),
                               (r.lc_del, "cdel"))
    for proj, r in store.projections.items():
        if not r.derived:
            yield f"proj:{proj}", ((r.d_base, "base"),
                                   (r.d_cins, "cins"), (r.d_cdel, "cdel"))


def _assert_regions_equal(sa, sb, msg):
    """LIVE-set LSM and projection regions of two stores are bitwise
    identical."""
    for (name, ta), (_, tb) in zip(_region_triples(sa),
                                   _region_triples(sb)):
        for (reg_a, tag), (reg_b, _) in zip(ta, tb):
            assert reg_a.key.dtype == reg_b.key.dtype, (msg, name, tag)
            np.testing.assert_array_equal(
                np.asarray(reg_a.key), np.asarray(reg_b.key),
                err_msg=f"{msg} {name} {tag} key")
            np.testing.assert_array_equal(
                np.asarray(reg_a.val), np.asarray(reg_b.val),
                err_msg=f"{msg} {name} {tag} val")
            np.testing.assert_array_equal(
                np.asarray(reg_a.n), np.asarray(reg_b.n),
                err_msg=f"{msg} {name} {tag} n")
            if reg_a.lo is not None:
                np.testing.assert_array_equal(
                    np.asarray(reg_a.lo), np.asarray(reg_b.lo),
                    err_msg=f"{msg} {name} {tag} lo")


@pytest.mark.parametrize("shard_w", [0, 2, 4], ids=["local", "w2", "w4"])
def test_mixed_narrow_wide_store_kernel_vs_jnp_differential(
        monkeypatch, shard_w):
    """One store, three key layouts (int32-hi composite, int64-pair
    composite, int64 single word): identical mixed-sign streams through the
    fused kernel fold and the jnp chain stay bitwise identical AND match
    the numpy recompute oracle."""
    rng = np.random.default_rng(60 + shard_w)
    nv = 10
    rels, store_k = _mixed_store(np.random.default_rng(77), nv, shard_w)
    _, store_j = _mixed_store(np.random.default_rng(77), nv, shard_w)
    cur = {k: v.copy() for k, v in rels.items()}
    for step in range(8):
        batch = {}
        for name, arity in (("tri", 3), ("quad", 4), ("edge", 2)):
            upd, w = random_batch_nary(rng, nv, cur[name], 8, arity=arity)
            batch[name] = (upd, w)
        for store, on in ((store_k, True), (store_j, False)):
            monkeypatch.setattr(D, "USE_MERGE_KERNEL", on)
            out = store.normalize({k: (u.copy(), w.copy())
                                   for k, (u, w) in batch.items()})
            if any(a.size or b.size for a, b in out.values()):
                store.begin_epoch(out)
                store.commit(out)
        monkeypatch.setattr(D, "USE_MERGE_KERNEL", None)
        for name in cur:
            cur[name] = apply_net_nary(cur[name], *batch[name])
            np.testing.assert_array_equal(
                store_k.relation_rows(name), cur[name],
                err_msg=f"epoch {step} {name} (kernel vs oracle)")
        _assert_regions_equal(store_k, store_j, f"epoch {step}")
    # the narrow lift actually happened where it should: the quad
    # projection binds 3 columns -> int32 hi word; the tri projection
    # binds 2 -> one packed int64 word; the live-set LSMs stay wide by
    # design (_packed_index pins narrow=False — delta batches may carry
    # ids the initial build never saw)
    quad_proj = next(r for r in store_k.projections.values()
                     if r.rel == "quad" and not r.derived)
    assert quad_proj.narrow and quad_proj.d_base.lo is not None
    assert quad_proj.d_base.key.dtype == jnp.int32
    tri_proj = next(r for r in store_k.projections.values()
                    if r.rel == "tri" and not r.derived)
    assert tri_proj.d_base.key.dtype == jnp.int64
    assert store_k._rels["tri"].lb.lo is not None  # composite live LSM


BAD_PRIMS = {"pure_callback", "io_callback", "debug_callback", "callback",
             "infeed", "outfeed", "device_put"}


def _prims_of(closed):
    from repro.compat import primitive_names
    return primitive_names(closed)


@pytest.mark.parametrize("shard_w", [0, 4], ids=["local", "w4"])
@pytest.mark.parametrize("arity", [2, 3, 4])
def test_commit_fold_one_launch_per_relation_no_host(arity, shard_w):
    """The per-relation commit fold with the kernel on: exactly one fused
    pallas_call, zero host round-trips — local and under the sharded vmap."""
    rng = np.random.default_rng(70 + arity)
    rows = np.unique(_rand_rel(rng, 12, 90, arity), axis=0)
    delta = np.unique(_rand_rel(rng, 12, 20, arity), axis=0)
    ba = D._packed_index(rows, shard_w, arity, capacity=256)
    ci = D._packed_index(delta[:10], shard_w, arity, capacity=128)
    cd = D._packed_index(delta[10:15], shard_w, arity, capacity=128)
    ui = D._packed_index(delta[15:], shard_w, arity, capacity=64)
    ud = D._packed_index(rows[:8], shard_w, arity, capacity=64)
    fold = lambda *r: D._commit_fold_impl(
        *r, cins_cap=256, cdel_cap=256, sharded=bool(shard_w),
        use_kernel=True)
    assert count_pallas_calls(fold, ba, ci, cd, ui, ud) == 1
    prims = _prims_of(jax.make_jaxpr(fold)(ba, ci, cd, ui, ud))
    assert not (prims & BAD_PRIMS), prims & BAD_PRIMS
    assert "pallas_call" in prims


def test_warm_composite_engine_epoch_under_transfer_guard(monkeypatch):
    """quad-e (arity-4 composite + edge) engine, merge kernel on: after
    warmup, epochs run under transfer_guard('disallow') — the fused fold
    and composite probe kernels never bounce through the host."""
    monkeypatch.setattr(D, "USE_MERGE_KERNEL", True)
    rng = np.random.default_rng(80)
    nv = 7
    quad0 = np.unique(_rand_rel(rng, nv, 100, 4), axis=0)
    edge0 = np.unique(_rand_rel(rng, nv, 30, 2), axis=0)
    eng = DeltaBigJoin(QUAD_E, {"quad": quad0, "edge": edge0}, cfg=CFG)
    cur = {"quad": quad0, "edge": edge0}

    def epoch():
        qu, qw = random_batch_nary(rng, nv, cur["quad"], 8, arity=4)
        eu, ew = random_batch_nary(rng, nv, cur["edge"], 6, arity=2)
        res = eng.apply({"quad": (qu, qw), "edge": (eu, ew)})
        after = {"quad": apply_net_nary(cur["quad"], qu, qw),
                 "edge": apply_net_nary(cur["edge"], eu, ew)}
        ot, ow = delta_oracle(QUAD_E, cur, after)
        assert canon(res.tuples, res.weights) == canon(ot, ow)
        return after

    for _ in range(3):  # warm up compiles
        cur = epoch()
    monkeypatch.setattr(D, "STRICT_TRANSFERS", True)
    try:
        for _ in range(2):
            cur = epoch()
    finally:
        monkeypatch.setattr(D, "STRICT_TRANSFERS", False)
    np.testing.assert_array_equal(eng.store.relation_rows("quad"),
                                  cur["quad"])
