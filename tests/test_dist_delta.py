"""Region partitioning invariants for the distributed Delta-BiGJoin path.

Three contracts, each from the paper's distributed design (§3.2 / §4.3):

- **ownership**: every (key, val) entry of every multi-version projection is
  stored by exactly ONE worker (cluster memory linearity — sharding splits,
  never replicates);
- **compaction transparency**: ``_maybe_compact`` on sharded regions changes
  the region layout, never the answers;
- **no host round-trips**: the distributed delta step is one compiled
  program whose scanned level loop contains collectives only — a jaxpr
  assertion that no callback/infeed primitive appears anywhere inside it.
"""
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.csr import (build_index, build_sharded_index, index_member,
                            pack_key, shard_of)
from repro.core.dataflow_index import VersionedIndex
from repro.core.delta import DeltaBigJoin, delta_oracle
from repro.core.plan import make_delta_plan, make_plan
from repro.core.query import delta_queries

from tests.test_delta import canon
from tests.test_delta_stream import (CFG, _dist_engine, _device_count,
                                     _start_edges, apply_net, random_batch)


# ---------------------------------------------------------------------------
# build_sharded_index: ownership + parity with the unsharded build
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key_pos,ext_pos,arity",
                         [((0,), 1, 2), ((1,), 0, 2), ((0, 1), 2, 3)])
@pytest.mark.parametrize("w", [1, 3, 4])
def test_sharded_index_every_entry_owned_once(key_pos, ext_pos, arity, w):
    rng = np.random.default_rng(0)
    tuples = rng.integers(0, 50, (300, arity)).astype(np.int32)
    sharded = build_sharded_index(tuples, key_pos, ext_pos, w)
    local = build_index(tuples, key_pos, ext_pos)
    ns = np.asarray(sharded.n)
    assert sharded.key.shape[0] == w and ns.shape == (w,)
    # memory linearity: shard sizes sum to the unsharded live size
    assert int(ns.sum()) == int(local.n)
    seen = []
    for k in range(w):
        nk = int(ns[k])
        keys = np.asarray(sharded.key[k][:nk]).astype(np.int64)
        vals = np.asarray(sharded.val[k][:nk]).astype(np.int64)
        # every live entry hashes home: owner_of(key) == its worker row
        np.testing.assert_array_equal(shard_of(keys, w),
                                      np.full(nk, k, np.int32))
        # shard rows keep the strict lexicographic (key, val) invariant
        if nk > 1:
            dk, dv = np.diff(keys), np.diff(vals)
            assert ((dk > 0) | ((dk == 0) & (dv > 0))).all()
        seen.append(np.stack([keys, vals], 1))
    # exactly-once: shards are pairwise disjoint and union to the local index
    allkv = np.concatenate(seen, axis=0)
    assert np.unique(allkv, axis=0).shape[0] == allkv.shape[0]
    lkeys = np.asarray(local.key[:int(local.n)]).astype(np.int64)
    lvals = np.asarray(local.val[:int(local.n)]).astype(np.int64)
    order = np.lexsort((allkv[:, 1], allkv[:, 0]))
    np.testing.assert_array_equal(allkv[order],
                                  np.stack([lkeys, lvals], 1))


def test_sharded_member_answers_match_unsharded():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    tuples = rng.integers(0, 40, (250, 2)).astype(np.int32)
    w = 4
    sharded = build_sharded_index(tuples, (0,), 1, w)
    local = build_index(tuples, (0,), 1)
    probes_k = rng.integers(0, 45, 64).astype(np.int32)
    probes_v = rng.integers(0, 45, 64).astype(np.int32)
    want = np.asarray(index_member(local, jnp.asarray(probes_k),
                                   jnp.asarray(probes_v)))
    own = shard_of(probes_k.astype(np.int64), w)
    vi = VersionedIndex((sharded,), ())
    got = np.zeros(64, bool)
    hit_off_owner = False
    for k in range(w):
        shard = vi.worker_shard(k)
        ans = np.asarray(index_member(shard.pos[0], jnp.asarray(probes_k),
                                      jnp.asarray(probes_v)))
        got |= ans & (own == k)
        hit_off_owner |= bool((ans & (own != k)).any())
    np.testing.assert_array_equal(got, want)
    assert not hit_off_owner  # non-owners never claim membership


# ---------------------------------------------------------------------------
# partition_indices: versioned regions (the old NotImplementedError path)
# ---------------------------------------------------------------------------

def test_partition_indices_versioned_regions_parity():
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    q = Q.triangle()
    plan = make_delta_plan(delta_queries(q)[1])
    assert any(v != "static" for *_x, v in plan.index_ids())
    base = np.unique(rng.integers(0, 30, (200, 2)).astype(np.int32), axis=0)
    keep = base[:, 0] != base[:, 1]
    base = base[keep]
    cins = np.array([[40, 1], [41, 2]], np.int32)
    cdel = base[:3].copy()
    uins = np.array([[50, 5]], np.int32)
    udel = base[4:6].copy()
    regions = {"base": base, "cins": cins, "cdel": cdel,
               "uins": uins, "udel": udel}
    w = 3
    from repro.core.distributed import partition_indices
    region_tuples = {}
    for _id, rel, key_pos, ext_pos, version in plan.index_ids():
        region_tuples[(rel, key_pos, ext_pos)] = regions
    out = partition_indices(plan, {}, w, region_tuples)
    probes_k = jnp.asarray(rng.integers(0, 55, 128).astype(np.int32))
    probes_v = jnp.asarray(rng.integers(0, 55, 128).astype(np.int32))
    for _id, rel, key_pos, ext_pos, version in plan.index_ids():
        names = {"old": ("base", "cins"), "new": ("base", "cins", "uins")}
        neg_names = {"old": ("cdel",), "new": ("cdel", "udel")}
        local = VersionedIndex(
            tuple(build_index(regions[nm], key_pos, ext_pos)
                  for nm in names[version]),
            tuple(build_index(regions[nm], key_pos, ext_pos)
                  for nm in neg_names[version]))
        vi = out[_id]
        assert vi.num_regions == local.num_regions
        # summed shard counts == local counts for every probe key
        cnt = sum(np.asarray(vi.worker_shard(k).count(probes_k))
                  for k in range(w))
        np.testing.assert_array_equal(cnt, np.asarray(local.count(probes_k)))
        # signed membership: OR over shards == local answer
        mem = np.zeros(128, bool)
        dele = np.zeros(128, bool)
        for k in range(w):
            m, d = vi.worker_shard(k).signed_member(probes_k, probes_v)
            mem |= np.asarray(m)
            dele |= np.asarray(d)
        lm, ld = local.signed_member(probes_k, probes_v)
        np.testing.assert_array_equal(mem, np.asarray(lm))
        np.testing.assert_array_equal(dele, np.asarray(ld))


def test_partition_indices_requires_regions_for_delta_versions():
    q = Q.triangle()
    plan = make_delta_plan(delta_queries(q)[0])
    from repro.core.distributed import partition_indices
    with pytest.raises(ValueError, match="DistDeltaBigJoin"):
        partition_indices(plan, {}, 2)


def test_static_partition_unchanged():
    """The static path still matches the oracle after the rewrite."""
    from repro.core.bigjoin import BigJoinConfig
    from repro.core.distributed import DistConfig, distributed_join
    from repro.core.generic_join import generic_join
    e = _start_edges(30, 260, 3)
    q = Q.triangle()
    plan = make_plan(q)
    cfg = DistConfig(BigJoinConfig(batch=128, mode="count"), 1,
                     route_capacity=128)
    res = distributed_join(plan, {Q.EDGE: e}, cfg=cfg)
    assert res.count == generic_join(q, {Q.EDGE: e}, plan=plan)[1]


# ---------------------------------------------------------------------------
# engine-level invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2])
def test_engine_memory_linearity_across_stream(w):
    """After every commit, each projection's shard entries sum EXACTLY to
    its host-truth region rows: nothing replicated, nothing dropped."""
    if _device_count() < w:
        pytest.skip(f"needs {w} devices (CI runs with 4 virtual devices)")
    q = Q.triangle()
    edges = _start_edges(18, 110, 9)
    engine = _dist_engine(q, edges, w)
    rng = np.random.default_rng(10)
    cur = edges.copy()
    for _ in range(4):
        upd, wts = random_batch(rng, 18, cur, 12)
        engine.apply(upd, wts)
        cur = engine.edges.copy()
        for reg in engine.projections.values():
            host_rows = (reg.base.shape[0] + reg.cins.shape[0]
                         + reg.cdel.shape[0])
            assert reg.versioned("new").live_entries() == host_rows
            # every region's shard rows hash home to their worker
            for d in (reg.d_base, reg.d_cins, reg.d_cdel):
                ns = np.asarray(d.n)
                for k in range(w):
                    keys = np.asarray(d.key[k][:ns[k]]).astype(np.int64)
                    assert (shard_of(keys, w) == k).all()


@pytest.mark.parametrize("w", [1, 2])
def test_maybe_compact_on_shards_preserves_answers(w):
    """Eager vs never compaction on the mesh engine: identical signed
    outputs every epoch (compaction only reshapes the LSM regions)."""
    if _device_count() < w:
        pytest.skip(f"needs {w} devices (CI runs with 4 virtual devices)")
    q = Q.diamond()
    edges = _start_edges(16, 90, 12)
    from repro.core.distributed import DistDeltaBigJoin, \
        default_delta_config
    from tests.test_delta_stream import _mesh
    dcfg = default_delta_config(w, batch=128, out_capacity=1 << 15)
    eager = DistDeltaBigJoin(q, edges, mesh=_mesh(w), dcfg=dcfg,
                             compact_ratio=0.01)
    lazy = DistDeltaBigJoin(q, edges, mesh=_mesh(w), dcfg=dcfg,
                            compact_ratio=1e9)
    rng = np.random.default_rng(13)
    cur = edges.copy()
    for _ in range(4):
        upd, wts = random_batch(rng, 16, cur, 10)
        a = eager.apply(upd, wts)
        b = lazy.apply(upd, wts)
        assert canon(a.tuples, a.weights) == canon(b.tuples, b.weights)
        np.testing.assert_array_equal(eager.edges, lazy.edges)
        cur = eager.edges.copy()
    # eager engine actually compacted (committed regions folded into base)
    assert all(r.cins.shape[0] == 0 and r.cdel.shape[0] == 0
               for r in eager.projections.values())


# ---------------------------------------------------------------------------
# jaxpr: the level loop is collectives-only (no per-update host trips)
# ---------------------------------------------------------------------------

_HOST_PRIMS = {"pure_callback", "io_callback", "debug_callback", "callback",
               "infeed", "outfeed", "host_local_array_to_global_array"}


def test_dist_delta_step_has_no_host_roundtrips():
    """Trace the whole per-worker delta program (seed -> while(level step)
    -> psum) and assert: (1) no host-callback primitive anywhere, (2) the
    drain while-loop exists and its body performs the index lookups through
    all_to_all collectives — i.e. every per-update lookup stays on-device
    and in-program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro import compat
    from repro.compat import iter_eqns, primitive_names, subjaxprs
    from repro.configs.wcoj import _abstract_indices
    from repro.core.bigjoin import BigJoinConfig
    from repro.core.distributed import (AXIS, DistConfig, build_per_worker)

    q = Q.triangle()
    plan = make_delta_plan(delta_queries(q)[0])
    w = 1
    dcfg = DistConfig(BigJoinConfig(batch=128, mode="count"), w,
                      route_capacity=64)
    per_worker = build_per_worker(plan, dcfg)
    indices = _abstract_indices(plan, 1 << 12, w, delta=128)
    S = 128
    seed = jax.ShapeDtypeStruct((w, S, 2), jnp.int32)
    seed_n = jax.ShapeDtypeStruct((w,), jnp.int32)
    seed_w = jax.ShapeDtypeStruct((w, S), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
    specs = (jax.tree.map(lambda _: P(AXIS), indices,
                          is_leaf=lambda x: isinstance(
                              x, jax.ShapeDtypeStruct)),
             P(AXIS), P(AXIS), P(AXIS))
    fn = compat.shard_map(per_worker, mesh=mesh, in_specs=specs,
                          out_specs=(P(),) * 7, check_vma=False)
    closed = jax.make_jaxpr(fn)(indices, seed, seed_n, seed_w)

    prims = primitive_names(closed)
    assert not (prims & _HOST_PRIMS), prims & _HOST_PRIMS
    assert "while" in prims  # the drain loop is in-program

    # find every while body; at least one must contain the all_to_all
    # request/response fabric and NONE may contain host primitives
    bodies = [sub for eqn in iter_eqns(closed)
              if eqn.primitive.name == "while"
              for v in eqn.params.values() for sub in subjaxprs(v)]
    assert bodies
    loop_prims = set().union(*map(primitive_names, bodies))
    assert "all_to_all" in loop_prims
    assert not (loop_prims & _HOST_PRIMS)


def test_one_program_invocation_per_delta_query():
    """The engine launches exactly one distributed program per dAQ_i per
    epoch — updates are batched into the dataflow, never looped on host."""
    q = Q.triangle()
    edges = _start_edges(14, 70, 14)
    engine = _dist_engine(q, edges, 1)
    calls = []
    for pi, prog in list(engine._programs.items()):
        pass  # programs built lazily on first apply

    orig = engine._run_plan
    def spy(plan, indices, seed, weights):
        calls.append(plan)
        return orig(plan, indices, seed, weights)
    engine._run_plan = spy
    upd = np.array([[1, 2], [2, 3], [60, 61]], np.int32)
    engine.apply(upd)
    assert len(calls) == len(engine.plans) == len(delta_queries(q))
