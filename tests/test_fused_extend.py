"""Fused extension pipeline: multi-region membership parity, single-launch
fusion accounting, and fused-extend-step vs the serial GJ oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.bigjoin import (BigJoinConfig, build_indices, build_step,
                                run_bigjoin, seed_tuples_for)
from repro.core.csr import build_index, empty_index
from repro.core.dataflow_index import VersionedIndex
from repro.core.generic_join import generic_join
from repro.core.plan import make_plan

from tests.test_generic_join import random_graph


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

from repro.kernels import count_pallas_calls  # noqa: E402


@pytest.fixture
def extend_kernel_on(monkeypatch):
    """Put the fused extend kernel on the path for one test: it is off the
    default path (it does not compile for TPU) but stays the interpret-mode
    kernel these tests hold to the jnp stages."""
    import repro.kernels as K
    monkeypatch.delitem(K.OFF_DEFAULT_PATH, "extend")


def random_versioned(rng, n_base=400, n_delta=60, nv=80):
    """A VersionedIndex with a randomized insert/delete region mix
    (pos = base/cins/uins, neg = cdel/udel) over single-column keys."""
    def edges(n):
        return rng.integers(0, nv, size=(max(n, 1), 2)).astype(np.int32)

    base = build_index(edges(n_base), (0,), 1, capacity=n_base + 17)
    cins = build_index(edges(n_delta), (0,), 1)
    uins = build_index(edges(n_delta // 2), (0,), 1)
    cdel = build_index(edges(n_delta // 2), (0,), 1)
    udel = build_index(edges(n_delta // 3), (0,), 1)
    return VersionedIndex((base, cins, uins), (cdel, udel))


# ---------------------------------------------------------------------------
# multi-region membership kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(5))
def test_multi_region_member_parity(seed):
    rng = np.random.default_rng(seed)
    idx = random_versioned(rng)
    B = 300
    qk = jnp.asarray(rng.integers(0, 80, B).astype(np.int32))
    qv = jnp.asarray(rng.integers(0, 80, B).astype(np.int32))
    ref_m = np.asarray(idx.member(qk, qv, use_kernel=False))
    ref_d = np.asarray(idx.deleted(qk, qv, use_kernel=False))
    got_m, got_d = idx.signed_member(qk, qv, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got_m), ref_m)
    np.testing.assert_array_equal(np.asarray(got_d), ref_d)
    np.testing.assert_array_equal(
        np.asarray(idx.member(qk, qv, use_kernel=True)), ref_m)
    np.testing.assert_array_equal(
        np.asarray(idx.deleted(qk, qv, use_kernel=True)), ref_d)


def test_multi_region_member_mixed_empty_regions():
    rng = np.random.default_rng(7)
    base = build_index(rng.integers(0, 30, (200, 2)).astype(np.int32),
                       (0,), 1)
    idx = VersionedIndex((base, empty_index(4)), (empty_index(2),))
    qk = jnp.asarray(rng.integers(0, 30, 64).astype(np.int32))
    qv = jnp.asarray(rng.integers(0, 30, 64).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(idx.member(qk, qv, use_kernel=True)),
        np.asarray(idx.member(qk, qv, use_kernel=False)))


def test_multi_region_member_is_single_launch():
    """R regions -> exactly ONE pallas_call (the per-region path would
    launch R; the fusion must save >= 1 launch whenever R > 1)."""
    rng = np.random.default_rng(3)
    idx = random_versioned(rng)
    R = len(idx.pos) + len(idx.neg)
    assert R > 1
    qk = jnp.zeros(64, jnp.int32)
    qv = jnp.zeros(64, jnp.int32)
    n = count_pallas_calls(
        lambda a, b: idx.member(a, b, use_kernel=True), qk, qv)
    assert n == 1  # saved R - 1 launches


# ---------------------------------------------------------------------------
# fused extend step vs serial GJ oracle
# ---------------------------------------------------------------------------

MOTIFS = [Q.triangle(), Q.four_clique(), Q.diamond()]


@pytest.mark.parametrize("q", MOTIFS, ids=lambda q: q.name)
def test_fused_extend_matches_oracle(q, extend_kernel_on):
    g = random_graph(45, 420, 11)
    plan = make_plan(q)
    rels = {Q.EDGE: g.edges}
    idx = build_indices(plan, rels)
    cfg = BigJoinConfig(batch=256, seed_chunk=128, out_capacity=1 << 16,
                        use_kernel=True)
    res = run_bigjoin(plan, idx, seed_tuples_for(plan, rels), cfg=cfg)
    ref, ref_cnt = generic_join(q, rels, plan=plan)
    assert res.count == ref_cnt
    if ref_cnt:
        np.testing.assert_array_equal(
            np.unique(res.tuples, axis=0), np.unique(ref, axis=0))


@pytest.mark.parametrize("q", MOTIFS, ids=lambda q: q.name)
def test_fused_step_bitexact_vs_jnp_step(q, extend_kernel_on):
    """The fused kernel middle must reproduce the jnp stage sequence
    bit-for-bit: identical output tuples AND identical work counters."""
    g = random_graph(40, 380, 5)
    plan = make_plan(q)
    rels = {Q.EDGE: g.edges}
    idx = build_indices(plan, rels)
    kw = dict(batch=128, seed_chunk=64, out_capacity=1 << 16)
    a = run_bigjoin(plan, idx, seed_tuples_for(plan, rels),
                    cfg=BigJoinConfig(use_kernel=True, **kw))
    b = run_bigjoin(plan, idx, seed_tuples_for(plan, rels),
                    cfg=BigJoinConfig(use_kernel=False, **kw))
    assert a.count == b.count
    assert a.proposals == b.proposals
    assert a.intersections == b.intersections
    assert a.steps == b.steps
    np.testing.assert_array_equal(a.tuples, b.tuples)


def test_fused_level_branch_is_single_launch(extend_kernel_on):
    """Each extension-level branch of the dataflow step lowers to exactly
    one pallas_call: no proposal round-trips through HBM between stages."""
    q = Q.four_clique()
    g = random_graph(30, 250, 9)
    plan = make_plan(q)
    idx = build_indices(plan, {Q.EDGE: g.edges})
    cfg = BigJoinConfig(batch=128, seed_chunk=64, mode="count",
                        use_kernel=True)
    from repro.core.bigjoin import make_state
    step = build_step(plan, cfg)
    state = make_state(plan, cfg)
    n = count_pallas_calls(step, state, idx)
    assert n == len(plan.levels)  # one fused launch per level branch


def test_default_level_branch_probes_with_member_kernel():
    """On the default path (extend off it) each level branch intersects
    through ONE multi-region member launch per binding."""
    from repro.kernels import on_default_path
    assert not on_default_path("extend") and on_default_path("member")
    q = Q.four_clique()
    g = random_graph(30, 250, 9)
    plan = make_plan(q)
    idx = build_indices(plan, {Q.EDGE: g.edges})
    cfg = BigJoinConfig(batch=128, seed_chunk=64, mode="count",
                        use_kernel=True)
    from repro.core.bigjoin import make_state
    step = build_step(plan, cfg)
    n = count_pallas_calls(step, make_state(plan, cfg), idx)
    assert n == sum(len(lv.bindings) for lv in plan.levels)


def test_kernel_family_choice_is_static():
    from repro.kernels import FAMILIES, OFF_DEFAULT_PATH, on_default_path
    assert set(OFF_DEFAULT_PATH) == {"extend", "rank", "fold"}
    assert [f for f in FAMILIES if on_default_path(f)] == ["member"]
    assert all(OFF_DEFAULT_PATH.values())  # each records its reason
    with pytest.raises(ValueError):
        on_default_path("gather")


# ---------------------------------------------------------------------------
# _NpIndex wide-key fallback (satellite: no Python-set probes)
# ---------------------------------------------------------------------------

def test_npindex_wide_key_fallback_vectorized():
    from repro.core.generic_join import _NpIndex
    rng = np.random.default_rng(0)
    # two key columns -> packed keys >= 2^31: the non-packed path
    tuples = np.stack([rng.integers(0, 2**20, 500),
                       rng.integers(0, 2**20, 500),
                       rng.integers(0, 100, 500)], axis=1)
    idx = _NpIndex(tuples, (0, 1), 2)
    assert idx._packed is None
    key = (tuples[:, 0].astype(np.int64) << 32) | tuples[:, 1]
    qk = np.concatenate([key[:50], key[:50] + 1])
    qv = np.concatenate([tuples[:50, 2], tuples[:50, 2]])
    got = idx.member(qk, qv.astype(np.int64))
    truth = {(int(k), int(v)) for k, v in zip(key, tuples[:, 2])}
    exp = np.array([(int(a), int(b)) in truth for a, b in zip(qk, qv)])
    np.testing.assert_array_equal(got, exp)
