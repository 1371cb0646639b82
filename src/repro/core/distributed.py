"""Distributed BiGJoin over a device mesh via shard_map (§3.2 / §3.4).

Workers are the devices of one mesh axis.  Every extension index is
hash-partitioned by its packed key (``owner_of``), so the cluster-wide memory
is O(IN) — each edge is stored by exactly one worker per direction, the
paper's linear-memory property.

Lookups are *request/response*: a worker keeps its popped prefixes and sends
(key) / (key,k) / (key,val) requests to the owners — precisely the three
distributed index services of BiGJoin-S (§3.4.1):

    count     C(p)          key        -> |Ext(p)|
    resolve   Ext-Res(p,k)  (key,k)    -> k-th extension
    member    Ext(p·e)      (key,val)  -> membership / deletion bits

Requests travel through a fixed-capacity bucketed ``all_to_all``
(``route_capacity`` slots per peer pair).  Overflowing requests are *not*
dropped: the affected prefix simply does not advance its rem-ext cursor this
round and is retried — backpressure instead of failure, the static-shape
analogue of the paper's Faucet-style flow control [33].  With BiGJoin-S
aggregation (``aggregate=True``, request dedup per key) the balls-into-bins
bound of Thm 3.4 makes overflow improbable at capacity O(B'/w · polylog).

Outputs stay on the producing worker (the paper assumes outputs leave the
cluster); counts/counters are psum-reduced at the end.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat, faults
from repro.core import compilestats, csr, hostread
from repro.core import delta as _delta
from repro.core.bigjoin import BigJoinConfig, JoinResult
from repro.core.csr import AXIS  # noqa: F401  (re-exported)
from repro.core.dataflow_index import VersionedIndex
from repro.core.plan import Plan
from repro.errors import (CapacityOverflow, ESCALATES_BATCH, ESCALATES_OUT,
                          ESCALATES_ROUTE, OVF_OUT, OVF_QUEUE, OVF_ROUTE,
                          OVF_SEED, _KIND_BITS)


# ---------------------------------------------------------------------------
# hashing / partitioning
# ---------------------------------------------------------------------------

def owner_of_np(key, w: int) -> np.ndarray:
    return csr.shard_of(key, w)


def owner_of(key, w: int) -> jax.Array:
    """Worker owning each packed key — composite (hi, lo) pairs fold into
    one routing word first (``csr.combine_key``, shared with the host-side
    shard builds so routing and placement can never disagree)."""
    if isinstance(key, tuple):
        key = csr.combine_key(*key)
    h = (key.astype(jnp.uint64) * jnp.uint64(csr.SHARD_MIX)) >> jnp.uint64(33)
    return (h % jnp.uint64(w)).astype(jnp.int32)


# region-name subsets backing each logical version (delta.py / §4.3):
# pos regions contribute extensions, neg regions subtract membership.
VERSION_REGIONS = {
    "static": (("base",), ()),
    "old": (("base", "cins"), ("cdel",)),
    "new": (("base", "cins", "uins"), ("cdel", "udel")),
}


def partition_indices(plan: Plan, relations: Dict[str, np.ndarray],
                      w: int, region_tuples: Optional[Dict] = None
                      ) -> Dict[str, VersionedIndex]:
    """Hash-partition every index the plan needs over ``w`` workers.

    Static versions partition ``relations[rel]`` directly.  Delta versions
    ("old"/"new") partition each multi-version REGION of the projection:
    ``region_tuples[(rel, key_pos, ext_pos)]`` must map region names
    (base/cins/cdel/uins/udel) to host tuple arrays — exactly the host truth
    a :class:`repro.core.delta._Regions` maintains.  Every region entry is
    owned by exactly one worker per projection, so cluster memory stays
    O(IN + delta): sharding never replicates, it only splits.

    Returns indices whose arrays carry a leading [w] axis (to be sharded
    over the worker mesh axis).
    """
    out: Dict[str, VersionedIndex] = {}
    for index_id, rel, key_pos, ext_pos, version in plan.index_ids():
        if version == "static":
            base = csr.build_sharded_index(np.asarray(relations[rel]),
                                           key_pos, ext_pos, w)
            out[index_id] = VersionedIndex((base,), ())
            continue
        if region_tuples is None:
            raise ValueError(
                f"plan index {index_id} reads version {version!r}: pass "
                "region_tuples with base/cins/cdel/uins/udel host arrays "
                "(or drive it through DistDeltaBigJoin)")
        regions = region_tuples[(rel, key_pos, ext_pos)]
        pos_names, neg_names = VERSION_REGIONS[version]
        arity = max(max(key_pos, default=0), ext_pos) + 1

        def shard(name):
            rows = np.asarray(regions[name])
            if rows.ndim != 2:  # flat legacy arrays: minimal covering arity
                rows = rows.reshape(-1, arity)
            return csr.build_sharded_index(rows, key_pos, ext_pos, w)

        out[index_id] = VersionedIndex(
            tuple(shard(nm) for nm in pos_names),
            tuple(shard(nm) for nm in neg_names))
    return out


def _local(idx: VersionedIndex) -> VersionedIndex:
    """Strip the leading worker axis inside shard_map."""
    return idx.worker_shard(0)


# ---------------------------------------------------------------------------
# bounded-capacity request/response exchange
# ---------------------------------------------------------------------------

def remote_service(queries, dest: jax.Array, valid: jax.Array, reply_fn,
                   w: int, cap: int, axis: str = AXIS):
    """Route ``queries`` (pytree of [B,...] arrays) to ``dest`` workers, apply
    ``reply_fn`` (pytree of [N,...] -> pytree of [N,...]) at the owner, and
    return (replies [B,...], ok [B]).

    ok=False rows overflowed the per-peer capacity and received no reply.
    """
    B = dest.shape[0]
    dest_eff = jnp.where(valid, dest, w)
    order = jnp.argsort(dest_eff, stable=True).astype(jnp.int32)
    sdest = dest_eff[order]
    first = jnp.searchsorted(sdest, sdest, side="left").astype(jnp.int32)
    slot = jnp.arange(B, dtype=jnp.int32) - first
    ok_sorted = (sdest < w) & (slot < cap)
    flat = jnp.where(ok_sorted, sdest * cap + slot, w * cap)

    def scatter(x):
        buf = jnp.zeros((w * cap,) + x.shape[1:], x.dtype)
        return buf.at[flat].set(x[order], mode="drop")

    send = jax.tree.map(scatter, queries)
    sent_mask = jnp.zeros(w * cap, jnp.int32).at[flat].set(
        jnp.ones(B, jnp.int32), mode="drop")

    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape((w, cap) + x.shape[1:]), axis, 0, 0, tiled=False
        ).reshape((w * cap,) + x.shape[1:])

    recv = jax.tree.map(a2a, send)
    recv_mask = a2a(sent_mask) > 0
    replies_at_owner = reply_fn(recv, recv_mask)
    back = jax.tree.map(a2a, replies_at_owner)

    # gather replies for my rows: row i sits at (dest[i], slot_of_row[i])
    slot_of_row = jnp.zeros(B, jnp.int32).at[order].set(slot)
    ok = (jnp.zeros(B, bool).at[order].set(ok_sorted)) & valid
    gidx = jnp.clip(dest * cap + slot_of_row, 0, w * cap - 1)
    replies = jax.tree.map(lambda x: x[gidx], back)
    recv_load = recv_mask.sum().astype(jnp.int64)  # requests I served
    return replies, ok, recv_load


def dedup_requests(key, valid: jax.Array):
    """BiGJoin-S aggregation (§3.4.2): collapse duplicate request keys.

    ``key`` is one array or a tuple of arrays (composite keys dedup on the
    exact word tuple — never on a lossy hash, which could merge distinct
    keys).  Returns (rep_idx [B] -> representative row, is_rep [B]).  Only
    representative rows are routed; replies are read through rep_idx.
    """
    keys = key if isinstance(key, tuple) else (key,)
    B = keys[0].shape[0]
    skeys = tuple(
        jnp.where(valid, k, jnp.asarray(np.iinfo(k.dtype.name).max, k.dtype))
        for k in keys)
    if len(skeys) == 1:
        order = jnp.argsort(skeys[0], stable=True).astype(jnp.int32)
        sk = skeys[0][order]
        first = jnp.searchsorted(sk, sk, side="left").astype(jnp.int32)
    else:
        # lexsort: LAST key is primary, so feed the tuple reversed
        order = jnp.lexsort(skeys[::-1]).astype(jnp.int32)
        sk = tuple(k[order] for k in skeys)
        diff = jnp.zeros(B - 1, bool) if B > 1 else jnp.zeros(0, bool)
        for c in sk:
            diff = diff | (c[1:] != c[:-1])
        starts = jnp.concatenate([jnp.ones(1, bool), diff])
        # index of each sorted row's group head: running max of start marks
        first = jax.lax.cummax(
            jnp.where(starts, jnp.arange(B, dtype=jnp.int32), 0))
    rep_sorted = order[first]  # representative original row per sorted pos
    rep_idx = jnp.zeros(B, jnp.int32).at[order].set(rep_sorted)
    is_rep = jnp.zeros(B, bool).at[rep_idx].set(True) & valid
    return rep_idx, is_rep


# ---------------------------------------------------------------------------
# distributed dataflow step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistConfig:
    base: BigJoinConfig
    num_workers: int
    route_capacity: int  # per peer-pair slots; <= batch
    aggregate: bool = True  # BiGJoin-S request dedup (§3.4.2)
    balance: bool = False  # BiGJoin-S Balance operator (§3.4.2)
    max_steps: int = 1 << 30
    axis: object = AXIS  # mesh axis name (or tuple of names) for collectives


def _remote_count(idx_local: VersionedIndex, qkey, dest, valid, w, cap,
                  aggregate, axis=AXIS):
    def reply(q, mask):
        return idx_local.count(q)

    if aggregate:
        rep_idx, is_rep = dedup_requests(qkey, valid)
        (cnt,), ok, load = remote_service(
            (qkey,), dest, is_rep, lambda q, m: (reply(q[0], m),), w, cap,
            axis)
        return cnt[rep_idx], ok[rep_idx] | ~valid, load
    (cnt,), ok, load = remote_service(
        (qkey,), dest, valid, lambda q, m: (reply(q[0], m),), w, cap, axis)
    return cnt, ok | ~valid, load


def _remote_resolve(idx_local: VersionedIndex, qkey, k, dest, valid, w,
                    cap, axis=AXIS):
    def reply(q, mask):
        qk, kk = q
        starts, counts = idx_local.ranges(qk)
        return (idx_local.gather(starts, counts, kk),)

    (val,), ok, load = remote_service((qkey, k), dest, valid, reply, w,
                                      cap, axis)
    return val, ok | ~valid, load


def _remote_member(idx_local: VersionedIndex, qkey, qval, dest, valid, w,
                   cap, aggregate, axis=AXIS, use_kernel=False,
                   interpret=None):
    def reply(q, mask):
        qk, qv = q
        # one fused pass over every region: membership and deletion bits
        # come from a single kernel launch (or one jnp reduction)
        mem, dele = idx_local.signed_member(qk, qv, use_kernel, interpret)
        return (mem.astype(jnp.int32) | (dele.astype(jnp.int32) << 1),)

    # dedup on the exact (key, val) tuple: packed into one word for narrow
    # int32 keys, an explicit word tuple for composite keys; wide int64
    # single-word keys cannot widen losslessly, so they skip aggregation
    if isinstance(qkey, tuple):
        pair = qkey + (qval.astype(jnp.int64),)
    elif qkey.dtype == jnp.int32:
        pair = (qkey.astype(jnp.int64) << 32) | qval.astype(jnp.int64)
    else:
        pair = None
    if aggregate and pair is not None:
        rep_idx, is_rep = dedup_requests(pair, valid)
        (bits,), ok, load = remote_service((qkey, qval), dest, is_rep, reply,
                                           w, cap, axis)
        bits, ok = bits[rep_idx], ok[rep_idx]
    else:
        (bits,), ok, load = remote_service((qkey, qval), dest, valid, reply,
                                           w, cap, axis)
    return (bits & 1) > 0, (bits & 2) > 0, ok | ~valid, load


# ---------------------------------------------------------------------------
# the distributed level branch (mirrors bigjoin._level_branch with remote
# lookups + rem-ext deferral backpressure)
# ---------------------------------------------------------------------------

def _build_dist_level(plan: Plan, dcfg: DistConfig, li: int):
    from repro.core.bigjoin import (BigJoinState, LevelQueue, _binding_key,
                                    _compact, _pack_cols, _scatter_append)
    lv = plan.levels[li]
    w, cap, B = dcfg.num_workers, dcfg.route_capacity, dcfg.base.batch
    is_last = li == len(plan.levels) - 1
    new_bound = lv.bound_attrs + (lv.ext_attr,)
    INF = jnp.int32(np.iinfo(np.int32).max)

    def branch(state, indices):
        qu = state.queues[li]
        W = min(B, qu.prefix.shape[0])
        wprefix, wk, wweight = qu.prefix[:W], qu.k[:W], qu.weight[:W]
        valid = jnp.arange(W, dtype=jnp.int32) < qu.size

        # ---- remote count minimization ------------------------------------
        qks, cnts, count_ok = [], [], valid
        recv_load = state.recv_load
        for b in lv.bindings:
            idx = indices[b.index_id]
            qk = _binding_key(wprefix, lv.bound_attrs, b.key_attrs, idx)
            cnt, ok, load = _remote_count(idx, qk, owner_of(qk, w), valid, w,
                                          cap, dcfg.aggregate, dcfg.axis)
            qks.append(qk)
            cnts.append(cnt)
            count_ok = count_ok & ok
            recv_load = recv_load + load
        tot = jnp.stack(cnts, -1)
        min_i = jnp.argmin(tot, -1).astype(jnp.int32)
        min_c = tot.min(-1)

        remaining_true = jnp.maximum(min_c - wk, 0)
        remaining = jnp.where(valid & count_ok, remaining_true, 0)
        acum = jnp.cumsum(remaining, dtype=jnp.int32)
        allowed = jnp.clip(B - (acum - remaining), 0, remaining
                           ).astype(jnp.int32)

        aacum = jnp.cumsum(allowed, dtype=jnp.int32)
        t = jnp.arange(B, dtype=jnp.int32)
        pvalid = t < aacum[-1]
        r = jnp.clip(jnp.searchsorted(aacum, t, side="right"), 0, W - 1)
        r = r.astype(jnp.int32)
        k_off = t - (aacum[r] - allowed[r]) + wk[r]

        # ---- remote extension resolution (Ext-Res lookups) ----------------
        cand = jnp.zeros(B, jnp.int32)
        incomplete = jnp.zeros(B, bool)
        for bi, b in enumerate(lv.bindings):
            idx = indices[b.index_id]
            qk_r = qks[bi][r]
            mask = pvalid & (min_i[r] == bi)
            val, ok, load = _remote_resolve(idx, qk_r, k_off,
                                            owner_of(qk_r, w), mask, w, cap,
                                            dcfg.axis)
            cand = jnp.where(mask, val, cand)
            incomplete = incomplete | (mask & ~ok)
            recv_load = recv_load + load
        new_prefix = jnp.concatenate([wprefix[r], cand[:, None]], axis=1)
        weight = wweight[r]
        alive = pvalid
        n_isect = jnp.asarray(0, jnp.int64)

        # ---- remote intersections ------------------------------------------
        for bi, b in enumerate(lv.bindings):
            idx = indices[b.index_id]
            pos = [list(new_bound).index(a) for a in b.key_attrs]
            qk = _pack_cols(new_prefix, pos, idx.pos[0].key.dtype)
            mem, dele, ok, load = _remote_member(
                idx, qk, cand, owner_of(qk, w), pvalid, w, cap,
                dcfg.aggregate, dcfg.axis, dcfg.base.use_kernel,
                dcfg.base.kernel_interpret)
            recv_load = recv_load + load
            is_min = min_i[r] == bi
            keep = jnp.where(is_min, ~dele, mem)
            n_isect = n_isect + (alive & ~is_min).sum().astype(jnp.int64)
            alive = alive & (keep | ~ok)  # unanswered rows defer, not die
            incomplete = incomplete | (pvalid & ~ok)
        for f in lv.filters:
            lo = new_prefix[:, list(new_bound).index(f.lo)]
            hi = new_prefix[:, list(new_bound).index(f.hi)]
            alive = alive & (lo < hi)

        # ---- rem-ext deferral: advance each prefix past its last complete
        # contiguous proposal only; later survivors are retried next round ---
        inc_off = jnp.where(incomplete, k_off, INF)
        first_inc = jax.ops.segment_min(inc_off, r, num_segments=W)
        first_inc = jnp.minimum(first_inc, INF)
        advance = jnp.clip(jnp.minimum(first_inc, wk + allowed) - wk,
                           0, allowed)
        consumed = valid & count_ok & (wk + advance >= min_c)
        alive = alive & (k_off < first_inc[r])
        n_proposed = (pvalid & (k_off < first_inc[r])).sum()

        # ---- retire / push (identical to the single-host branch) ----------
        kfull = qu.k.at[:W].set(wk + advance)
        live_row = jnp.arange(qu.prefix.shape[0], dtype=jnp.int32) < qu.size
        keep_rows = live_row & ~jnp.pad(consumed,
                                        (0, qu.prefix.shape[0] - W))
        (pfx, kk, ww), nsz = _compact([qu.prefix, kfull, qu.weight],
                                      keep_rows)
        queues = list(state.queues)
        queues[li] = LevelQueue(pfx, kk, ww, nsz)

        out_buf, out_weight = state.out_buf, state.out_weight
        out_n, out_count = state.out_n, state.out_count
        overflow = state.overflow
        if is_last:
            out_count = out_count + (weight * alive).sum().astype(jnp.int64)
            if dcfg.base.mode == "collect":
                perm = np.argsort(np.asarray(plan.attr_order))
                out_buf, n_new, ovf1 = _scatter_append(
                    out_buf, out_n, new_prefix[:, perm], alive)
                out_weight, _, _ = _scatter_append(
                    out_weight, out_n, weight, alive)
                out_n = jnp.minimum(out_n + n_new,
                                    jnp.int32(out_buf.shape[0]))
                overflow = overflow | jnp.where(ovf1, OVF_OUT, 0)
        else:
            nxt = queues[li + 1]
            npfx, n_new, ovf1 = _scatter_append(
                nxt.prefix, nxt.size, new_prefix, alive)
            nk, _, _ = _scatter_append(
                nxt.k, nxt.size, jnp.zeros(B, jnp.int32), alive)
            nw, _, _ = _scatter_append(nxt.weight, nxt.size, weight, alive)
            queues[li + 1] = LevelQueue(
                npfx, nk, nw,
                jnp.minimum(nxt.size + n_new,
                            jnp.int32(nxt.prefix.shape[0])))
            overflow = overflow | jnp.where(ovf1, OVF_QUEUE, 0)

        return BigJoinState(
            tuple(queues), out_buf, out_weight, out_n, out_count, overflow,
            state.proposals + n_proposed.astype(jnp.int64),
            state.intersections + n_isect, recv_load)

    return branch


def build_dist_step(plan: Plan, dcfg: DistConfig):
    """Step on (BigJoinState, piece_queues).  Lock-step level choice: workers
    must agree (they all participate in the collectives), so the globally
    deepest non-empty queue is chosen via psum'd sizes."""
    if dcfg.balance:
        from repro.core.balance import build_balanced_step
        return build_balanced_step(plan, dcfg)

    branches = [_build_dist_level(plan, dcfg, li)
                for li in range(len(plan.levels))]

    def step(carry, indices):
        state, pieces = carry
        sizes = jnp.stack([q.size for q in state.queues])
        gsizes = jax.lax.psum(sizes, dcfg.axis)
        nz = gsizes > 0
        deepest = (len(branches) - 1
                   - jnp.argmax(nz[::-1]).astype(jnp.int32))
        deepest = jnp.clip(deepest, 0, len(branches) - 1)
        return jax.lax.switch(deepest, branches, state, indices), pieces

    return step


# ---------------------------------------------------------------------------
# whole-join program: shard_map( seed -> while(step) -> psum(outputs) )
# ---------------------------------------------------------------------------

def build_per_worker(plan: Plan, dcfg: DistConfig):
    """The SPMD body: fn(indices, seed [1,S,2], seed_n [1], seed_w [1,S])
    run under shard_map.  ``seed_w`` carries signed seed weights (+1/-1), so
    the same program serves static joins (all ones) and Delta-BiGJoin's
    signed dR seeds.  Exposed separately so the multi-pod dry-run can lower
    it on arbitrary meshes (launch/dryrun.py)."""
    from repro.core.bigjoin import make_state
    from repro.core.bigjoin import _scatter_append, _binding_key
    step = build_dist_step(plan, dcfg)
    w, cap = dcfg.num_workers, dcfg.route_capacity
    collect = dcfg.base.mode == "collect"

    def per_worker(indices, seed, seed_n, seed_w):
        compilestats.record("distributed.program")
        seed, seed_n, seed_w = seed[0], seed_n[0], seed_w[0]
        local = {k: _local(v) for k, v in indices.items()}
        state = make_state(plan, dcfg.base, seed_capacity=seed.shape[0])

        # seed enqueue with remote seed filters (P_w prefixes: width 2 for
        # projection-seeded plans, the seed atom's arity for n-ary deltas)
        alive = jnp.arange(seed.shape[0], dtype=jnp.int32) < seed_n
        bound = tuple(plan.attr_order[:plan.seed_width])
        route_ovf = jnp.asarray(0, jnp.int32)
        for b in plan.seed_filters:
            idx = local[b.index_id]
            qk = _binding_key(seed, bound, b.key_attrs, idx)
            qv = seed[:, bound.index(b.ext_attr)]
            mem, _, ok, _ld = _remote_member(
                idx, qk, qv, owner_of(qk, w), alive, w,
                max(cap, seed.shape[0] // max(w // 2, 1) + 1),
                dcfg.aggregate, dcfg.axis, dcfg.base.use_kernel,
                dcfg.base.kernel_interpret)
            # a seed whose route slot overflowed got NO reply; dropping it
            # would silently undercount, so flag OVF_ROUTE and escalate
            route_ovf = route_ovf | jnp.where(
                (alive & ~ok).any(), OVF_ROUTE, 0)
            alive = alive & mem & ok
        for f in plan.seed_ineq:
            alive = alive & (seed[:, bound.index(f.lo)]
                             < seed[:, bound.index(f.hi)])
        state = dataclasses.replace(state,
                                    overflow=state.overflow | route_ovf)
        if not plan.levels:
            # the seed covers every attribute (single-atom delta plans):
            # filtered seeds ARE the outputs; nothing to drain
            wts = seed_w.astype(jnp.int32)
            out_count = state.out_count + (wts * alive).sum().astype(
                jnp.int64)
            out_buf, out_weight = state.out_buf, state.out_weight
            out_n, ovf0 = state.out_n, state.overflow
            if collect:
                perm = np.argsort(np.asarray(plan.attr_order))
                out_buf, n_new, ovf = _scatter_append(
                    out_buf, out_n, seed[:, perm], alive)
                out_weight, _, _ = _scatter_append(
                    out_weight, out_n, wts, alive)
                out_n = jnp.minimum(out_n + n_new,
                                    jnp.int32(out_buf.shape[0]))
                ovf0 = ovf0 | jnp.where(ovf, OVF_OUT, 0)
            state = dataclasses.replace(
                state, out_buf=out_buf, out_weight=out_weight, out_n=out_n,
                out_count=out_count, overflow=ovf0)
            steps = jnp.asarray(0, jnp.int32)
        else:
            q0 = state.queues[0]
            npfx, n_new, ovf = _scatter_append(q0.prefix, q0.size, seed,
                                               alive)
            nk, _, _ = _scatter_append(
                q0.k, q0.size, jnp.zeros(seed.shape[0], jnp.int32), alive)
            nw, _, _ = _scatter_append(
                q0.weight, q0.size, seed_w.astype(jnp.int32), alive)
            from repro.core.bigjoin import LevelQueue
            queues = list(state.queues)
            queues[0] = LevelQueue(npfx, nk, nw, q0.size + n_new)
            state = dataclasses.replace(
                state, queues=tuple(queues),
                overflow=state.overflow | jnp.where(ovf, OVF_SEED, 0))
            if dcfg.balance:
                from repro.core.balance import make_piece_queues
                pieces = make_piece_queues(plan, dcfg)
            else:
                pieces = ()

            def total_active(carry_state):
                st, pcs = carry_state
                sizes = jnp.stack([q.size for q in st.queues]).sum()
                if pcs:
                    sizes = sizes + jnp.stack([p.size for p in pcs]).sum()
                return jax.lax.psum(sizes, dcfg.axis) > 0

            def cond(carry):
                _, active, it = carry
                return active & (it < dcfg.max_steps)

            def body(carry):
                st, _, it = carry
                st = step(st, local)
                return st, total_active(st), it + 1

            carry0 = (state, pieces)
            (state, pieces), _, steps = jax.lax.while_loop(
                cond, body, (carry0, total_active(carry0),
                             jnp.asarray(0, jnp.int32)))

        count = jax.lax.psum(state.out_count, dcfg.axis)
        props = jax.lax.psum(state.proposals, dcfg.axis)
        isect = jax.lax.psum(state.intersections, dcfg.axis)
        # psum per BIT so distinct workers' overflow kinds OR (not add)
        nbits = len(_KIND_BITS)
        shifts = jnp.arange(nbits, dtype=jnp.int32)
        bits = jax.lax.psum((state.overflow >> shifts) & 1, dcfg.axis)
        ovf = jnp.where(bits > 0, jnp.int32(1) << shifts, 0
                        ).sum().astype(jnp.int32)
        # TPU all-reduces lower only Sum for 64-bit integers: gather the
        # per-worker loads and reduce locally instead of an s64 pmax
        max_load = jax.lax.all_gather(state.recv_load, dcfg.axis).max()
        sum_load = jax.lax.psum(state.recv_load, dcfg.axis)
        outs = (count, props, isect, steps, ovf, max_load, sum_load)
        if collect:
            outs = outs + (state.out_buf[None], state.out_weight[None],
                           state.out_n[None])
        return outs

    return per_worker


class DistributedProgram:
    """One whole-join shard_map program: jitted fn(indices, seed [w,S,width],
    seed_n [w], seed_w [w,S]) -> (count, proposals, intersections, steps,
    overflow, max_load, sum_load [, out_buf, out_weight, out_n]).

    The shard_map'd callable is built ONCE and reused: jax.jit caches on
    callable identity, so repeated epochs with stable shapes (the delta
    engine's ratcheted pow2 regions and pinned seed chunks) hit the compile
    cache instead of re-lowering every update batch.  :meth:`warm`
    AOT-compiles the program against ShapeDtypeStruct prototypes
    (``RegionStore.indices_sds_for``) so even the FIRST epoch — and every
    prewarmed capacity-rung crossing — skips XLA entirely (DESIGN.md §8).
    """

    def __init__(self, plan: Plan, dcfg: DistConfig, mesh: Mesh):
        self._per_worker = build_per_worker(plan, dcfg)
        self._mesh = mesh
        self._ax = dcfg.axis
        self.w = dcfg.num_workers
        out_specs = (P(), P(), P(), P(), P(), P(), P())
        if dcfg.base.mode == "collect":
            ax = dcfg.axis
            out_specs = out_specs + (P(ax), P(ax), P(ax))
        self._out_specs = out_specs
        # in_specs must mirror the indices pytree: build per structure
        # (stable per plan, so the jitted wrapper is reused)
        self._cache = {}

    def _jitted(self, treedef):
        f = self._cache.get(treedef)
        if f is None:
            ax = self._ax
            specs = (jax.tree.unflatten(
                treedef, [P(ax)] * treedef.num_leaves),
                P(ax), P(ax), P(ax))
            f = jax.jit(compat.shard_map(
                self._per_worker, mesh=self._mesh, in_specs=specs,
                out_specs=self._out_specs, check_vma=False))
            self._cache[treedef] = f
        return f

    def __call__(self, indices, seed, seed_n, seed_w):
        return self._jitted(jax.tree.structure(indices))(
            indices, seed, seed_n, seed_w)

    def warm(self, indices_sds, chunk: int, width: int) -> None:
        """AOT-compile for per-worker seed chunks of ``chunk`` rows.

        ``indices_sds`` is the ShapeDtypeStruct mirror of the runtime
        indices pytree.  The program runs ONCE on zero-filled inputs (all
        seed counts 0, so the epoch loop body is empty) because only a
        real call lands the executable in the jit dispatch cache
        ``__call__`` reads — ``lower().compile()`` would warm the trace
        cache but leave the first streaming call paying the XLA compile
        (see ``delta._warm_call``)."""
        S = jax.ShapeDtypeStruct
        w = self.w
        _delta._warm_call(
            self._jitted(jax.tree.structure(indices_sds)),
            indices_sds, S((w, int(chunk), int(width)), jnp.int32),
            S((w,), jnp.int32), S((w, int(chunk)), jnp.int32))


def build_distributed_program(plan: Plan, dcfg: DistConfig, mesh: Mesh
                              ) -> DistributedProgram:
    """Build one :class:`DistributedProgram` (kept as the stable public
    constructor — callers treat the result as a callable)."""
    return DistributedProgram(plan, dcfg, mesh)


# ---------------------------------------------------------------------------
# compiled-program cache: one shard_map program per (plan, config, mesh)
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: Dict[Tuple[Plan, "DistConfig", Mesh], object] = {}
_PROGRAM_BUILDS = 0  # monotonic build counter (cache-hit assertions in tests)


def get_distributed_program(plan: Plan, dcfg: DistConfig, mesh: Mesh):
    """The process-wide compiled-program cache.  Plans, configs and meshes
    all hash structurally, so every engine/session asking for the same
    (plan, config, mesh) triple shares ONE shard_map program — and with the
    pow2-padded region/seed shapes, one XLA executable."""
    global _PROGRAM_BUILDS
    key = (plan, dcfg, mesh)
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        _PROGRAM_BUILDS += 1
        prog = build_distributed_program(plan, dcfg, mesh)
        _PROGRAM_CACHE[key] = prog
    return prog


def deal_seed(seed: np.ndarray, weights: np.ndarray, w: int,
              width: int = 2, floor: int = 0
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Round-robin deal of a seed batch across ``w`` workers, padded to a
    stable pow2 per-worker chunk (keeps the jitted program's shapes — and
    hence its compile cache — warm across epochs).  ``width`` is the seed
    prefix width (``plan.seed_width``); ``floor`` raises the chunk to a
    ratcheted rung so every delta epoch of a stream shares ONE seed shape
    (the delta engine pins it to the update-batch bound)."""
    seed = np.asarray(seed, np.int32).reshape(-1, width)
    weights = np.asarray(weights, np.int32)
    per = -(-seed.shape[0] // w)
    S = max(_delta._pow2(per), int(floor))
    chunks = np.zeros((w, S, width), np.int32)
    wchunks = np.zeros((w, S), np.int32)
    seed_n = np.zeros(w, np.int32)
    for k in range(w):
        rows = seed[k::w]
        chunks[k, :rows.shape[0]] = rows
        wchunks[k, :rows.shape[0]] = weights[k::w]
        seed_n[k] = rows.shape[0]
    return chunks, seed_n, wchunks


def run_program(program, w: int, collect: bool, indices,
                seed: np.ndarray, weights: np.ndarray, width: int = 2,
                seed_floor: int = 0):
    """Deal the seed, launch one compiled program, unpack psum'd outputs."""
    with jax.profiler.TraceAnnotation("dataflow.run"):
        return _run_program(program, w, collect, indices, seed, weights,
                            width, seed_floor)


def _run_program(program, w, collect, indices, seed, weights, width,
                 seed_floor):
    faults.fire("dist.program")
    chunks, seed_n, wchunks = deal_seed(seed, weights, w, width,
                                        floor=seed_floor)
    out = program(indices, jnp.asarray(chunks), jnp.asarray(seed_n),
                  jnp.asarray(wchunks))
    reads = hostread.Reads()
    mask = hostread.read("scalars", reads, int, out[4])
    if mask:
        raise CapacityOverflow(mask, where="distributed join",
                               detail=f"w={w} seed_floor={seed_floor}")
    tuples = wts = None
    if collect:
        bufs, ws, ns = (hostread.read("collect", reads, np.asarray, out[i])
                        for i in (7, 8, 9))
        tuples = np.concatenate([bufs[i, :ns[i]] for i in range(w)])
        wts = np.concatenate([ws[i, :ns[i]] for i in range(w)])
    count, proposals, intersections, steps = (
        hostread.read("scalars", reads, int, out[i]) for i in range(4))
    return JoinResult(count, tuples, wts, proposals, intersections, steps,
                      reads.host_reads, reads.host_read_bytes)


@dataclasses.dataclass
class DistJoinResult:
    count: int
    proposals: int
    intersections: int
    steps: int
    max_load: int = 0  # max over workers of requests served (Thm 3.4)
    mean_load: float = 0.0
    tuples: Optional[np.ndarray] = None
    weights: Optional[np.ndarray] = None


def distributed_join(plan: Plan, relations: Dict[str, np.ndarray],
                     mesh: Optional[Mesh] = None,
                     cfg: Optional[DistConfig] = None) -> DistJoinResult:
    """End-to-end distributed static join on the given worker mesh."""
    from repro.core.bigjoin import seed_tuples_for
    if mesh is None:
        devs = np.array(jax.devices())
        if cfg is not None:  # honor the caller's worker count on the
            devs = devs[:cfg.num_workers]  # default mesh (w <= devices)
        mesh = Mesh(devs, (AXIS,))
    w = mesh.shape[AXIS]
    if cfg is None:
        base = BigJoinConfig(batch=1024, mode="count")
        cfg = DistConfig(base, w, route_capacity=max(1024 // w, 16) * 4)
    assert cfg.num_workers == w
    indices = partition_indices(plan, relations, w)
    seed = seed_tuples_for(plan, relations)
    sw = plan.seed_width
    per = -(-seed.shape[0] // w)
    pad = np.zeros((per * w - seed.shape[0], sw), np.int32)
    chunks = np.concatenate([seed, pad]).reshape(w, per, sw)
    seed_n = np.full(w, per, np.int32)
    seed_n[-1] = per - pad.shape[0]
    run = build_distributed_program(plan, cfg, mesh)
    out = run(indices, jnp.asarray(chunks), jnp.asarray(seed_n),
              jnp.ones((w, per), jnp.int32))
    if int(out[4]):
        raise CapacityOverflow(int(out[4]), where="distributed static join")
    res = DistJoinResult(int(out[0]), int(out[1]), int(out[2]), int(out[3]),
                         int(out[5]), float(out[6]) / w)
    if cfg.base.mode == "collect":
        bufs, wts, ns = (np.asarray(out[7]), np.asarray(out[8]),
                         np.asarray(out[9]))
        res.tuples = np.concatenate([bufs[i, :ns[i]] for i in range(w)])
        res.weights = np.concatenate([wts[i, :ns[i]] for i in range(w)])
    return res


# ---------------------------------------------------------------------------
# Distributed Delta-BiGJoin (§4): streaming maintenance on the mesh
# ---------------------------------------------------------------------------

def default_delta_config(w: int, batch: int = 1024,
                         mode: str = "collect",
                         out_capacity: int = 1 << 18,
                         balance: bool = False,
                         use_kernel: bool = True,
                         axis=AXIS) -> DistConfig:
    """A DistConfig sized for delta workloads: generous route capacity (the
    deferral backpressure still guarantees correctness if exceeded) and the
    PR-1 fused-kernel default inherited by the delta path."""
    base = BigJoinConfig(batch=batch, seed_chunk=batch, mode=mode,
                         out_capacity=out_capacity, use_kernel=use_kernel)
    return DistConfig(base, w, route_capacity=max(4 * batch // w, 64),
                      balance=balance, axis=axis)


def make_delta_monitor(query, initial_edges, local: bool = False,
                       batch: int = 2048, out_capacity: int = 1 << 20,
                       balance: bool = False, mesh: Optional[Mesh] = None):
    """Deprecated: use :class:`repro.api.GraphSession` — one session owns the
    graph and serves many standing queries off a single commit per epoch.
    Kept as a thin wrapper for old callers; selects the host-local
    :class:`~repro.core.delta.DeltaBigJoin` or mesh-backed
    :class:`DistDeltaBigJoin` with matching B'/output budgets."""
    import warnings
    warnings.warn(
        "make_delta_monitor is deprecated; use repro.api.GraphSession "
        "(register() one or more queries, update() once per epoch)",
        DeprecationWarning, stacklevel=2)
    if local:
        cfg = BigJoinConfig(batch=batch, seed_chunk=batch, mode="collect",
                            out_capacity=out_capacity)
        return _delta.DeltaBigJoin(query, initial_edges, cfg=cfg)
    w = (jax.device_count() if mesh is None else
         int(np.prod([mesh.shape[a] for a in mesh.axis_names])))
    return DistDeltaBigJoin(
        query, initial_edges, mesh=mesh,
        dcfg=default_delta_config(w, batch=batch,
                                  out_capacity=out_capacity,
                                  balance=balance))


class DistDeltaBigJoin(_delta.DeltaBigJoin):
    """Delta-BiGJoin where every region shard lives on a mesh worker.

    Inherits the epoch bookkeeping of :class:`repro.core.delta.
    DeltaBigJoin` (normalize / commit / compaction semantics are identical —
    asserted by the differential stress suite) and overrides only the
    worker layout:

    - every ``_Regions`` multi-version projection is hash-partitioned by
      packed key over the mesh workers (``csr.build_sharded_index``), so
      each region entry has exactly one owner and cluster memory is
      O(IN + delta) — the paper's memory-linearity carried over to the
      maintained setting.  The per-epoch commit folds run shard-local
      (ownership is by key, so a delta entry and the committed entry it
      cancels always share a worker): ``delta._commit_fold`` vmaps the
      sorted-merge over the worker axis with no collectives, and each
      worker folds only its owned rows;
    - each delta query dAQ_i seeds its SIGNED dR batch round-robin across
      workers and runs the request/response dataflow of §3.4
      (``build_dist_step`` / ``build_balanced_step`` under ``balance``),
      with counts and outputs psum-merged;
    - the per-plan shard_map program is built once and jit-cached; pow2
      region/seed padding keeps its shapes stable across epochs, so
      steady-state monitoring never re-lowers.
    """

    def __init__(self, query, initial_edges, mesh: Optional[Mesh] = None,
                 dcfg: Optional[DistConfig] = None,
                 compact_ratio: float = 0.5,
                 store: Optional[_delta.RegionStore] = None,
                 device_resident: bool = True):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (AXIS,))
        self.mesh = mesh
        self.w = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        if dcfg is None:
            dcfg = default_delta_config(self.w)
        axes = dcfg.axis if isinstance(dcfg.axis, tuple) else (dcfg.axis,)
        if dcfg.num_workers != self.w or set(axes) != set(mesh.axis_names):
            raise ValueError(
                "dcfg does not match the mesh: "
                f"{dcfg.num_workers} workers on axes {axes} vs mesh "
                f"{dict(mesh.shape)}")
        if store is not None and store.shard_w != self.w:
            raise ValueError(
                f"shared store is sharded over {store.shard_w} workers, "
                f"mesh has {self.w}")
        if store is not None and store.mesh not in (None, mesh):
            raise ValueError("shared store is placed on another mesh")
        self.dcfg = dcfg
        self._programs: Dict[int, object] = {}
        super().__init__(query, initial_edges, cfg=dcfg.base,
                         compact_ratio=compact_ratio, store=store,
                         device_resident=device_resident)

    def _new_store(self, edges, compact_ratio):
        return _delta.RegionStore(edges, shard_w=self.w, mesh=self.mesh,
                                  compact_ratio=compact_ratio,
                                  device_resident=self.device_resident)

    def _run_plan(self, plan, indices, seed, weights):
        pi = self.plans.index(plan)
        if pi not in self._programs:
            self._programs[pi] = get_distributed_program(
                plan, self.dcfg, self.mesh)
        # the per-worker seed chunk rides its own ratcheted rung so every
        # epoch of a stream launches ONE program signature (prewarm pins
        # the mark at the update-batch bound; _static_eval full-graph
        # seeds deliberately bypass this key)
        width = plan.seed_width
        per = -(-seed.shape[0] // self.w)
        floor = self.store.ratchet.capacity(("seed", width), per)
        return run_program(self._programs[pi], self.w,
                           self.dcfg.base.mode == "collect", indices,
                           seed, weights, width=width, seed_floor=floor)

    def _escalate(self, exc) -> None:
        """Mesh overflow recovery: grows the per-peer route tables too,
        and rebuilds the shard_map programs on the escalated DistConfig
        (program identity keys on the config, so the stale programs must
        be dropped before the replay)."""
        qn = self.query.name
        r = self.store.ratchet
        base, dcfg, changed = self.dcfg.base, self.dcfg, False
        if exc.kinds & ESCALATES_OUT:
            new_out = r.escalate(("cap", "out", qn),
                                 floor=base.out_capacity)
            base = dataclasses.replace(base, out_capacity=new_out)
            changed = True
        if exc.kinds & ESCALATES_BATCH:
            new_b = r.escalate(("cap", "batch", qn), floor=base.batch)
            base = dataclasses.replace(
                base, batch=new_b, seed_chunk=max(base.seed_chunk, new_b))
            changed = True
        if exc.kinds & ESCALATES_ROUTE:
            new_rt = r.escalate(("cap", "route", qn),
                                floor=dcfg.route_capacity)
            dcfg = dataclasses.replace(dcfg, route_capacity=new_rt)
            changed = True
        if not changed:
            raise exc
        if base is not self.dcfg.base:
            dcfg = dataclasses.replace(dcfg, base=base)
        self.dcfg = dcfg
        self.cfg = base
        self._programs.clear()
        self.store.stats.escalations += 1
        self._reprewarm()

    def prewarm(self, update_batch: int, horizon=None) -> int:
        """AOT-compile every (program, committed-rung) signature this
        engine's delta plans can request for batches ≤ ``update_batch``
        (the mesh half of ``GraphSession.prewarm``)."""
        ub = max(int(update_batch), 1)
        self._prewarm_args = (ub, horizon)
        snap = compilestats.snapshot()
        for pi, plan in enumerate(self.plans):
            if pi not in self._programs:
                self._programs[pi] = get_distributed_program(
                    plan, self.dcfg, self.mesh)
            prog = self._programs[pi]
            width = plan.seed_width
            per = -(-ub // self.w)
            chunk = self.store.ratchet.capacity(("seed", width), per)
            rels = {rel for _id, rel, *_ in plan.index_ids()}
            # reachable rung cross-product, not just the same-rung
            # diagonal — relations grow independently (delta._rung_combos)
            ladders = {rel: self.store.committed_ladder(rel, ub, horizon)
                       for rel in rels}
            for combo in _delta._rung_combos(ladders):
                prog.warm(self.store.indices_sds_for(plan, combo, ub),
                          chunk, width)
        return compilestats.since(snap)
