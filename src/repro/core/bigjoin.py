"""BiGJoin: the paper's dataflow primitive (§3.1) + join driver (§3.2) in JAX.

The adaptation is described in DESIGN.md §2: the paper's batching optimization
(§3.1.2) becomes the static shape itself.  Each *step* pops a window of the
deepest non-empty prefix queue and pushes at most ``B'`` proposals through

    count-minimization -> candidate proposal -> intersection

exactly as Fig. 2, with partially-extended prefixes resuming via their
``rem-ext`` offset (the paper's (p, min-c, min-i, rem-ext) quadruples).

Scheduling follows §3.2: always extend the *deepest* level with pending work,
which bounds every queue at O(B') entries (Lemma 3.1's memory invariant —
asserted by tests/test_bigjoin.py::test_queue_invariant).

All shapes are static; the step function is jit-compiled once per
(plan, config) and reused.  Weighted prefixes (+1/-1) make the same dataflow
serve Delta-BiGJoin (delta.py) without modification.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compilestats, hostread
from repro.core.dataflow_index import VersionedIndex
from repro.core.plan import Plan
from repro.errors import (CapacityOverflow, OVF_OUT, OVF_QUEUE, OVF_SEED)
from repro.kernels import on_default_path

Indices = Dict[str, VersionedIndex]


@dataclasses.dataclass(frozen=True)
class BigJoinConfig:
    """``batch`` is B' — the per-step proposal budget (§3.1.2).

    ``use_kernel`` (default on) routes membership probes through the
    multi-region intersect kernel, and each level's extension step through
    the fused Pallas pipeline (kernels/extend) when that family is on the
    default path (``repro.kernels.on_default_path``); ``kernel_interpret``
    overrides the platform gating (None = compiled on TPU, interpret
    elsewhere).  The jnp path (``use_kernel=False``) remains as oracle.
    """

    batch: int = 4096
    seed_chunk: int = 4096
    out_capacity: int = 1 << 20
    mode: str = "collect"  # "collect" | "count"
    use_kernel: bool = True  # member kernel (+ fused extend when on path)
    kernel_interpret: Optional[bool] = None  # None: platform detection

    def queue_capacity(self) -> int:
        return 2 * self.batch

    def __post_init__(self):
        assert self.mode in ("collect", "count")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LevelQueue:
    prefix: jax.Array  # [cap, width] int32
    k: jax.Array  # [cap] int32 — next extension offset (rem-ext cursor)
    weight: jax.Array  # [cap] int32
    size: jax.Array  # [] int32

    def tree_flatten(self):
        return (self.prefix, self.k, self.weight, self.size), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BigJoinState:
    queues: Tuple[LevelQueue, ...]  # widths 2..m-1
    out_buf: jax.Array  # [Ocap, m] int32 (or [1, m] in count mode)
    out_weight: jax.Array  # [Ocap] int32
    out_n: jax.Array  # [] int32 rows used in out_buf
    out_count: jax.Array  # [] int64 weighted output count
    overflow: jax.Array  # [] int32 — OVF_* bitmask (repro.errors); stays 0
    proposals: jax.Array  # [] int64 work counter
    intersections: jax.Array  # [] int64 work counter
    recv_load: jax.Array  # [] int64 — requests served (distributed only)

    def tree_flatten(self):
        return (self.queues, self.out_buf, self.out_weight, self.out_n,
                self.out_count, self.overflow, self.proposals,
                self.intersections, self.recv_load), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def make_state(plan: Plan, cfg: BigJoinConfig,
               seed_capacity: Optional[int] = None) -> BigJoinState:
    m = plan.query.num_attrs
    sw = plan.seed_width
    queues = []
    for width in range(sw, m):
        cap = (seed_capacity or cfg.seed_chunk) if width == sw \
            else cfg.queue_capacity()
        queues.append(LevelQueue(
            jnp.zeros((cap, width), jnp.int32),
            jnp.zeros(cap, jnp.int32),
            jnp.zeros(cap, jnp.int32),
            jnp.asarray(0, jnp.int32)))
    ocap = cfg.out_capacity if cfg.mode == "collect" else 1
    return BigJoinState(
        tuple(queues),
        jnp.zeros((ocap, m), jnp.int32),
        jnp.zeros(ocap, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int64),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int64),
        jnp.asarray(0, jnp.int64),
        jnp.asarray(0, jnp.int64))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _pack_cols(prefix: jax.Array, positions: Sequence[int], dtype):
    """Pack prefix columns into a probe key via the ONE shared packer
    (``csr.pack_key``): a single array cast to the index key dtype, or the
    (hi, lo) int64 pair for 3-4 bound columns (composite indices)."""
    from repro.core import csr
    packed = csr.pack_key(tuple(prefix[:, p] for p in positions))
    if isinstance(packed, tuple):
        return packed
    return packed.astype(dtype)


def _binding_key(prefix: jax.Array, bound_attrs: Sequence[int],
                 key_attrs: Sequence[int], idx: VersionedIndex):
    pos = [list(bound_attrs).index(a) for a in key_attrs]
    return _pack_cols(prefix, pos, idx.pos[0].key.dtype)


def _compact(arrays, keep: jax.Array):
    """Stable-partition rows with keep=True to the front; returns new size."""
    perm = jnp.argsort(~keep, stable=True)
    return [a[perm] for a in arrays], keep.sum().astype(jnp.int32)


def _scatter_append(dst: jax.Array, size: jax.Array, src: jax.Array,
                    alive: jax.Array):
    """Append alive rows of src to dst at [size, ...); returns (dst, n, ovf)."""
    cap = dst.shape[0]
    cum = (jnp.cumsum(alive.astype(jnp.int32), dtype=jnp.int32)
           - alive.astype(jnp.int32))
    dest = jnp.where(alive, size + cum, cap)  # cap => dropped
    n_new = alive.sum().astype(jnp.int32)
    ovf = (size + n_new) > cap
    return dst.at[dest].set(src, mode="drop"), n_new, ovf


# ---------------------------------------------------------------------------
# the dataflow step
# ---------------------------------------------------------------------------

def _level_branch(plan: Plan, cfg: BigJoinConfig, li: int):
    """Build the pop→count-min→propose→intersect→push branch for level li.

    With ``cfg.use_kernel`` and the extend family on the default path, the
    count-min/propose/intersect middle runs as ONE fused ``pallas_call``
    (kernels/extend): proposals are born, gathered and membership-filtered
    in VMEM without HBM round-trips between stages.  Otherwise the jnp
    stage sequence below runs (the bit-exact oracle), its intersections
    through one multi-region member kernel launch per binding.
    """
    lv = plan.levels[li]
    m = plan.query.num_attrs
    B = cfg.batch
    is_last = li == len(plan.levels) - 1
    new_bound = lv.bound_attrs + (lv.ext_attr,)

    def middle_fused(wprefix, wk, valid, indices):
        from repro.kernels.extend.ops import fused_extend
        qks, pos, neg = [], [], []
        for b in lv.bindings:
            idx = indices[b.index_id]
            qks.append(_binding_key(wprefix, lv.bound_attrs, b.key_attrs,
                                    idx))
            pos.append(idx.pos)
            neg.append(idx.neg)
        cand, r, alive, allowed, consumed, counters = fused_extend(
            tuple(pos), tuple(neg), tuple(qks), wk, valid, B,
            interpret=cfg.kernel_interpret)
        return (cand, r, alive, allowed, consumed,
                counters[0].astype(jnp.int64), counters[1].astype(jnp.int64))

    def middle_jnp(wprefix, wk, valid, indices):
        # ---- count minimization (one pass per binding, Fig 2 "Count") ----
        starts_b, counts_b, totals = [], [], []
        for b in lv.bindings:
            idx = indices[b.index_id]
            qk = _binding_key(wprefix, lv.bound_attrs, b.key_attrs, idx)
            s, c = idx.ranges(qk)
            starts_b.append(s)
            counts_b.append(c)
            totals.append(c.sum(-1))
        tot = jnp.stack(totals, -1)  # [W, NB]
        min_i = jnp.argmin(tot, -1).astype(jnp.int32)
        min_c = tot.min(-1)
        W = wk.shape[0]

        # ---- proposal budget allocation (rem-ext resumption) -------------
        remaining = jnp.where(valid, jnp.maximum(min_c - wk, 0), 0)
        acum = jnp.cumsum(remaining, dtype=jnp.int32)
        allowed = jnp.clip(B - (acum - remaining), 0, remaining
                           ).astype(jnp.int32)
        consumed = valid & (allowed == remaining)

        aacum = jnp.cumsum(allowed, dtype=jnp.int32)
        t = jnp.arange(B, dtype=jnp.int32)
        pvalid = t < aacum[-1]
        r = jnp.clip(jnp.searchsorted(aacum, t, side="right"), 0, W - 1)
        r = r.astype(jnp.int32)
        k_off = t - (aacum[r] - allowed[r]) + wk[r]

        # ---- candidate proposal (Fig 2 "Proposal") ------------------------
        cand = jnp.zeros(B, jnp.int32)
        for bi, b in enumerate(lv.bindings):
            idx = indices[b.index_id]
            v = idx.gather(starts_b[bi][r], counts_b[bi][r], k_off)
            cand = jnp.where(min_i[r] == bi, v, cand)
        new_prefix = jnp.concatenate([wprefix[r], cand[:, None]], axis=1)
        alive = pvalid
        n_proposed = pvalid.sum().astype(jnp.int64)

        # ---- intersection (Fig 2 "Intersect") -----------------------------
        n_isect = jnp.asarray(0, jnp.int64)
        for bi, b in enumerate(lv.bindings):
            idx = indices[b.index_id]
            pos = [list(new_bound).index(a) for a in b.key_attrs]
            qk = _pack_cols(new_prefix, pos, idx.pos[0].key.dtype)
            is_min = min_i[r] == bi
            mem, dele = idx.signed_member(qk, cand, cfg.use_kernel,
                                          cfg.kernel_interpret)
            ok = jnp.where(is_min, ~dele, mem)
            n_isect = n_isect + (alive & ~is_min).sum().astype(jnp.int64)
            alive = alive & ok
        return cand, r, alive, allowed, consumed, n_proposed, n_isect

    def branch(state: BigJoinState, indices: Indices) -> BigJoinState:
        qu = state.queues[li]
        W = min(B, qu.prefix.shape[0])
        wprefix, wk = qu.prefix[:W], qu.k[:W]
        wweight = qu.weight[:W]
        valid = jnp.arange(W, dtype=jnp.int32) < qu.size

        use_fused = cfg.use_kernel and on_default_path("extend")
        if use_fused:
            from repro.kernels.intersect.ops import (default_interpret,
                                                     fused_fits)
            regions = [reg for b in lv.bindings
                       for reg in (indices[b.index_id].pos
                                   + indices[b.index_id].neg)]
            # compiled path: drop to the jnp oracle when the level's regions
            # (composite lo word tiles included — fused_fits counts their
            # 8 B/slot) cannot be VMEM-resident (DESIGN.md §3), rather than
            # failing Mosaic
            use_fused = (default_interpret(cfg.kernel_interpret)
                         or fused_fits(regions, B))
        middle = middle_fused if use_fused else middle_jnp
        (cand, r, alive, allowed, consumed, n_proposed,
         n_isect) = middle(wprefix, wk, valid, indices)
        new_prefix = jnp.concatenate([wprefix[r], cand[:, None]], axis=1)
        weight = wweight[r]
        for f in lv.filters:
            lo = new_prefix[:, list(new_bound).index(f.lo)]
            hi = new_prefix[:, list(new_bound).index(f.hi)]
            alive = alive & (lo < hi)

        # ---- retire consumed prefixes from this queue ---------------------
        kfull = qu.k.at[:W].set(wk + allowed)
        live_row = jnp.arange(qu.prefix.shape[0], dtype=jnp.int32) < qu.size
        keep = live_row & ~jnp.pad(consumed, (0, qu.prefix.shape[0] - W))
        (pfx, kk, ww), nsz = _compact([qu.prefix, kfull, qu.weight], keep)
        queues = list(state.queues)
        queues[li] = LevelQueue(pfx, kk, ww, nsz)

        out_buf, out_weight = state.out_buf, state.out_weight
        out_n, out_count = state.out_n, state.out_count
        overflow = state.overflow
        if is_last:
            out_count = out_count + (weight * alive).sum().astype(jnp.int64)
            if cfg.mode == "collect":
                perm = np.argsort(np.asarray(plan.attr_order))
                rows = new_prefix[:, perm]
                out_buf, n_new, ovf1 = _scatter_append(
                    out_buf, out_n, rows, alive)
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
                jnp.minimum(nxt.size + n_new, jnp.int32(nxt.prefix.shape[0])))
            overflow = overflow | jnp.where(ovf1, OVF_QUEUE, 0)

        return BigJoinState(
            tuple(queues), out_buf, out_weight, out_n, out_count, overflow,
            state.proposals + n_proposed.astype(jnp.int64),
            state.intersections + n_isect, state.recv_load)

    return branch


def build_step(plan: Plan, cfg: BigJoinConfig):
    """One scheduler step: extend the deepest non-empty level (§3.2)."""
    branches = [_level_branch(plan, cfg, li)
                for li in range(len(plan.levels))]
    if not branches:
        # the seed covers every attribute (single-atom delta plans): seeds
        # go straight to output in the seed step; there is nothing to drain
        def step(state: BigJoinState, indices: Indices) -> BigJoinState:
            compilestats.record("bigjoin.step")
            return state

        return step

    def step(state: BigJoinState, indices: Indices) -> BigJoinState:
        compilestats.record("bigjoin.step")
        sizes = jnp.stack([q.size for q in state.queues])
        nz = sizes > 0
        deepest = (len(branches) - 1
                   - jnp.argmax(nz[::-1]).astype(jnp.int32))
        deepest = jnp.clip(deepest, 0, len(branches) - 1)
        return jax.lax.switch(deepest, branches, state, indices)

    return step


def build_seed_step(plan: Plan, cfg: BigJoinConfig):
    """Enqueue a chunk of P_w seed prefixes, applying seed filters (§4.2).

    Width 2 for projection-seeded static plans; an n-ary delta plan seeds
    its full dR_i tuples directly into the width-r queue.  When the seed
    covers EVERY attribute (single-atom delta plans) filtered seeds go
    straight to the output buffer — there are no extension levels.
    """

    def seed_step(state: BigJoinState, indices: Indices, prefixes: jax.Array,
                  weights: jax.Array, valid: jax.Array) -> BigJoinState:
        compilestats.record("bigjoin.seed_step")
        alive = valid
        bound = tuple(plan.attr_order[:plan.seed_width])
        for b in plan.seed_filters:
            idx = indices[b.index_id]
            qk = _binding_key(prefixes, bound, b.key_attrs, idx)
            qv = prefixes[:, bound.index(b.ext_attr)]
            alive = alive & idx.member(qk, qv, cfg.use_kernel,
                                       cfg.kernel_interpret)
        for f in plan.seed_ineq:
            alive = alive & (prefixes[:, bound.index(f.lo)]
                             < prefixes[:, bound.index(f.hi)])
        if not plan.levels:  # seed covers all attrs: direct output
            weights = weights.astype(jnp.int32)
            out_count = state.out_count + (
                weights * alive).sum().astype(jnp.int64)
            out_buf, out_weight = state.out_buf, state.out_weight
            out_n, overflow = state.out_n, state.overflow
            if cfg.mode == "collect":
                perm = np.argsort(np.asarray(plan.attr_order))
                out_buf, n_new, ovf = _scatter_append(
                    out_buf, out_n, prefixes[:, perm], alive)
                out_weight, _, _ = _scatter_append(
                    out_weight, out_n, weights, alive)
                out_n = jnp.minimum(out_n + n_new,
                                    jnp.int32(out_buf.shape[0]))
                overflow = overflow | jnp.where(ovf, OVF_OUT, 0)
            return dataclasses.replace(
                state, out_buf=out_buf, out_weight=out_weight, out_n=out_n,
                out_count=out_count, overflow=overflow)
        q0 = state.queues[0]
        npfx, n_new, ovf = _scatter_append(q0.prefix, q0.size, prefixes, alive)
        nk, _, _ = _scatter_append(
            q0.k, q0.size, jnp.zeros(prefixes.shape[0], jnp.int32), alive)
        nw, _, _ = _scatter_append(q0.weight, q0.size, weights, alive)
        queues = list(state.queues)
        queues[0] = LevelQueue(
            npfx, nk, nw,
            jnp.minimum(q0.size + n_new, jnp.int32(q0.prefix.shape[0])))
        return dataclasses.replace(
            state, queues=tuple(queues),
            overflow=state.overflow | jnp.where(ovf, OVF_SEED, 0))

    return seed_step


@functools.lru_cache(maxsize=64)
def _compiled_fns(plan: Plan, cfg: BigJoinConfig):
    return (jax.jit(build_step(plan, cfg)),
            jax.jit(build_seed_step(plan, cfg)))


@dataclasses.dataclass
class JoinResult:
    count: int  # weighted output count
    tuples: Optional[np.ndarray]  # [N, m] in attribute order (collect mode)
    weights: Optional[np.ndarray]
    proposals: int
    intersections: int
    steps: int
    host_reads: int = 0  # blocking device->host reads of the run
    host_read_bytes: int = 0


def run_bigjoin(plan: Plan, indices: Indices, seed: np.ndarray,
                weights: Optional[np.ndarray] = None,
                cfg: BigJoinConfig = BigJoinConfig()) -> JoinResult:
    """Host driver: feed seed chunks, drain the dataflow to completion."""
    with jax.profiler.TraceAnnotation("dataflow.run"):
        return _run_bigjoin(plan, indices, seed, weights, cfg)


def _run_bigjoin(plan: Plan, indices: Indices, seed: np.ndarray,
                 weights: Optional[np.ndarray], cfg: BigJoinConfig
                 ) -> JoinResult:
    step, seed_step = _compiled_fns(plan, cfg)
    state = make_state(plan, cfg)
    seed = np.asarray(seed, np.int32).reshape(-1, plan.seed_width)
    if weights is None:
        weights = np.ones(seed.shape[0], np.int32)
    weights = np.asarray(weights, np.int32)
    reads = hostread.Reads()
    S = cfg.seed_chunk
    nsteps = 0
    for lo in range(0, max(seed.shape[0], 1), S):
        chunk = seed[lo:lo + S]
        wchunk = weights[lo:lo + S]
        n = chunk.shape[0]
        if n == 0:
            continue
        pad = S - n
        chunk = np.pad(chunk, ((0, pad), (0, 0)))
        wchunk = np.pad(wchunk, (0, pad))
        vmask = np.arange(S) < n
        state = seed_step(state, indices, jnp.asarray(chunk),
                          jnp.asarray(wchunk), jnp.asarray(vmask))
        while True:
            sizes = [hostread.read("queues", reads, int, q.size)
                     for q in state.queues]
            if not any(s > 0 for s in sizes):
                break
            state = step(state, indices)
            nsteps += 1
    mask = hostread.read("scalars", reads, int, state.overflow)
    if mask:
        raise CapacityOverflow(
            mask, where="local bigjoin",
            detail=f"batch={cfg.batch} out_capacity={cfg.out_capacity}")
    tuples = wts = None
    if cfg.mode == "collect":
        n = hostread.read("scalars", reads, int, state.out_n)
        tuples = hostread.read("collect", reads, np.asarray,
                               state.out_buf)[:n]
        wts = hostread.read("collect", reads, np.asarray,
                            state.out_weight)[:n]
    count, proposals, intersections = (
        hostread.read("scalars", reads, int, x)
        for x in (state.out_count, state.proposals, state.intersections))
    return JoinResult(count, tuples, wts, proposals, intersections, nsteps,
                      reads.host_reads, reads.host_read_bytes)


def build_indices(plan: Plan, relations: Dict[str, np.ndarray],
                  capacity_slack: float = 1.0) -> Indices:
    """Static VersionedIndex per plan index id (version 'static' only)."""
    from repro.core.csr import build_index
    out: Indices = {}
    for index_id, rel, key_pos, ext_pos, version in plan.index_ids():
        if version != "static":
            raise ValueError("use delta.DeltaIndexStore for delta plans")
        tuples = np.asarray(relations[rel])
        cap = max(int(tuples.shape[0] * capacity_slack), 1)
        out[index_id] = VersionedIndex.static(
            build_index(tuples, key_pos, ext_pos, cap))
    return out


def seed_tuples_for(plan: Plan, relations: Dict[str, np.ndarray]
                    ) -> np.ndarray:
    rel = np.asarray(relations[plan.query.atoms[plan.seed_atom].rel])
    return np.unique(rel[:, list(plan.seed_cols)], axis=0).astype(np.int32)
