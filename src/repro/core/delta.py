"""Delta-GJ / Delta-BiGJoin (§3.3): incremental maintenance of join queries.

For each update batch dR (signed edge tuples) the engine runs the n delta
queries

    dQ_i :- R'_1, ..., R'_{i-1}, dR_i, R_{i+1}, ..., R_n

each through the *same* BiGJoin dataflow (bigjoin.py), seeded with dR_i and
planned with an attribute order that begins with R_i's attributes (Thm 3.2).
Atoms left of the seed read the NEW version, atoms right of it the OLD
version — the logical sequencing that makes simultaneous updates correct.

The multi-version index is the paper's three-region LSM structure (§4.3):

    base   — compacted committed state (large, device-resident)
    cins/cdel — uncompacted committed inserts/deletes since last compaction
    uins/udel — the current (uncommitted) batch

OLD = base + cins - cdel;   NEW = OLD + uins - udel.

Commit folds uins/udel into cins/cdel with cancellation, keeping the
invariants  cins ∩ base = ∅,  cdel ⊆ base,  cins ∩ cdel = ∅  so positive
regions never hold duplicates.  Compaction (merge committed into base) runs
when the committed regions exceed ``compact_ratio`` × |base| — and eagerly in
the rare re-insertion-of-committed-delete case, which would otherwise create
a positive/negative overlap (see DESIGN.md §2).

Region state is DEVICE-RESIDENT (DESIGN.md §6): each live relation is a
sorted packed device array maintained as its own three-region LSM,
``normalize`` is a jitted searchsorted membership probe against it, and
``commit`` is a jitted sorted-merge/diff fold (``csr.merge_index`` /
``diff_index`` / ``intersect_index``) that touches only the committed
regions and the delta — the compacted base is merged at (amortized)
compaction only, so warm epoch cost is O(|Δ|·log|R| + |committed|) instead
of the full rescan the host path pays.  Host numpy arrays are a
lazily-materialized debug mirror, pulled only by oracle/differential paths
(``StoreStats.mirror_pulls`` counts the pulls).  ``device_resident=False``
keeps the legacy host-truth store (with an incrementally-maintained packed
live cache) for contrast benchmarks.

The store is MULTI-RELATION (DESIGN.md §7): any mix of dynamic relations
of arity 2..4 (the binary ``edge`` graph, the ternary ``tri`` relation of
§5.4, ...), each with its own live LSM, per-relation update batches, and
composite-key (hi, lo) regions sharded by the same ownership hash as the
binary ones.  Projections that don't cover a relation's full row are
DERIVED on demand instead of folded (see :class:`_Regions`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults
from repro.core import compilestats, csr, hostread
from repro.core.bigjoin import (BigJoinConfig, Indices, JoinResult,
                                run_bigjoin)
from repro.core.capacity import Ratchet
from repro.core.csr import IndexData, build_index
from repro.core.dataflow_index import VersionedIndex
from repro.core.plan import Plan, make_delta_plan
from repro.core.query import Query, delta_queries
from repro.errors import (CapacityOverflow, ESCALATES_BATCH, ESCALATES_OUT,
                          SnapshotError)
from repro.kernels import on_default_path

Projection = Tuple[str, Tuple[int, ...], int]  # (rel, key_pos, ext_pos)

# With the strict flag every jitted device step of the store runs under
# ``jax.transfer_guard("disallow")``: any host<->device copy on the warm
# normalize/commit path raises instead of silently re-uploading an index.
# The CI transfer-guard lane sets this for the delta-stream suites; the
# delta-sized staging uploads and scalar count pulls happen OUTSIDE the
# guarded scopes by construction (they are proportional to |Δ|, not |E|).
STRICT_TRANSFERS = os.environ.get("REPRO_STRICT_TRANSFERS", "") not in ("",
                                                                        "0")

# Merge/fold kernel routing for the commit path: None = the static choice of
# ``repro.kernels.on_default_path`` (the fused commit fold and the rank
# kernel are off it: neither compiles for TPU), True/False force — tests
# force True to exercise the kernels in interpret mode.  With the kernels
# forced on, the commit fold takes the single-launch fused kernel
# (kernels/merge/fold) when its operands fit the VMEM budget, sharded meshes
# included (the kernel grids over the worker axis); the compaction fold
# keeps the rank-kernel-per-op chain, jnp when sharded (vmap-of-pallas is
# not a supported production path).
USE_MERGE_KERNEL: Optional[bool] = None


def _merge_kernel_on() -> bool:
    if USE_MERGE_KERNEL is None:
        return on_default_path("fold") and on_default_path("rank")
    return bool(USE_MERGE_KERNEL)


@contextlib.contextmanager
def _device_scope():
    if STRICT_TRANSFERS:
        with jax.transfer_guard("disallow"):
            yield
    else:
        yield


def _pack2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return (a.astype(np.int64) << 32) | b.astype(np.int64)


def _unpack2(packed: np.ndarray) -> np.ndarray:
    packed = np.asarray(packed, np.int64)
    return np.stack([(packed >> 32).astype(np.int32),
                     (packed & 0xFFFFFFFF).astype(np.int32)], 1)


def _pack_rows(rows: np.ndarray, arity: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Full rows of an n-ary relation as the (hi, lo) lex word pair the
    live-set LSM keys on (lo ≡ 0 for arity <= 2, matching the legacy
    single-word packing bit for bit)."""
    rows = np.asarray(rows, np.int32).reshape(-1, arity)
    packed = csr.pack_key(tuple(rows[:, c] for c in range(arity)))
    if isinstance(packed, tuple):
        return packed
    return packed, np.zeros(rows.shape[0], np.int64)


def _unpack_rows(hi: np.ndarray, lo: np.ndarray, arity: int) -> np.ndarray:
    """Inverse of :func:`_pack_rows`: [N, arity] int32 rows."""
    if arity <= 2:
        return csr.unpack_key(np.asarray(hi, np.int64), arity)
    return csr.unpack_key((np.asarray(hi, np.int64),
                           np.asarray(lo, np.int64)), arity)


def _degenerate_rows(rows: np.ndarray) -> np.ndarray:
    """Rows with any repeated vertex (self-loops generalized to n-ary):
    normalize drops them, exactly as the edge path drops u == v."""
    rows = np.asarray(rows)
    bad = np.zeros(rows.shape[0], bool)
    for i in range(rows.shape[1]):
        for j in range(i + 1, rows.shape[1]):
            bad |= rows[:, i] == rows[:, j]
    return bad


def _check_batch(rel: str, updates, weights, arity: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Validate one relation's update batch: integer dtype, [N, arity]
    shape, non-negative int32-representable ids, matching weights — loud
    errors instead of the old silent ``reshape(-1, 2)`` mangling."""
    arr = np.asarray(updates)
    if arr.size == 0:  # empty batches are always a valid no-op
        arr = np.zeros((0, arity), np.int64)
    if not np.issubdtype(arr.dtype, np.integer):
        raise TypeError(
            f"{rel!r} update batch must be integer tuples, got dtype "
            f"{arr.dtype}")
    if arr.ndim != 2 or arr.shape[1] != arity:
        raise ValueError(
            f"{rel!r} update batch must be [N, {arity}] (relation arity "
            f"{arity}), got shape {arr.shape}")
    if arr.size:
        amin, amax = int(arr.min()), int(arr.max())
        if amin < 0:
            raise ValueError(
                f"{rel!r} update batch contains negative id {amin}")
        if amax >= 2 ** 31:
            raise ValueError(
                f"{rel!r} update batch contains id {amax} outside the "
                "int32 vertex-id domain")
    if weights is None:
        weights = np.ones(arr.shape[0], np.int32)
    w = np.asarray(weights)
    if not np.issubdtype(w.dtype, np.integer):
        raise TypeError(
            f"{rel!r} update weights must be signed integers, got dtype "
            f"{w.dtype}")
    if w.shape != (arr.shape[0],):
        raise ValueError(
            f"{rel!r} update weights must be [N] = [{arr.shape[0]}], got "
            f"shape {w.shape}")
    return arr.astype(np.int32), w.astype(np.int32)


def _pow2(n: int) -> int:
    """Index capacities rounded up to powers of two (>= one kernel segment):
    stable shapes across update batches keep the jitted dataflow's
    compilation cache warm, and SEG-aligned capacities make the kernels'
    segment-major view a free reshape.  Alias of THE canonical helper
    (``csr.pow2_capacity``) the sharded region builds and the session
    sizing use, so every capacity in the repo sits on one ladder."""
    return csr.pow2_capacity(n)


def _total(n) -> int:
    return int(np.sum(n))


def _maxn(n) -> int:
    return int(np.max(n)) if np.ndim(np.asarray(n)) else int(n)


def _count_of(d: IndexData, stats=None, site: str = "commit"):
    """Exact live count(s) of a device region: int single-host, [w] int64
    vector sharded.  One scalar/vector pull — never the index arrays; a
    counted ``read.<site>`` when ``stats`` (the store's) is given."""
    n = np.asarray(d.n) if stats is None else \
        hostread.read(site, stats, np.asarray, d.n)
    return n.astype(np.int64) if n.ndim else int(n)


# ---------------------------------------------------------------------------
# jitted device cores (called by RegionStore under _device_scope; all
# arguments are device arrays — no implicit transfers on the warm path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sharded",))
def _normalize_core(p_hi: jax.Array, p_lo: jax.Array, w: jax.Array,
                    base: IndexData, cins: IndexData, cdel: IndexData,
                    sharded: bool = False):
    """Net one padded update batch against a relation's live LSM:
    (ins_hi, ins_lo, n_ins, del_hi, del_lo, n_dels) as sentinel-padded
    sorted lex word pairs.

    p_hi/p_lo [B] int64 are the packed rows (degenerate/padding rows
    pre-masked to the sentinel on the host — the batch is delta-sized);
    base/cins/cdel: the relation's packed live regions (IndexData, val≡0;
    composite ``lo`` word for arity > 2), hash-partitioned over a leading
    [w] worker axis when ``sharded`` — a key lives on exactly one shard, so
    membership is an OR over vmapped per-shard probes and per-worker live
    memory stays O(|R|/w).  live = (base \\ cdel) ∪ cins under the commit
    invariants.
    """
    compilestats.record("delta.normalize_core")
    SENT = jnp.int64(csr.SENTINEL)
    order = jnp.lexsort((p_lo, p_hi))
    hs, ls, ws = p_hi[order], p_lo[order], w[order]
    first = jnp.concatenate([jnp.ones(1, bool),
                             (hs[1:] != hs[:-1]) | (ls[1:] != ls[:-1])])
    ids = jnp.cumsum(first.astype(jnp.int32)) - 1
    net = jax.ops.segment_sum(ws.astype(jnp.int64), ids,
                              num_segments=hs.shape[0])
    uniq_h = jnp.full(hs.shape[0], SENT, jnp.int64).at[ids].set(hs)
    uniq_l = jnp.full(hs.shape[0], SENT, jnp.int64).at[ids].set(ls)
    zeros = jnp.zeros(hs.shape[0], jnp.int32)
    composite = base.lo is not None  # static: arity > 2 relations
    qkey = (uniq_h, uniq_l) if composite else uniq_h

    def member(idx):
        if sharded:
            return jax.vmap(
                lambda d: csr.index_member(d, qkey, zeros))(idx).any(0)
        return csr.index_member(idx, qkey, zeros)

    in_base = member(base)
    in_cins = member(cins)
    in_cdel = member(cdel)
    exists = (in_base & ~in_cdel) | in_cins
    alive = uniq_h < SENT
    ins_m = alive & (net > 0) & ~exists
    del_m = alive & (net < 0) & exists

    def compact(mask):
        cum = jnp.cumsum(mask.astype(jnp.int32))
        pos = jnp.where(mask, cum - 1, mask.shape[0])
        oh = jnp.full(mask.shape[0], SENT, jnp.int64
                      ).at[pos].set(uniq_h, mode="drop")
        ol = jnp.full(mask.shape[0], SENT, jnp.int64
                      ).at[pos].set(uniq_l, mode="drop")
        return oh, ol, mask.sum(dtype=jnp.int32)

    oih, oil, ni = compact(ins_m)
    odh, odl, nd = compact(del_m)
    return oih, oil, ni, odh, odl, nd


def _commit_fold_impl(base: IndexData, cins: IndexData, cdel: IndexData,
                      uins: IndexData, udel: IndexData, *, cins_cap: int,
                      cdel_cap: int, sharded: bool, use_kernel: bool = False):
    """The committed-region fold of one epoch, merged never rebuilt:

        cins' = (cins \\ udel) ∪ (uins \\ cdel)
        cdel' = cdel ∪ (udel ∩ base)

    Touches only the committed regions and the delta — ``base`` is probed
    (O(|Δ|·log|base|)), never scanned.  ``sharded`` vmaps the fold over the
    leading worker axis: ownership is by packed key, so every merge is
    shard-local and the distributed commit stays collective-free.

    No input is donated: the old committed regions survive the fold, so
    a commit that fails midway rolls back to them (DESIGN.md §10).  The
    price is one committed-region generation of peak memory per epoch;
    donating ``cins``/``cdel`` would save it, but executables read back
    from the persistent compilation cache (always on, `compilestats`)
    mis-handled the in-place aliasing on the CPU mesh path (corrupted
    committed regions in an otherwise bit-identical run).

    ``use_kernel`` routes the whole fold — both outputs — through ONE
    fused ``pallas_call`` per relation (`kernels/merge/fold.py`): only the
    delta-sized ``udel ∩ base`` probe stays a jnp search (its bit vector is
    the kernel's ``in_ba`` input), so base never enters VMEM.  Folds the
    fused kernel cannot serve (over-VMEM compiled calls) fall back to the
    five-stage rank chain, bit-exactly.
    """
    compilestats.record("delta.commit_fold")
    if use_kernel:
        from repro.kernels.merge import fold as merge_fold
        if merge_fold.commit_fold_ok(cins, cdel, uins, udel,
                                     cins_cap, cdel_cap):
            def in_ba_of(ba, ud):
                lt, le = csr.index_ranks(ba, csr._qcols_of(ud), ud.val)
                return (le > lt).astype(jnp.int32)

            in_ba = (jax.vmap(in_ba_of)(base, udel) if sharded
                     else in_ba_of(base, udel))
            return merge_fold.commit_fold(
                cins, cdel, uins, udel, in_ba,
                cins_cap=cins_cap, cdel_cap=cdel_cap, sharded=sharded)
    # the rank-kernel chain stays single-host only: under the sharded vmap
    # each stage would relaunch per shard, which the fused path avoids
    chain_k = use_kernel and not sharded

    def fold(ba, ci, cd, ui, ud):
        kept = csr._select_core(ci, ud, ci.capacity, False, chain_k)
        fresh = csr._select_core(ui, cd, ui.capacity, False, chain_k)
        new_cins = csr._merge_core(kept, fresh, cins_cap, chain_k)
        dead = csr._select_core(ud, ba, ud.capacity, True, chain_k)
        new_cdel = csr._merge_core(cd, dead, cdel_cap, chain_k)
        return new_cins, new_cdel

    if sharded:
        return jax.vmap(fold)(base, cins, cdel, uins, udel)
    return fold(base, cins, cdel, uins, udel)


_commit_fold = functools.partial(
    jax.jit, static_argnames=("cins_cap", "cdel_cap", "sharded",
                              "use_kernel"))(_commit_fold_impl)


@functools.partial(jax.jit, static_argnames=("out_cap", "sharded",
                                             "use_kernel"))
def _compact_fold(base: IndexData, cins: IndexData, cdel: IndexData, *,
                  out_cap: int, sharded: bool, use_kernel: bool = False
                  ) -> IndexData:
    """base' = (base \\ cdel) ∪ cins — the amortized O(|base|) merge."""
    compilestats.record("delta.compact_fold")

    def fold(ba, ci, cd):
        kept = csr._select_core(ba, cd, ba.capacity, False, use_kernel)
        return csr._merge_core(kept, ci, out_cap, use_kernel)

    if sharded:
        return jax.vmap(fold)(base, cins, cdel)
    return fold(base, cins, cdel)


@functools.partial(jax.jit, static_argnames=("sharded",))
def _any_member(idx: IndexData, qk: jax.Array, qv: jax.Array,
                sharded: bool = False) -> jax.Array:
    """any((qk,qv) ∈ idx) — the eager re-insertion probe (delta-sized)."""
    compilestats.record("delta.any_member")
    if sharded:
        return jax.vmap(lambda d: csr.index_member(d, qk, qv))(idx).any()
    return csr.index_member(idx, qk, qv).any()


def _packed_index(rows: np.ndarray, shard_w: int = 0,
                  arity: int = 2, capacity: Optional[int] = None,
                  mesh=None) -> IndexData:
    """Packed full-row IndexData (key = the relation's lex word pair,
    val ≡ 0) from host rows — only ever built for the initial relations and
    per-epoch deltas.  Delegates to the csr builders over a zero ext column
    with key_pos = ALL columns, so the sharded layout and ownership
    (``csr.shard_of``) are THE SAME code path as the projections' shards —
    the cross-structure shard agreement the distributed commit folds rely
    on is not re-implemented here.  ``capacity`` (a per-shard floor when
    sharded) lets the caller pin the ratcheted rung; the pow2 of the actual
    row count is the lower bound either way.  ``mesh`` places the sharded
    stacks."""
    rows = np.asarray(rows, np.int32).reshape(-1, arity)
    rows_ext = np.concatenate(
        [rows, np.zeros((rows.shape[0], 1), np.int32)], axis=1)
    key_pos = tuple(range(arity))
    if shard_w:
        return csr.build_sharded_index(rows_ext, key_pos, arity, shard_w,
                                       capacity=capacity, narrow=False,
                                       mesh=mesh)
    return csr.build_index(
        rows_ext, key_pos, arity,
        capacity=max(int(capacity or 0), _pow2(rows_ext.shape[0])),
        narrow=False)


def _empty_packed(shard_w: int = 0, arity: int = 2, mesh=None
                  ) -> IndexData:
    composite = arity > 2
    if not shard_w:
        return csr.empty_index(narrow=False, composite=composite)
    w = int(shard_w)

    def put(x):
        return csr.place_worker_stack(x, mesh)

    return IndexData(
        put(np.full((w, csr.SEG), csr.SENTINEL, np.int64)),
        put(np.zeros((w, csr.SEG), np.int32)), put(np.zeros(w, np.int32)),
        put(np.full((w, csr.SEG), csr.SENTINEL, np.int64))
        if composite else None)


def _pad_probe(keys, vals: np.ndarray, sent,
               cap: Optional[int] = None, mesh=None) -> Tuple:
    """Pow2-pad a probe batch; ``keys`` is one packed array or a composite
    (hi, lo) pair (padding rows take the sentinel in every key word).
    ``cap`` raises the pad to a ratcheted rung so probe shapes stay pinned
    across batches.  ``mesh``: replicate on a sharded store's mesh."""
    def put(x):
        return csr.place_for_workers(x, mesh)

    if isinstance(keys, tuple):
        hi, lo = keys
        B = max(int(cap or 0), _pow2(hi.shape[0]))
        kh = np.full(B, csr.SENTINEL, np.int64)
        kl = np.full(B, csr.SENTINEL, np.int64)
        kh[:hi.shape[0]] = hi
        kl[:lo.shape[0]] = lo
        v = np.zeros(B, np.int32)
        v[:vals.shape[0]] = vals
        return (put(kh), put(kl)), put(v)
    B = max(int(cap or 0), _pow2(keys.shape[0]))
    k = np.full(B, sent, keys.dtype)
    k[:keys.shape[0]] = keys
    v = np.zeros(B, np.int32)
    v[:vals.shape[0]] = vals
    return put(k), put(v)


def _sds_like(idx: IndexData, cap: Optional[int] = None) -> IndexData:
    """ShapeDtypeStruct skeleton of ``idx`` with its capacity (the last
    axis of every padded array) overridden to ``cap`` — the argument
    prototype prewarm warms a fold against (see :func:`_warm_call`).
    Mirrors dtypes, the composite ``lo`` word and the sharded leading [w]
    axis — with its mesh placement — exactly, so the AOT signature is the
    runtime signature (jit keys its trace cache on input shardings)."""
    def arr(a, cap=None):
        shp = list(a.shape)
        if cap is not None:
            shp[-1] = int(cap)
        sharding = getattr(a, "sharding", None)
        if not isinstance(sharding, jax.sharding.NamedSharding):
            sharding = None  # default placement, as uploaded
        return jax.ShapeDtypeStruct(tuple(shp), a.dtype, sharding=sharding)

    return IndexData(arr(idx.key, cap), arr(idx.val, cap), arr(idx.n),
                     None if idx.lo is None else arr(idx.lo, cap))


def _warm_call(fn, *args, **static):
    """Execute a jitted ``fn`` once on zero-filled concretizations of the
    ShapeDtypeStruct prototypes in ``args`` (``static`` kwargs pass
    through).

    This — not ``jit(...).lower(...).compile()`` — is what makes the first
    streaming call at a warmed signature free: jax's AOT path populates
    the trace cache but NOT the jit dispatch executable cache, so a
    lower/compile-only prewarm still pays the full XLA compile (seconds)
    when the stream first crosses onto the rung, invisibly to the trace
    counters.  Zero-filled inputs make every fold a trivially-empty pass
    (all counts 0), so the execution itself costs microseconds."""
    z = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding), args)
    jax.block_until_ready(fn(*z, **static))


PREWARM_CROSS_CAP = 128


def _rung_combos(ladders: Dict[str, List[int]],
                 cap: int = PREWARM_CROSS_CAP) -> List[Dict[str, int]]:
    """Committed-rung combinations a multi-relation plan can request.

    Relations grow (and compact) independently, so a plan reading two
    relations can see ANY pair of committed rungs — warming only the
    same-rung diagonal (PR 6) left one compile per first-crossed mixed
    combo.  This enumerates the reachable cross-product of each
    relation's ladder; when the product exceeds ``cap`` (only possible
    with many relations on deep ladders) it falls back to a documented
    bounded subset — the same-rung diagonal plus every one-relation axis
    sweep off the ladder floor — so prewarm stays O(sum of ladder
    lengths) and only simultaneous multi-relation high-rung mixes can
    still pay a first-crossing compile (DESIGN.md §8)."""
    rels = sorted(ladders)
    if not rels:
        return []
    total = 1
    for rel in rels:
        total *= max(len(ladders[rel]), 1)
    if total <= cap:
        return [dict(zip(rels, combo)) for combo in
                itertools.product(*(ladders[rel] for rel in rels))]
    combos: List[Dict[str, int]] = []
    seen = set()

    def add(combo):
        key = tuple(sorted(combo.items()))
        if key not in seen:
            seen.add(key)
            combos.append(combo)

    depth = max(len(ladders[rel]) for rel in rels)
    for i in range(depth):  # the diagonal, clamped per relation
        add({rel: ladders[rel][min(i, len(ladders[rel]) - 1)]
             for rel in rels})
    for rel in rels:  # per-relation sweeps with the others on the floor
        for r in ladders[rel]:
            combo = {other: ladders[other][0] for other in rels}
            combo[rel] = r
            add(combo)
    return combos


@dataclasses.dataclass
class _Regions:
    """Device truth of one projection's regions (+ optional mirrors).

    ``device_resident`` (default): ``d_base/d_cins/d_cdel`` ARE the state —
    sorted device IndexData updated by the jitted folds above; ``base`` /
    ``cins`` / ``cdel`` are lazily-materialized host mirrors for debug and
    differential paths.  Legacy mode inverts this: ``_host`` numpy arrays
    are the truth and ``refresh()`` rebuilds the device mirrors from them.

    With ``shard_w > 0`` every region array carries a leading [w] worker
    axis and each (key, val) entry is stored by exactly one worker
    (``csr.build_sharded_index``) — the distributed engine's
    memory-linearity contract; the folds vmap over the axis, so each worker
    folds only its owned rows.

    ``derived=True`` marks a projection whose (key, ext) columns do NOT
    cover the relation's full row (possible only for arity > 2 relations,
    e.g. the a1->a3 index of ``tri`` ignoring a2).  Such a projection is a
    lossy many-to-one image of the relation, so sorted set folds cannot
    maintain it incrementally (deleting one supporting row must not kill a
    pair another live row still supports); instead ``versioned()`` derives
    it from the relation's live rows on demand, cached until the next
    begin_epoch/commit.  Delta plans never touch derived projections
    (their bindings always cover the row — see DESIGN.md §7), so the warm
    epoch loop stays delta-proportional.
    """

    key_pos: Tuple[int, ...]
    ext_pos: int
    rel: str = "edge"
    rel_arity: int = 0  # the backing relation's TRUE arity
    shard_w: int = 0
    mesh: object = None  # the sharded store's mesh (placement only)
    device_resident: bool = True
    narrow: bool = True
    derived: bool = False
    d_base: IndexData = None
    d_cins: IndexData = None
    d_cdel: IndexData = None
    d_uins: IndexData = None
    d_udel: IndexData = None
    # exact live counts (host bookkeeping, pulled once per fold):
    # ints single-host, [w] int64 vectors sharded
    n_base: object = 0
    n_cins: object = 0
    n_cdel: object = 0
    _host: dict = dataclasses.field(default_factory=dict)
    _mirror: dict = dataclasses.field(default_factory=dict)
    _derived_cache: dict = dataclasses.field(default_factory=dict)
    _store: object = None

    @property
    def arity(self) -> int:
        return self.rel_arity or \
            max(max(self.key_pos, default=0), self.ext_pos) + 1

    def _ratchet(self, kind: str):
        """The store ratchet + key quantizing ``kind`` capacities for this
        projection's relation, or None for storeless regions.  All
        non-derived projections of one relation cover its full row, so
        their region counts are EQUAL — one shared (kind, rel) mark per
        relation keeps every projection (and the live LSM) on the same
        rung, halving the fold-signature space."""
        store = self._store
        if store is None:
            return None, None
        r = store.base_ratchet if kind == "base" else store.ratchet
        return r, (kind, self.rel)

    def _build(self, tup: np.ndarray, kind: str = "base") -> IndexData:
        rows = np.asarray(tup).reshape(-1, self.arity)
        ratchet, key = self._ratchet(kind)
        if self.shard_w:
            from repro.core.csr import build_sharded_index
            per = -(-max(rows.shape[0], 1) // self.shard_w)
            cap = _pow2(per) if ratchet is None else \
                ratchet.capacity(key, per)
            idx = build_sharded_index(rows, self.key_pos, self.ext_pos,
                                      self.shard_w, capacity=cap,
                                      narrow=self.narrow, mesh=self.mesh)
        else:
            cap = _pow2(rows.shape[0]) if ratchet is None else \
                ratchet.capacity(key, rows.shape[0])
            idx = build_index(rows, self.key_pos, self.ext_pos,
                              capacity=cap, narrow=self.narrow)
        if ratchet is not None:
            # sharded builds may exceed the per-shard floor under skew:
            # feed the REAL capacity back so the rung stays truthful
            ratchet.observe(key, idx.key.shape[-1])
        return idx

    # -- host rows: legacy truth, or the device mode's lazy debug mirror ----
    def _rows(self, name: str) -> np.ndarray:
        if self.derived:
            # base = the backing relation's live rows; committed deltas are
            # folded into the relation itself, never into this projection
            if name == "base":
                return self._store._rel_rows(self.rel)
            return np.zeros((0, self.arity), np.int32)
        if not self.device_resident:
            return self._host[name]
        if name not in self._mirror:
            self._mirror[name] = self._materialize(getattr(self,
                                                           "d_" + name))
            if self._store is not None:
                self._store.stats.mirror_pulls += 1
        return self._mirror[name]

    @property
    def base(self) -> np.ndarray:
        return self._rows("base")

    @property
    def cins(self) -> np.ndarray:
        return self._rows("cins")

    @property
    def cdel(self) -> np.ndarray:
        return self._rows("cdel")

    def _materialize(self, d: IndexData) -> np.ndarray:
        """Reconstruct host tuple rows from the device (key[, lo], val)
        arrays; canonical row-lex (np.unique) order, like the old host
        truth.  Columns outside key_pos/ext_pos (possible only on derived
        projections, which never come through here) stay zero."""
        keys, vals, ns = np.asarray(d.key), np.asarray(d.val), np.asarray(d.n)
        los = None if d.lo is None else np.asarray(d.lo)
        if self.shard_w:
            key = np.concatenate([keys[k][:ns[k]]
                                  for k in range(self.shard_w)])
            val = np.concatenate([vals[k][:ns[k]]
                                  for k in range(self.shard_w)])
            lo = None if los is None else np.concatenate(
                [los[k][:ns[k]] for k in range(self.shard_w)])
        else:
            key, val = keys[:int(ns)], vals[:int(ns)]
            lo = None if los is None else los[:int(ns)]
        rows = np.zeros((key.shape[0], self.arity), np.int32)
        nk = len(self.key_pos)
        kcols = csr.unpack_key(key.astype(np.int64) if lo is None
                               else (key.astype(np.int64),
                                     lo.astype(np.int64)), nk) \
            if nk else None
        for c, p in enumerate(self.key_pos):
            rows[:, p] = kcols[:, c]
        rows[:, self.ext_pos] = val
        order = np.lexsort(tuple(rows[:, c]
                                 for c in range(rows.shape[1] - 1, -1, -1)))
        return rows[order]

    def refresh(self, which=("base", "cins", "cdel")):
        """Legacy mode only: rebuild device mirrors from the host truth."""
        assert not self.device_resident, \
            "device-resident regions are merged, never rebuilt"
        for name in which:
            setattr(self, "d_" + name,
                    self._build(self._host[name],
                                kind="base" if name == "base"
                                else "committed"))

    def set_uncommitted(self, uins: np.ndarray, udel: np.ndarray):
        if self.derived:
            self._derived_cache.clear()  # the "new" image changed
            return
        self.d_uins = self._build(uins, kind="delta")
        self.d_udel = self._build(udel, kind="delta")

    def probe_cdel(self, ins: np.ndarray) -> bool:
        """any(ins ∈ cdel) — device probe, O(|Δ|·log|cdel|)."""
        if self.derived:
            return False  # no committed-delete region to overlap
        key = csr.pack_key(tuple(ins[:, p].astype(np.int32)
                                 for p in self.key_pos))
        kdt = np.dtype(self.d_cdel.key.dtype.name)
        sent = csr.SENTINEL32 if kdt == np.int32 else csr.SENTINEL
        if not isinstance(key, tuple):
            key = key.astype(kdt)
        ratchet, rkey = self._ratchet("probe")
        cap = None if ratchet is None else \
            ratchet.capacity(rkey, ins.shape[0])
        qk, qv = _pad_probe(key, ins[:, self.ext_pos].astype(np.int32),
                            sent, cap=cap, mesh=self.mesh)
        return hostread.read("compact", self._store.stats, bool,
                             _any_member(self.d_cdel, qk, qv,
                                         sharded=bool(self.shard_w)))

    def versioned(self, version: str) -> VersionedIndex:
        if self.derived:
            return self._derived_versioned(version)
        if version == "old":
            return VersionedIndex((self.d_base, self.d_cins), (self.d_cdel,))
        if version == "new":
            return VersionedIndex((self.d_base, self.d_cins, self.d_uins),
                                  (self.d_cdel, self.d_udel))
        if version == "static":
            return VersionedIndex((self.d_base,), ())
        raise ValueError(version)

    def _derived_versioned(self, version: str) -> VersionedIndex:
        """Projection image rebuilt from the relation's live rows: "old"
        (= "static") is the committed state, "new" folds the staged batch.
        Cached until the next begin_epoch/commit/compaction."""
        if version not in ("old", "new", "static"):
            raise ValueError(version)
        tag = "new" if version == "new" else "old"
        idx = self._derived_cache.get(tag)
        if idx is None:
            rows = self._store._rel_rows(self.rel)
            if tag == "new":
                ins, dels = self._store._staged_for(self.rel)
                if dels.size:
                    rows = rows[~rows_isin(rows, dels)]
                if ins.size:
                    rows = np.unique(np.concatenate([rows, ins]), axis=0)
            idx = self._build(rows)
            self._derived_cache[tag] = idx
        return VersionedIndex((idx,), ())


def _diff_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Rows of a not in b (both [N, m] int, any arity)."""
    if a.size == 0 or b.size == 0:
        return a
    if a.shape[1] == 2:
        pa, pb = _pack2(a[:, 0], a[:, 1]), _pack2(b[:, 0], b[:, 1])
        return a[~np.isin(pa, pb)]
    return a[~rows_isin(a, b)]


def _inter_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if a.size == 0 or b.size == 0:
        return a[:0]
    if a.shape[1] == 2:
        pa, pb = _pack2(a[:, 0], a[:, 1]), _pack2(b[:, 0], b[:, 1])
        return a[np.isin(pa, pb)]
    return a[rows_isin(a, b)]


@dataclasses.dataclass
class DeltaResult:
    count_delta: int
    tuples: Optional[np.ndarray]
    weights: Optional[np.ndarray]
    per_dq: List[JoinResult]


@dataclasses.dataclass
class StoreStats:
    """Per-store epoch accounting.  ``normalize_calls`` / ``commit_calls``
    are the facade's one-commit-per-epoch contract: with N standing queries
    on one store both advance by exactly 1 per update epoch.
    ``mirror_pulls`` counts host materializations of device-resident state
    (debug/differential paths only — zero on the warm epoch loop);
    ``live_compactions`` tracks the store-level live-set LSM separately
    from the per-projection ``compactions``.  ``compile_events`` is the
    number of jit traces (= XLA compiles on one backend) recorded by any
    instrumented fold since this store was created — steady state it must
    stay FLAT across epochs (the DESIGN.md §8 compilation-stability
    invariant); ``prewarm_compiles`` is the subset spent walking the AOT
    ladder up front."""

    normalize_calls: int = 0
    commit_calls: int = 0
    compactions: int = 0
    epochs: int = 0
    live_compactions: int = 0
    mirror_pulls: int = 0
    compile_events: int = 0
    prewarm_compiles: int = 0
    # robustness accounting (DESIGN.md §10)
    escalations: int = 0  # capacity rungs bumped after CapacityOverflow
    replays: int = 0  # epoch dataflow re-runs after an escalation
    rollbacks: int = 0  # rollback() calls (faulted commits)
    escalation_compiles: int = 0  # compile events spent re-prewarming
    # blocking device->host reads of normalize, commit and compaction
    # (``hostread.read``), and the bytes they moved
    host_reads: int = 0
    host_read_bytes: int = 0


@dataclasses.dataclass
class PreparedBatch:
    """One update batch after :meth:`RegionStore.prepare` (stage A of a
    pipelined epoch, DESIGN.md §9): validated, degenerate-masked, packed
    and sentinel-padded entirely on the host.

    ``rels`` maps relation -> the padded ``(hi, lo, weights)`` probe
    arrays (device-resident stores only); ``raw`` keeps the checked
    ``(rows, weights)`` per relation — the canonical bytes a write-ahead
    log records and the legacy host store normalizes from.  ``was_dict``
    preserves the edge-array sugar of :meth:`RegionStore.normalize`."""

    rels: Dict[str, Tuple[np.ndarray, np.ndarray, np.ndarray]]
    raw: Dict[str, Tuple[np.ndarray, np.ndarray]]
    was_dict: bool


@dataclasses.dataclass
class _RelLive:
    """One relation's live-set state: its own packed three-region LSM
    (device mode) or host truth rows + sorted packed cache (legacy)."""

    arity: int
    # device-resident LSM (key = the row's lex word pair, val ≡ 0)
    lb: IndexData = None
    lc_ins: IndexData = None
    lc_del: IndexData = None
    n_live: list = None  # [n_base, n_cins, n_cdel]
    mirror: Optional[np.ndarray] = None  # lazily-pulled host rows
    # legacy host truth
    rows: Optional[np.ndarray] = None  # [N, arity] unique row-lex
    packed: Optional[np.ndarray] = None  # arity<=2: sorted packed words
    packed_pair: Optional[Tuple[np.ndarray, np.ndarray]] = None  # arity>2


class RegionStore:
    """Owner of every dynamic relation's live set and every projection's
    LSM regions.

    This is the shared substrate under both the single-query engines and the
    :class:`repro.api.GraphSession` facade: projections are created on demand
    (:meth:`ensure`) and SHARED between every query registered against the
    store, so N standing queries pay one region build, one ``normalize`` and
    one ``commit`` per epoch instead of N copies of each.

    The store is MULTI-RELATION: ``initial`` may be a plain [E, 2] edge
    array (sugar for ``{"edge": edges}``) or a dict of n-ary relations
    (arity up to 4, e.g. the ternary ``tri`` relation of §5.4); every
    relation gets its own live-set LSM, and updates arrive as per-relation
    batches (``normalize({"edge": (rows, w), "tri": ...})`` — the bare
    2-column array form still means the edge relation).

    ``device_resident=True`` (default): the source of truth is on device —
    each live set is its own packed three-region LSM, ``normalize`` is
    a jitted membership probe, ``commit``/compaction are jitted sorted-merge
    folds, and ``edges`` / region rows are lazily-pulled debug mirrors.
    ``device_resident=False`` keeps the legacy host-numpy truth (the old
    behaviour, with an incrementally-maintained packed live cache).

    ``shard_w > 0`` builds every device region hash-partitioned over that
    many mesh workers (the distributed engine's layout), n-ary regions
    included — ownership is by the row's composite key, so commits stay
    owner-local and collective-free and no worker holds O(|R|) of any
    relation.  ``mesh`` (shard_w devices) is the mesh the engines'
    shard_map programs run on: the worker stacks are placed one row per
    device of it, in its device order, and delta uploads are replicated on
    it.  Without one, the stacks keep the default placement.
    """

    def __init__(self, initial, shard_w: int = 0,
                 compact_ratio: float = 0.5, device_resident: bool = True,
                 mesh=None):
        if mesh is not None and mesh.size != shard_w:
            raise ValueError(f"a {mesh.size}-device mesh cannot place a "
                             f"store of {shard_w} shards")
        self.shard_w = shard_w
        self.mesh = mesh
        self.compact_ratio = compact_ratio
        self.device_resident = bool(device_resident)
        self.projections: Dict[Projection, _Regions] = {}
        self.stats = StoreStats()
        self._compile_base = compilestats.total()
        # growth hysteresis (DESIGN.md §8): delta/probe/committed caps ride
        # the slack ladder and never shrink; base caps are monotone pow2
        # (factor 2 — no slack: base is the big region, 2x headroom max)
        self.ratchet = Ratchet()
        self.base_ratchet = Ratchet(factor=2)
        self._rels: Dict[str, _RelLive] = {}
        self._staged: Optional[Dict[str, Tuple[np.ndarray, np.ndarray]]] \
            = None
        rels = initial if isinstance(initial, dict) else \
            {"edge": np.asarray(initial, np.int32).reshape(-1, 2)}
        for rel, rows in rels.items():
            self.add_relation(rel, rows)

    def _sync_compile_stats(self):
        self.stats.compile_events = compilestats.total() - self._compile_base

    # -- ratcheted capacities (marks are PER-SHARD units when sharded) -----
    def _per_shard(self, n: int) -> int:
        return -(-max(int(n), 1) // self.shard_w) if self.shard_w \
            else max(int(n), 1)

    def _base_cap(self, rel: str, n: int) -> int:
        return self.base_ratchet.capacity(("base", rel), self._per_shard(n))

    def _delta_cap(self, rel: str, n: int) -> int:
        return self.ratchet.capacity(("delta", rel), self._per_shard(n))

    def _probe_cap(self, rel: str, n: int) -> int:
        return self.ratchet.capacity(("probe", rel), max(int(n), 1))

    def _committed_cap(self, rel: str, n: int) -> int:
        return self.ratchet.capacity(("committed", rel), max(int(n), 1))

    def add_relation(self, rel: str, rows: np.ndarray,
                     arity: Optional[int] = None):
        """Register one dynamic relation with its initial tuples [N, arity]
        (arity 2..4; ``arity`` disambiguates an empty batch).

        Seeding a relation that exists but is still EMPTY (e.g. one
        ``register()`` auto-declared for a query before its tuples were
        materialized) replaces it in place — its projections are rebuilt
        from the seeded rows; a non-empty relation cannot be re-seeded."""
        old = self._rels.get(rel)
        if old is not None:
            staged = bool(self._staged) and rel in self._staged and \
                any(x.size for x in self._staged[rel])
            if self.num_tuples(rel) or staged:
                raise ValueError(f"relation {rel!r} already exists")
        rows = np.asarray(rows)
        if rows.ndim != 2 and not (rows.size == 0 and arity):
            raise ValueError(
                f"initial {rel!r} tuples must be [N, arity], got shape "
                f"{rows.shape}")
        ar = int(arity or rows.shape[1])
        if rows.ndim == 2 and rows.size and rows.shape[1] != ar:
            raise ValueError(
                f"initial {rel!r} tuples are [N, {rows.shape[1]}] but "
                f"arity={ar} was requested")
        if not 2 <= ar <= 4:
            raise ValueError(
                f"relation {rel!r} arity {ar} unsupported (2..4: composite "
                "keys cover up to 4 columns)")
        if old is not None and ar != old.arity:
            raise ValueError(
                f"relation {rel!r} was declared with arity {old.arity}, "
                f"cannot re-seed with arity {ar}")
        rows, _ = _check_batch(rel, rows.reshape(-1, ar), None, ar)
        rows = csr.unique_rows(rows)
        st = _RelLive(arity=ar)
        if self.device_resident:
            # each live LSM shards like the projections (ownership by
            # packed key), so per-worker live memory stays O(|R|/w)
            st.lb = _packed_index(rows, self.shard_w, ar,
                                  capacity=self._base_cap(rel,
                                                          rows.shape[0]),
                                  mesh=self.mesh)
            self.base_ratchet.observe(("base", rel), st.lb.key.shape[-1])
            st.lc_ins = _empty_packed(self.shard_w, ar, self.mesh)
            st.lc_del = _empty_packed(self.shard_w, ar, self.mesh)
            zero = np.zeros(self.shard_w, np.int64) if self.shard_w else 0
            nb = _count_of(st.lb) if self.shard_w else rows.shape[0]
            st.n_live = [nb, zero, zero]  # base, cins, cdel
            st.mirror = rows
        else:
            st.rows = rows
            self._refresh_host_cache(st)
        self._rels[rel] = st
        if old is not None:
            # rebuild projections ensured against the empty declaration
            for proj in [p for p in self.projections if p[0] == rel]:
                del self.projections[proj]
                self.ensure(*proj)

    def _refresh_host_cache(self, st: _RelLive):
        if st.arity <= 2:
            hi, _ = _pack_rows(st.rows, st.arity)
            st.packed = np.sort(hi)
        else:
            hi, lo = _pack_rows(st.rows, st.arity)
            order = np.lexsort((lo, hi))
            st.packed_pair = (hi[order], lo[order])

    # -- relation introspection ---------------------------------------------
    @property
    def relations(self) -> Tuple[str, ...]:
        return tuple(self._rels)

    def arity_of(self, rel: str) -> int:
        return self._rel(rel).arity

    def _rel(self, rel: str) -> _RelLive:
        st = self._rels.get(rel)
        if st is None:
            raise KeyError(
                f"unknown relation {rel!r}; known: "
                f"{', '.join(self._rels) or '(none)'} — pass it in the "
                "initial relations dict or add_relation() first")
        return st

    def _rel_rows(self, rel: str) -> np.ndarray:
        """One relation's live rows on the host.  Legacy: the truth.
        Device-resident: a lazily-materialized mirror (oracle/differential
        paths only — the warm epoch loop never touches it)."""
        st = self._rel(rel)
        if not self.device_resident:
            return st.rows
        if st.mirror is None:
            nb, nci, _ = st.n_live
            cap = _pow2(_maxn(np.asarray(nb) + np.asarray(nci)))
            live = _compact_fold(st.lb, st.lc_ins, st.lc_del,
                                 out_cap=cap, sharded=bool(self.shard_w))
            if self.shard_w:
                ns = np.asarray(live.n)
                keys = np.asarray(live.key)
                hi = np.concatenate(
                    [keys[k][:ns[k]] for k in range(self.shard_w)])
                if live.lo is None:
                    lo = np.zeros(hi.shape[0], np.int64)
                else:
                    los = np.asarray(live.lo)
                    lo = np.concatenate(
                        [los[k][:ns[k]] for k in range(self.shard_w)])
            else:
                hi = np.asarray(live.key)[:int(live.n)]
                lo = np.zeros(hi.shape[0], np.int64) if live.lo is None \
                    else np.asarray(live.lo)[:int(live.n)]
            order = np.lexsort((lo, hi))
            st.mirror = _unpack_rows(hi[order], lo[order], st.arity)
            self.stats.mirror_pulls += 1
        return st.mirror

    def relation_rows(self, rel: str) -> np.ndarray:
        """Public host view of one relation's live tuples."""
        return self._rel_rows(rel)

    def num_tuples(self, rel: str) -> int:
        """Live tuple count of one relation, O(1) from tracked sizes."""
        st = self._rel(rel)
        if not self.device_resident:
            return int(st.rows.shape[0])
        nb, nci, ncd = st.n_live
        return _total(nb) + _total(nci) - _total(ncd)

    @property
    def max_live(self) -> int:
        """Largest relation's live size (capacity/AGM sizing input)."""
        return max((self.num_tuples(r) for r in self._rels), default=0)

    # -- the live edge set (edge-relation sugar + legacy aliases) ----------
    @property
    def edges(self) -> np.ndarray:
        return self._rel_rows("edge")

    @property
    def num_edges(self) -> int:
        """Live edge count, O(1) from the tracked region sizes — no mirror
        materialization (|live| = |base| + |cins| − |cdel|)."""
        return self.num_tuples("edge") if "edge" in self._rels else 0

    @property
    def _lb(self) -> IndexData:
        return self._rel("edge").lb

    @property
    def _lc_ins(self) -> IndexData:
        return self._rel("edge").lc_ins

    @property
    def _lc_del(self) -> IndexData:
        return self._rel("edge").lc_del

    @property
    def _n_live(self) -> list:
        return self._rel("edge").n_live

    @property
    def _edges_mirror(self) -> Optional[np.ndarray]:
        return self._rel("edge").mirror

    @property
    def _edges(self) -> np.ndarray:
        return self._rel("edge").rows

    @property
    def _packed_live(self) -> np.ndarray:
        return self._rel("edge").packed

    def ensure(self, rel: str, key_pos: Tuple[int, ...], ext_pos: int,
               arity: Optional[int] = None) -> _Regions:
        """Region storage for one projection, built from the CURRENT live
        relation on first use and reused by every later query that needs the
        same projection (the hoisted per-query path of old DeltaBigJoin).
        ``arity`` lets a plan auto-declare a not-yet-seen relation (created
        empty)."""
        st = self._rels.get(rel)
        if st is None:
            if arity is None:
                self._rel(rel)  # raises with the helpful message
            self.add_relation(rel, np.zeros((0, arity), np.int32))
            st = self._rels[rel]
        proj = (rel, key_pos, ext_pos)
        reg = self.projections.get(proj)
        if reg is not None:
            return reg
        # a projection whose key/ext columns don't cover the relation's
        # full row is a lossy image: it is DERIVED from the live rows on
        # demand instead of folded incrementally (see _Regions docs)
        used = set(key_pos) | {ext_pos}
        covers = used == set(range(st.arity)) and \
            len(key_pos) + 1 == st.arity
        rows = self._rel_rows(rel)
        # narrow is decided ONCE per projection (merges must keep one
        # dtype): auto-widen when an id already collides with the int32
        # sentinel, like build_index's per-build check did.  Composite
        # projections with a single-column hi word (3 bound columns)
        # narrow too — the lo word is always int64.
        narrow = csr.single_word_hi(len(key_pos)) and \
            (rows.size == 0 or int(rows.max()) < int(csr.SENTINEL32))
        reg = _Regions(key_pos, ext_pos, rel=rel, rel_arity=st.arity,
                       shard_w=self.shard_w, mesh=self.mesh,
                       device_resident=self.device_resident, narrow=narrow,
                       derived=not covers, _store=self)
        empty = rows[:0]
        if reg.derived:
            self.projections[proj] = reg
            return reg
        if self.device_resident:
            reg.d_base = reg._build(rows)
            reg.d_cins = reg._build(empty, kind="committed")
            reg.d_cdel = reg._build(empty, kind="committed")
            reg.n_base = _count_of(reg.d_base) if self.shard_w \
                else rows.shape[0]
            reg.n_cins = np.zeros(self.shard_w, np.int64) if self.shard_w \
                else 0
            reg.n_cdel = np.zeros(self.shard_w, np.int64) if self.shard_w \
                else 0
            reg._mirror["base"] = rows
            reg._mirror["cins"] = empty
            reg._mirror["cdel"] = empty
        else:
            reg._host = {"base": rows, "cins": empty, "cdel": empty}
            reg.refresh()
        # a projection ensured mid-epoch (after begin_epoch, before commit)
        # must see the staged batch: its base is the PRE-commit live set, so
        # old = base and new = base + uins - udel stay consistent, and the
        # commit fold picks the delta up instead of losing it
        ins, dels = self._staged_for(rel) if self._staged is not None else \
            (empty, empty)
        reg.set_uncommitted(ins, dels)
        self.projections[proj] = reg
        return reg

    def _staged_for(self, rel: str) -> Tuple[np.ndarray, np.ndarray]:
        ar = self._rel(rel).arity
        empty = np.zeros((0, ar), np.int32)
        if not self._staged:
            return empty, empty
        return self._staged.get(rel, (empty, empty))

    def ensure_plan(self, plan: Plan):
        arities = {a.rel: a.arity for a in plan.query.atoms}
        for _id, rel, key_pos, ext_pos, _v in plan.index_ids():
            self.ensure(rel, key_pos, ext_pos, arity=arities.get(rel))
        # the seed relation may carry no index at all (e.g. a binary seed
        # atom whose attrs are fully bound at P_2): declare it anyway so
        # seeds/updates for it resolve
        seed_rel = plan.query.atoms[plan.seed_atom].rel
        if seed_rel not in self._rels:
            self.add_relation(
                seed_rel, np.zeros((0, arities[seed_rel]), np.int32))

    def indices_for(self, plan: Plan) -> Indices:
        """Assemble the plan's VersionedIndex dict off the shared regions."""
        return {
            _id: self.ensure(rel, key_pos, ext_pos).versioned(version)
            for _id, rel, key_pos, ext_pos, version in plan.index_ids()}

    # -- AOT prewarm (DESIGN.md §8) ------------------------------------
    def committed_ladder(self, rel: str, update_batch: int,
                         horizon: Optional[int] = None) -> List[int]:
        """The canonical committed-region rungs relation ``rel`` can visit
        before compaction drains it: counts run from 0 up to the compaction
        threshold plus one last pre-compaction batch.  ``horizon`` caps the
        count at the stream's total expected churn (epochs × batch) so a
        short stream over a huge graph doesn't warm rungs it can never
        reach — an unreached rung costs nothing but prewarm time, a missed
        one costs one compile when crossed."""
        st = self._rel(rel)
        nb = _total(st.n_live[0]) if self.device_resident \
            else st.rows.shape[0]
        hi = int(self.compact_ratio * max(nb, 1)) + 2 * int(update_batch)
        if horizon is not None:
            hi = min(hi, max(int(horizon), 2 * int(update_batch)))
        return self.ratchet.rungs(1, hi)

    def pin_delta_marks(self, update_batch: int) -> int:
        """Pin every relation's probe/delta mark to the update-batch bound
        so delta-sized buffers keep ONE shape for the stream's life (a
        batch can land entirely on one shard, so the per-shard pin is the
        full pow2 of the batch).  Returns the pin."""
        P = _pow2(max(int(update_batch), 1))
        for rel in self._rels:
            self.ratchet.observe(("probe", rel), P)
            self.ratchet.observe(("delta", rel), P)
        return P

    def prewarm_folds(self, update_batch: int,
                      horizon: Optional[int] = None) -> int:
        """AOT-compile the store's fold ladder: every jit signature the
        canonical committed ladder can request this side of a base-region
        regrowth — normalize, the eager re-insertion probes, every
        commit-fold rung transition, and compaction — by executing each
        fold once on zero-filled ShapeDtypeStruct prototypes
        (:func:`_warm_call`).

        After this, a stream of batches ≤ ``update_batch`` triggers ZERO
        XLA compiles until a relation's base region outgrows its pow2 rung
        (amortized-rare; compaction itself replays warmed shapes).
        Returns the compile events spent (also accumulated in
        ``stats.prewarm_compiles``)."""
        if not self.device_resident:
            return 0
        snap = compilestats.snapshot()
        ub = max(int(update_batch), 1)
        P = self.pin_delta_marks(ub)
        sharded = bool(self.shard_w)
        # statics must match the runtime call sites EXACTLY or the warm
        # epoch recompiles: the commit fold's kernel choice, and the
        # compaction's single-host-only rank chain
        commit_k = _merge_kernel_on()
        compact_k = _merge_kernel_on() and not sharded
        # delta-sized probe inputs are uploaded replicated on the store's
        # mesh (``csr.place_for_workers``); the prototypes say so too
        rep = csr.worker_sharding(self.mesh, jax.sharding.PartitionSpec())

        def S(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
        pv = S((P,), jnp.int32)
        for rel, st in self._rels.items():
            ladder = self.committed_ladder(rel, ub, horizon)
            # (base proto, committed proto, live?) — all non-derived
            # projections of rel share its committed rung (tied marks)
            groups = [(st.lb, st.lc_ins, True)]
            for reg in self.projections.values():
                if reg.rel == rel and not reg.derived:
                    groups.append((reg.d_base, reg.d_cins, False))
            for base_idx, cproto, is_live in groups:
                b_sds = _sds_like(base_idx)
                # delta regions come from the same builders as committed
                # ones, so the dtypes match; capacity is the pinned P
                d_sds = _sds_like(cproto, P)
                qk = (S((P,), jnp.int64), S((P,), jnp.int64)) \
                    if cproto.lo is not None else S((P,), cproto.key.dtype)
                bcap = int(base_idx.key.shape[-1])
                b_outs = list(dict.fromkeys(
                    (bcap, self.base_ratchet.next_rung(bcap))))
                for r in ladder:
                    ci = _sds_like(cproto, r)
                    if is_live:
                        _warm_call(
                            _normalize_core, S((P,), jnp.int64),
                            S((P,), jnp.int64), S((P,), jnp.int32),
                            b_sds, ci, ci, sharded=sharded)
                    _warm_call(_any_member, ci, qk, pv, sharded=sharded)
                    for out in self.ratchet.rungs(r, r + ub):
                        _warm_call(
                            _commit_fold, b_sds, ci, ci, d_sds, d_sds,
                            cins_cap=out, cdel_cap=out, sharded=sharded,
                            use_kernel=commit_k)
                    for out in b_outs:
                        _warm_call(
                            _compact_fold, b_sds, ci, ci, out_cap=out,
                            sharded=sharded, use_kernel=compact_k)
        spent = compilestats.since(snap)
        self.stats.prewarm_compiles += spent
        self._sync_compile_stats()
        return spent

    def kernel_coverage(self, update_batch: int = 64) -> dict:
        """Per-relation kernel-dispatch evidence for the CI coverage gate.

        Traces the EXACT jitted entry points a warm epoch dispatches to —
        the commit fold with the runtime statics (``_merge_kernel_on``,
        current committed rung, pinned delta capacity) and one projection's
        OLD-version signed-membership probe — and counts their
        ``pallas_call`` equations.  Runtime launch counting would need host
        callbacks (banned on the serving path); tracing the same (function,
        statics, shapes) the warm jit cache serves is the static equivalent:
        what the trace contains is what every warm epoch executes.  Pure
        introspection — no ratchet observation, no store mutation.
        ``probe_mosaic`` says whether the probe lowers to a compiled Mosaic
        kernel (a TPU backend) rather than interpret mode."""
        from repro.kernels import count_pallas_calls
        if not self.device_resident:
            return {}
        use_k = _merge_kernel_on()
        sharded = bool(self.shard_w)
        P = self.pin_delta_marks(max(int(update_batch), 1))
        out = {}
        for rel, st in self._rels.items():
            cc = int(st.lc_ins.key.shape[-1])  # current committed rung
            li = _packed_index(np.zeros((0, st.arity), np.int32),
                               self.shard_w, st.arity, capacity=P,
                               mesh=self.mesh)
            fold_calls = count_pallas_calls(
                lambda ba, ci, cd, ui, ud: _commit_fold_impl(
                    ba, ci, cd, ui, ud, cins_cap=cc, cdel_cap=cc,
                    sharded=sharded, use_kernel=use_k),
                st.lb, st.lc_ins, st.lc_del, li, li)
            probe_calls, probe_mosaic = 0, False
            for reg in self.projections.values():
                if reg.rel != rel or reg.derived:
                    continue
                vi = reg.versioned("old")
                shard0 = (lambda d: jax.tree_util.tree_map(
                    lambda x: x[0], d)) if sharded else (lambda d: d)
                vi = VersionedIndex(tuple(map(shard0, vi.pos)),
                                    tuple(map(shard0, vi.neg)))
                composite = vi.pos[0].lo is not None
                qk = ((jnp.zeros(P, jnp.int64), jnp.zeros(P, jnp.int64))
                      if composite else jnp.zeros(P, jnp.int64))
                qv = jnp.zeros(P, jnp.int32)

                def probe(vi, a, b):  # regions as arguments, not constants
                    return vi.signed_member(a, b, use_kernel=True)
                probe_calls = count_pallas_calls(probe, vi, qk, qv)
                # a compiled kernel lowers to a Mosaic custom call; interpret
                # mode lowers the kernel body to plain HLO instead.  Lowered
                # from unplaced shapes: on a mesh the kernel runs per worker
                # inside shard_map, and jit cannot partition a Mosaic call
                # over the shard's mesh placement
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (vi, qk, qv))
                probe_mosaic = "tpu_custom_call" in jax.jit(probe).lower(
                    *shapes).as_text()
                break
            out[rel] = {
                "composite": st.lb.lo is not None,
                "key_dtype": str(st.lb.key.dtype),
                "fold_pallas_calls": int(fold_calls),
                "fused_fold": bool(use_k and fold_calls == 1),
                "probe_pallas_calls": int(probe_calls),
                "probe_mosaic": bool(probe_mosaic),
            }
        return out

    def indices_sds_for(self, plan: Plan, rung,
                        update_batch: int) -> Indices:
        """ShapeDtypeStruct mirror of :meth:`indices_for` with every
        committed region at ``rung`` (an int, or a per-relation
        ``{rel: rung}`` dict — relations cross rungs independently, see
        :func:`_rung_combos`) and every uncommitted region at the pinned
        delta capacity — the prototype the engines' dataflow steps are
        AOT-lowered against (``GraphSession.prewarm``)."""
        P = self.pin_delta_marks(update_batch)
        out = {}
        for _id, rel, key_pos, ext_pos, version in plan.index_ids():
            reg = self.ensure(rel, key_pos, ext_pos)
            if reg.derived:
                vi = reg._derived_versioned(version)
                out[_id] = VersionedIndex(
                    tuple(_sds_like(p) for p in vi.pos),
                    tuple(_sds_like(n) for n in vi.neg))
                continue
            r = rung[rel] if isinstance(rung, dict) else int(rung)
            base = _sds_like(reg.d_base)
            com = _sds_like(reg.d_cins, r)
            delta = _sds_like(reg.d_uins if reg.d_uins is not None
                              else reg.d_cins, P)
            if version == "static":
                out[_id] = VersionedIndex((base,), ())
            elif version == "old":
                out[_id] = VersionedIndex((base, com), (com,))
            else:  # "new"
                out[_id] = VersionedIndex((base, com, delta), (com, delta))
        return out

    # ------------------------------------------------------------------
    def prepare(self, updates, weights=None) -> "PreparedBatch":
        """Stage A of an update epoch: validate, degenerate-mask, pack and
        sentinel-pad one batch on the HOST — pure numpy, no jax call, no
        device touch.  The returned :class:`PreparedBatch` feeds
        :meth:`normalize_prepared` (stage B, the jitted probe), so a
        serving pipeline can prepare batch k+1 on a prep thread while the
        device is still committing batch k (DESIGN.md §9).

        Accepts the same forms as :meth:`normalize` (bare edge arrays or a
        per-relation dict) and raises the same validation errors."""
        was_dict = isinstance(updates, dict)
        if was_dict:
            if weights is not None:
                raise ValueError(
                    "per-relation batches carry their own weights: pass "
                    "{rel: (rows, weights)}, not a top-level weights "
                    "argument")
            items = {rel: self._split(rel, batch)
                     for rel, batch in updates.items()}
        else:
            items = {"edge": (updates, weights)}
        rels, raw = {}, {}
        for rel, (rows, w) in items.items():
            st = self._rel(rel)
            rows, w = _check_batch(rel, rows, w, st.arity)
            raw[rel] = (rows, w)
            if self.device_resident:
                rels[rel] = self._pad_host(rel, rows, w)
        return PreparedBatch(rels=rels, raw=raw, was_dict=was_dict)

    def normalize_prepared(self, prep: "PreparedBatch") -> Dict:
        """Stage B of :meth:`prepare`: net the prepared batch against the
        live relation state on device (one jitted probe per relation).
        Always returns the per-relation ``{rel: (ins, dels)}`` dict —
        :meth:`normalize` unwraps the edge sugar."""
        with jax.profiler.TraceAnnotation("store.normalize"):
            faults.fire("store.normalize")
            self.stats.normalize_calls += 1
            out = {}
            for rel, (rows, w) in prep.raw.items():
                if not self.device_resident:
                    out[rel] = self._normalize_host(rel, rows, w)
                else:
                    out[rel] = self._normalize_device(rel, *prep.rels[rel])
            self._sync_compile_stats()
            return out

    def normalize(self, updates, weights=None):
        """Net out a batch against the live relation state.

        Array form (edge sugar): ``normalize(rows [N,2], weights)`` returns
        ``(ins, dels)``.  Dict form: ``normalize({rel: (rows, w), ...})``
        returns ``{rel: (ins, dels), ...}`` — one epoch, many relations.
        Wrong-arity / negative-id / non-integer batches raise instead of
        being silently reshaped.

        Device-resident: one jitted probe per relation against its packed
        live LSM — O(|Δ|·log|R|), no full scan, no mirror pull.
        Internally ``prepare`` (host pack/pad) then ``normalize_prepared``
        (device probe) — split callable separately for pipelining.
        """
        prep = self.prepare(updates, weights)
        out = self.normalize_prepared(prep)
        return out if prep.was_dict else out["edge"]

    def _split(self, rel: str, batch):
        """One relation's update entry: a bare row array, or (rows, w)."""
        if isinstance(batch, tuple):
            if len(batch) != 2:
                raise ValueError(
                    f"{rel!r} update entry must be rows or (rows, "
                    f"weights), got a {len(batch)}-tuple")
            return batch
        return batch, None

    def _pad_host(self, rel: str, updates: np.ndarray, weights: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host half of the device normalize: degenerate rows (any repeated
        vertex — the n-ary self-loop) and zero weights are masked to the
        sentinel, rows packed to lex word pairs, all padded to the probe
        rung.  Pure numpy (prep-thread safe)."""
        st = self._rel(rel)
        SENT = np.int64(csr.SENTINEL)
        valid = ~_degenerate_rows(updates) & (weights != 0)
        hi, lo = _pack_rows(updates, st.arity)
        hi = np.where(valid, hi, SENT)
        lo = np.where(valid, lo, SENT)
        B = self._probe_cap(rel, updates.shape[0])
        ph = np.full(B, SENT, np.int64)
        pl = np.full(B, SENT, np.int64)
        pw = np.zeros(B, np.int32)
        ph[:hi.shape[0]] = hi
        pl[:lo.shape[0]] = lo
        pw[:weights.shape[0]] = weights
        return ph, pl, pw

    def _normalize_rel(self, rel: str, updates, weights
                       ) -> Tuple[np.ndarray, np.ndarray]:
        st = self._rel(rel)
        updates, weights = _check_batch(rel, updates, weights, st.arity)
        if not self.device_resident:
            return self._normalize_host(rel, updates, weights)
        return self._normalize_device(rel,
                                      *self._pad_host(rel, updates, weights))

    def _normalize_device(self, rel: str, ph: np.ndarray, pl: np.ndarray,
                          pw: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        st = self._rel(rel)
        dh, dl, dw = (csr.place_for_workers(x, self.mesh)
                      for x in (ph, pl, pw))
        with _device_scope():
            oih, oil, ni, odh, odl, nd = _normalize_core(
                dh, dl, dw, st.lb, st.lc_ins, st.lc_del,
                sharded=bool(self.shard_w))
        read = functools.partial(hostread.read, "normalize", self.stats)
        ni, nd = read(int, ni), read(int, nd)
        ins = _unpack_rows(read(np.asarray, oih)[:ni],
                           read(np.asarray, oil)[:ni], st.arity)
        dels = _unpack_rows(read(np.asarray, odh)[:nd],
                            read(np.asarray, odl)[:nd], st.arity)
        return ins, dels

    def _normalize_host(self, rel: str, updates: np.ndarray,
                        weights: np.ndarray):
        """Legacy host path, probing the incrementally-maintained sorted
        packed cache (no per-call re-pack of the live rows)."""
        st = self._rel(rel)
        keep = ~_degenerate_rows(updates)
        updates, weights = updates[keep], weights[keep]
        if st.arity == 2:
            packed = _pack2(updates[:, 0], updates[:, 1])
            uniq, inv = np.unique(packed, return_inverse=True)
            net = np.zeros(uniq.shape[0], np.int64)
            np.add.at(net, inv, weights)
            rows = _unpack2(uniq)
            live = st.packed
            if live.size:
                pos = np.searchsorted(live, uniq)
                exists = (pos < live.shape[0]) & \
                    (live[np.minimum(pos, live.shape[0] - 1)] == uniq)
            else:
                exists = np.zeros(uniq.shape[0], bool)
        else:
            hi, lo = _pack_rows(updates, st.arity)
            pairs = np.stack([hi, lo], 1)
            uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
            net = np.zeros(uniq.shape[0], np.int64)
            np.add.at(net, inv.reshape(-1), weights)
            rows = _unpack_rows(uniq[:, 0], uniq[:, 1], st.arity)
            lh, ll = st.packed_pair
            exists = rows_isin(uniq, np.stack([lh, ll], 1))
        ins = rows[(net > 0) & ~exists]
        dels = rows[(net < 0) & exists]
        return ins.astype(np.int32), dels.astype(np.int32)

    # ------------------------------------------------------------------
    def _maybe_compact(self, force: bool = False):
        if not self.device_resident:
            self._maybe_compact_host(force)
            return
        use_k = _merge_kernel_on() and not self.shard_w
        for rel, st in self._rels.items():
            nb, nci, ncd = st.n_live
            if (force or _total(nci) + _total(ncd) >
                    self.compact_ratio * max(_total(nb), 1)) and \
                    (_total(nci) or _total(ncd)):
                with jax.profiler.TraceAnnotation("store.compact"):
                    self._compact_live(rel, st, use_k)
        for reg in self.projections.values():
            if reg.derived:
                continue  # rebuilt from the relation rows on demand
            committed = _total(reg.n_cins) + _total(reg.n_cdel)
            if not (force or committed >
                    self.compact_ratio * max(_total(reg.n_base), 1)):
                continue
            if committed:
                with jax.profiler.TraceAnnotation("store.compact"):
                    self._compact_projection(reg, use_k)

    def _compact_live(self, rel: str, st: _RelLive, use_k: bool):
        """Fold one relation's committed live-set regions into its base."""
        nb, nci, ncd = st.n_live
        new_nb = np.asarray(nb) - np.asarray(ncd) + np.asarray(nci)
        out_cap = self.base_ratchet.capacity(("base", rel), _maxn(new_nb))
        with _device_scope():
            st.lb = _compact_fold(st.lb, st.lc_ins, st.lc_del,
                                  out_cap=out_cap,
                                  sharded=bool(self.shard_w),
                                  use_kernel=use_k)
        zero = np.zeros(self.shard_w, np.int64) if self.shard_w else 0
        st.lc_ins = _empty_packed(self.shard_w, st.arity, self.mesh)
        st.lc_del = _empty_packed(self.shard_w, st.arity, self.mesh)
        st.n_live = [new_nb if self.shard_w else int(new_nb), zero, zero]
        self.stats.live_compactions += 1
        st.mirror = None
        # the committed regions drained to zero: restart their rung ladder
        # instead of pinning every future fold at the pre-compaction rung
        # (which would cost O(threshold) per epoch).  The replayed rungs
        # are already in the jit cache, so re-walking the ladder compiles
        # nothing new.
        self.ratchet.reset(("committed", rel))
        # invariant audit: cdel ⊆ base and cins ∩ base = ∅ make the
        # compacted size exact arithmetic — a mismatch means corruption
        assert (np.asarray(_count_of(st.lb, self.stats, "compact"))
                == new_nb).all()

    def _compact_projection(self, reg: _Regions, use_k: bool):
        """Fold one projection's committed regions into its base."""
        new_n = np.asarray(reg.n_base) - np.asarray(reg.n_cdel) \
            + np.asarray(reg.n_cins)
        out_cap = self.base_ratchet.capacity(("base", reg.rel),
                                             _maxn(new_n))
        with _device_scope():
            reg.d_base = _compact_fold(
                reg.d_base, reg.d_cins, reg.d_cdel,
                out_cap=out_cap,
                sharded=bool(self.shard_w), use_kernel=use_k)
        assert (np.asarray(_count_of(reg.d_base, self.stats, "compact"))
                == new_n).all()
        reg.n_base = _count_of(reg.d_base, self.stats, "compact") \
            if self.shard_w else int(new_n)
        self.ratchet.reset(("committed", reg.rel))
        empty = np.zeros((0, reg.arity), np.int32)
        reg.d_cins = reg._build(empty, kind="committed")
        reg.d_cdel = reg._build(empty, kind="committed")
        reg.n_cins = np.zeros(self.shard_w, np.int64) \
            if self.shard_w else 0
        reg.n_cdel = np.zeros(self.shard_w, np.int64) \
            if self.shard_w else 0
        self.stats.compactions += 1
        reg._mirror.clear()

    def _maybe_compact_host(self, force: bool = False):
        for reg in self.projections.values():
            if reg.derived:
                continue
            h = reg._host
            committed = h["cins"].shape[0] + h["cdel"].shape[0]
            if force or committed > self.compact_ratio * max(
                    h["base"].shape[0], 1):
                if h["cins"].size or h["cdel"].size:
                    h["base"] = np.unique(np.concatenate(
                        [_diff_rows(h["base"], h["cdel"]), h["cins"]]),
                        axis=0)
                    self.stats.compactions += 1
                h["cins"] = h["cins"][:0]
                h["cdel"] = h["cdel"][:0]
                reg.refresh()

    def _as_batches(self, ins, dels=None) -> Dict:
        """Array sugar -> per-relation {rel: (ins, dels)} batches.

        Accepts the normalized-dict form ``({rel: (ins, dels)}, None)``,
        the two-dict form ``({rel: ins}, {rel: dels})``, or the legacy
        edge arrays ``(ins, dels)``.
        """
        if isinstance(ins, dict):
            out = {}
            if dels is None:
                for rel, pair in ins.items():
                    ar = self._rel(rel).arity
                    ri, rd = pair
                    out[rel] = (np.asarray(ri, np.int32).reshape(-1, ar),
                                np.asarray(rd, np.int32).reshape(-1, ar))
                return out
            if not isinstance(dels, dict):
                raise ValueError("mixed dict/array (ins, dels) batches")
            for rel in set(ins) | set(dels):
                ar = self._rel(rel).arity
                empty = np.zeros((0, ar), np.int32)
                out[rel] = (np.asarray(ins.get(rel, empty),
                                       np.int32).reshape(-1, ar),
                            np.asarray(dels.get(rel, empty),
                                       np.int32).reshape(-1, ar))
            return out
        return {"edge": (np.asarray(ins, np.int32).reshape(-1, 2),
                         np.asarray(dels, np.int32).reshape(-1, 2))}

    def begin_epoch(self, ins, dels=None):
        """Stage one normalized batch (array sugar for the edge relation,
        or per-relation dicts) as the uncommitted region of EVERY
        projection (after the eager re-insertion compaction check)."""
        batches = self._as_batches(ins, dels)
        # eager compaction iff a committed delete is being re-inserted
        # (would create a positive/negative region overlap, DESIGN.md §2)
        need = False
        for rel, (r_ins, r_dels) in batches.items():
            if not r_ins.size:
                continue
            st = self._rel(rel)
            if self.device_resident:
                if _total(st.n_live[2]):
                    pi = _pack_rows(r_ins, st.arity)
                    probe = pi if st.arity > 2 else pi[0]
                    qk, qv = _pad_probe(probe,
                                        np.zeros(r_ins.shape[0], np.int32),
                                        np.int64(csr.SENTINEL),
                                        cap=self._probe_cap(
                                            rel, r_ins.shape[0]),
                                        mesh=self.mesh)
                    need = need or hostread.read(
                        "compact", self.stats, bool,
                        _any_member(st.lc_del, qk, qv,
                                    sharded=bool(self.shard_w)))
                if not need:
                    need = any(reg.probe_cdel(r_ins)
                               for reg in self.projections.values()
                               if reg.rel == rel and not reg.derived
                               and _total(reg.n_cdel))
            else:
                need = need or any(
                    _inter_rows(r_ins, reg._host["cdel"]).size
                    for reg in self.projections.values()
                    if reg.rel == rel and not reg.derived)
            if int(r_ins.max()) >= int(csr.SENTINEL32) and \
                    any(reg.narrow for reg in self.projections.values()
                        if reg.rel == rel):
                raise ValueError(
                    f"vertex id >= {int(csr.SENTINEL32)} collides with "
                    "the narrow int32 index sentinel of an existing "
                    f"{rel!r} projection; ids this large must be present "
                    "in the initial tuples so the projection is built "
                    "wide")
        self._maybe_compact(force=bool(need))
        self._staged = batches
        for reg in self.projections.values():
            reg.set_uncommitted(*self._staged_for(reg.rel))

    def commit(self, ins, dels=None):
        """Fold uins/udel into the committed regions (with cancellation) and
        advance every updated relation's live set — once per epoch, shared
        by every query.

        Device-resident: jitted sorted-merge/diff folds over the committed
        regions and the staged delta only; the compacted base region object
        passes through UNTOUCHED (no rebuild, no re-upload).

        ATOMIC (DESIGN.md §10): every fold output is computed into a
        staging list first — the store is not mutated until all folds
        succeeded, then the swap is a pure host assignment loop with no
        fault points.  A failure mid-commit (an injected
        ``store.commit.fold`` fault) therefore leaves the store
        bit-identical to the epoch boundary; :meth:`rollback` clears the
        staged batch.
        """
        with jax.profiler.TraceAnnotation("store.commit"):
            self._commit(ins, dels)

    def _commit(self, ins, dels):
        if self._staged is None:
            # raw commit without begin_epoch: net the args against the live
            # set first (a live "insert" or absent "delete" must be a no-op,
            # exactly as normalize guarantees on the staged path), then
            # stage — so projections and the live set fold the SAME batch
            raw = self._as_batches(ins, dels)
            self.stats.normalize_calls += 1  # matches the staged path
            netted = {
                rel: self._normalize_rel(
                    rel,
                    np.concatenate([ri, rd]),
                    np.concatenate([np.ones(ri.shape[0], np.int32),
                                    -np.ones(rd.shape[0], np.int32)]))
                for rel, (ri, rd) in raw.items()}
            self.begin_epoch(netted)
        batches = self._staged
        if not self.device_resident:
            self._staged = None
            self.stats.commit_calls += 1
            self.stats.epochs += 1
            self._commit_host(batches)
            self._sync_compile_stats()
            return
        use_k = _merge_kernel_on()
        # ---- stage: compute every fold output, store untouched ------------
        staged_rels = []  # (st, new_cins, new_cdel, n_live)
        for rel, (r_ins, r_dels) in batches.items():
            if not (r_ins.size or r_dels.size):
                continue
            st = self._rel(rel)
            # live-set LSM fold (per relation; shard-local when sharded).
            # Delta indices ride the pinned (rel, "delta") rung; both
            # committed outputs share ONE (rel, "committed") rung — tied
            # caps halve the fold-signature space and a rung only ever
            # grows between compactions (ratchet hysteresis).
            faults.fire("store.commit.fold")
            li = _packed_index(r_ins, self.shard_w, st.arity,
                               capacity=self._delta_cap(rel,
                                                        r_ins.shape[0]),
                               mesh=self.mesh)
            self.ratchet.observe(("delta", rel), li.key.shape[-1])
            ld = _packed_index(r_dels, self.shard_w, st.arity,
                               capacity=self._delta_cap(rel,
                                                        r_dels.shape[0]),
                               mesh=self.mesh)
            self.ratchet.observe(("delta", rel), ld.key.shape[-1])
            nb, nci, ncd = st.n_live
            need = max(_maxn(np.asarray(nci)
                             + np.asarray(_count_of(li, self.stats))),
                       _maxn(np.asarray(ncd)
                             + np.asarray(_count_of(ld, self.stats))))
            cc = self._committed_cap(rel, need)
            with _device_scope():
                new_ci, new_cd = _commit_fold(
                    st.lb, st.lc_ins, st.lc_del, li, ld,
                    cins_cap=cc, cdel_cap=cc,
                    sharded=bool(self.shard_w), use_kernel=use_k)
            staged_rels.append((st, new_ci, new_cd,
                                [nb, _count_of(new_ci, self.stats),
                                 _count_of(new_cd, self.stats)]))
        # per-projection folds (vmapped over shards when distributed)
        staged_projs = []  # (reg, d_cins, d_cdel, empty_ins, empty_dels)
        derived_dirty = []
        for reg in self.projections.values():
            r_ins, r_dels = batches.get(
                reg.rel, (np.zeros((0, reg.arity), np.int32),) * 2)
            if reg.derived:
                if r_ins.size or r_dels.size:
                    derived_dirty.append(reg)  # committed rows changed
                continue
            if not (r_ins.size or r_dels.size):
                continue  # untouched relation: regions pass through
            faults.fire("store.commit.fold")
            need = max(
                _maxn(np.asarray(reg.n_cins)
                      + np.asarray(_count_of(reg.d_uins, self.stats))),
                _maxn(np.asarray(reg.n_cdel)
                      + np.asarray(_count_of(reg.d_udel, self.stats))))
            cc = self._committed_cap(reg.rel, need)
            with _device_scope():
                d_cins, d_cdel = _commit_fold(
                    reg.d_base, reg.d_cins, reg.d_cdel, reg.d_uins,
                    reg.d_udel, cins_cap=cc, cdel_cap=cc,
                    sharded=bool(self.shard_w), use_kernel=use_k)
            staged_projs.append((reg, d_cins, d_cdel,
                                 r_ins[:0], r_dels[:0]))
        # ---- swap: pure host assignments, no fault points -----------------
        self._staged = None
        for st, new_ci, new_cd, n_live in staged_rels:
            st.lc_ins, st.lc_del = new_ci, new_cd
            st.n_live = n_live
            st.mirror = None
        for reg in derived_dirty:
            reg._derived_cache.clear()
        for reg, d_cins, d_cdel, e_ins, e_dels in staged_projs:
            reg.d_cins, reg.d_cdel = d_cins, d_cdel
            reg.n_cins = _count_of(d_cins, self.stats)
            reg.n_cdel = _count_of(d_cdel, self.stats)
            reg.set_uncommitted(e_ins, e_dels)
            # commit never touches d_base: keep its mirror (compaction's
            # full clear is the one that must drop it)
            reg._mirror.pop("cins", None)
            reg._mirror.pop("cdel", None)
        self.stats.commit_calls += 1
        self.stats.epochs += 1
        self._maybe_compact()
        self._sync_compile_stats()

    def rollback(self) -> None:
        """Return the store to the epoch boundary: drop the staged batch
        and reset every projection's uncommitted region to empty.  Exact
        by construction — :meth:`commit` swaps nothing in until every fold
        has succeeded, so a failure between :meth:`begin_epoch` and a
        completed commit leaves all committed regions untouched."""
        self._staged = None
        for reg in self.projections.values():
            empty = np.zeros((0, reg.arity), np.int32)
            reg.set_uncommitted(empty, empty)
        self.stats.rollbacks += 1

    def _commit_host(self, batches: Dict):
        for reg in self.projections.values():
            r_ins, r_dels = batches.get(
                reg.rel, (np.zeros((0, reg.arity), np.int32),) * 2)
            if reg.derived:
                if r_ins.size or r_dels.size:
                    reg._derived_cache.clear()
                continue
            h = reg._host
            cins = np.unique(np.concatenate(
                [_diff_rows(h["cins"], r_dels),
                 _diff_rows(r_ins, h["cdel"])]),
                axis=0) if (r_ins.size or h["cins"].size) else h["cins"]
            cdel = np.unique(np.concatenate(
                [h["cdel"], _inter_rows(r_dels, h["base"])]), axis=0) \
                if (r_dels.size or h["cdel"].size) else h["cdel"]
            h["cins"], h["cdel"] = cins, cdel
            reg.refresh(("cins", "cdel"))
            reg.set_uncommitted(r_ins[:0], r_dels[:0])
        for rel, (ins, dels) in batches.items():
            st = self._rel(rel)
            if not (ins.size or dels.size):
                continue
            if st.arity == 2:
                # incremental sorted maintenance of the packed live cache
                # (and the rows derived from it): O(|E|) memmove, no
                # re-pack, no re-sort
                if ins.size:
                    pi = np.sort(_pack2(ins[:, 0], ins[:, 1]))
                    st.packed = np.insert(
                        st.packed, np.searchsorted(st.packed, pi), pi)
                if dels.size:
                    pd = np.sort(_pack2(dels[:, 0], dels[:, 1]))
                    pos = np.searchsorted(st.packed, pd)
                    # normalize guarantees dels ⊆ live, but stay tolerant
                    # of raw commit() calls: only positions that actually
                    # match are removed
                    hit = (pos < st.packed.shape[0]) & \
                        (st.packed[np.minimum(
                            pos, max(st.packed.shape[0] - 1, 0))] == pd)
                    st.packed = np.delete(st.packed, pos[hit])
                st.rows = _unpack2(st.packed)
            else:
                rows = st.rows
                if dels.size:
                    rows = rows[~rows_isin(rows, dels)]
                if ins.size:
                    rows = np.unique(np.concatenate([rows, ins]), axis=0)
                st.rows = rows
                self._refresh_host_cache(st)
        self._maybe_compact()

    # -- durability (DESIGN.md §9) -------------------------------------
    SNAPSHOT_FORMAT = 1

    @staticmethod
    def _index_parts(idx: IndexData):
        parts = [("key", idx.key), ("val", idx.val), ("n", idx.n)]
        if idx.lo is not None:
            parts.append(("lo", idx.lo))
        return parts

    def snapshot(self) -> Tuple[List[np.ndarray], dict]:
        """Serialize the store's dynamic state to ``(leaves, meta)`` —
        the leaves are host arrays in ``meta["names"]`` order (ready for
        ``repro.checkpoint.save_pytree(leaves, ..., extra=meta)``), meta
        is a JSON-safe dict.

        Captured per relation: the live three-region LSM (sorted device
        regions, composite ``lo`` words included) and its exact counts;
        per non-derived projection: the base/cins/cdel regions and counts;
        plus both Ratchet mark sets (so a restored store re-requests the
        SAME buffer shapes — prewarmed executables stay hot) and the epoch
        counters.  Sharded stores serialize per shard: every leaf keeps
        its leading [w] worker axis.

        Must be called at an epoch boundary (nothing staged); the staged
        uncommitted regions are transient by design — a WAL records the
        raw batches instead (``repro.serve.wal``)."""
        if not self.device_resident:
            raise NotImplementedError(
                "snapshot() serializes the device-resident store; the "
                "legacy host store is already plain numpy state")
        if self._staged is not None:
            raise SnapshotError(
                "snapshot mid-epoch: commit (or rollback) the staged batch "
                "first — snapshots are epoch-boundary consistent")
        leaves: List[np.ndarray] = []
        names: List[str] = []

        def emit(prefix, idx):
            for suffix, arr in self._index_parts(idx):
                names.append(f"{prefix}.{suffix}")
                leaves.append(np.asarray(arr))

        meta_rels = {}
        for rel in sorted(self._rels):
            st = self._rels[rel]
            for region, idx in (("lb", st.lb), ("lc_ins", st.lc_ins),
                                ("lc_del", st.lc_del)):
                emit(f"rel/{rel}/{region}", idx)
            meta_rels[rel] = {
                "arity": st.arity,
                "n_live": [np.asarray(n).tolist() for n in st.n_live]}
        projs = []
        for i, (pkey, reg) in enumerate(
                sorted(self.projections.items(), key=lambda kv: repr(kv[0]))):
            spec = {"rel": reg.rel, "key_pos": list(reg.key_pos),
                    "ext_pos": int(reg.ext_pos),
                    "rel_arity": int(reg.rel_arity),
                    "narrow": bool(reg.narrow),
                    "derived": bool(reg.derived)}
            if not reg.derived:
                for region in ("d_base", "d_cins", "d_cdel"):
                    emit(f"proj/{i}/{region}", getattr(reg, region))
                spec["n_base"] = np.asarray(reg.n_base).tolist()
                spec["n_cins"] = np.asarray(reg.n_cins).tolist()
                spec["n_cdel"] = np.asarray(reg.n_cdel).tolist()
            projs.append(spec)
        st_ = self.stats
        meta = {
            "format": self.SNAPSHOT_FORMAT,
            "shard_w": int(self.shard_w),
            "compact_ratio": float(self.compact_ratio),
            "rels": meta_rels,
            "projections": projs,
            "ratchet": [[list(k), v] for k, v in
                        sorted(self.ratchet.marks().items(),
                               key=lambda kv: repr(kv[0]))],
            "base_ratchet": [[list(k), v] for k, v in
                             sorted(self.base_ratchet.marks().items(),
                                    key=lambda kv: repr(kv[0]))],
            "stats": {f: getattr(st_, f) for f in
                      ("normalize_calls", "commit_calls", "compactions",
                       "epochs", "live_compactions")},
            "names": names,
        }
        return leaves, meta

    def restore(self, leaves: List[np.ndarray], meta: dict) -> None:
        """Rebuild this store's dynamic state from a :meth:`snapshot`,
        in place — engines holding a reference re-resolve their regions
        through ``indices_for`` each epoch, so they pick the restored
        truth up without rebuilding.  The mesh width must match the
        snapshot's (failover restores onto the same topology)."""
        if meta.get("format") != self.SNAPSHOT_FORMAT:
            raise ValueError(
                f"unknown snapshot format {meta.get('format')!r}")
        if int(meta["shard_w"]) != int(self.shard_w):
            raise ValueError(
                f"snapshot was taken on a shard_w={meta['shard_w']} store; "
                f"this store has shard_w={self.shard_w} — restore onto the "
                "same mesh width")
        if not self.device_resident:
            raise NotImplementedError(
                "restore() targets the device-resident store")
        by_name = dict(zip(meta["names"], leaves))
        if len(by_name) != len(meta["names"]):
            raise ValueError("snapshot leaves do not match meta['names']")

        def put(x):  # default placement for an unsharded store
            return csr.place_worker_stack(x, self.mesh)

        def pull(prefix) -> IndexData:
            lo = by_name.get(f"{prefix}.lo")
            return IndexData(put(by_name[f"{prefix}.key"]),
                             put(by_name[f"{prefix}.val"]),
                             put(by_name[f"{prefix}.n"]),
                             None if lo is None else put(lo))

        def nval(v):
            arr = np.asarray(v, np.int64)
            return arr if self.shard_w else int(arr)

        # ratchet marks FIRST: the empty delta regions built below must
        # land on the snapshot's pinned rungs, not re-derive fresh ones
        for ratchet, recs in ((self.ratchet, meta["ratchet"]),
                              (self.base_ratchet, meta["base_ratchet"])):
            ratchet.reset()
            for key, cap in recs:
                ratchet.observe(tuple(key), int(cap))
        self._rels = {}
        for rel, rec in meta["rels"].items():
            st = _RelLive(arity=int(rec["arity"]))
            st.lb = pull(f"rel/{rel}/lb")
            st.lc_ins = pull(f"rel/{rel}/lc_ins")
            st.lc_del = pull(f"rel/{rel}/lc_del")
            st.n_live = [nval(n) for n in rec["n_live"]]
            st.mirror = None
            self._rels[rel] = st
        self.projections = {}
        for i, spec in enumerate(meta["projections"]):
            reg = _Regions(tuple(spec["key_pos"]), int(spec["ext_pos"]),
                           rel=spec["rel"], rel_arity=int(spec["rel_arity"]),
                           shard_w=self.shard_w, mesh=self.mesh,
                           device_resident=True,
                           narrow=bool(spec["narrow"]),
                           derived=bool(spec["derived"]), _store=self)
            if not reg.derived:
                reg.d_base = pull(f"proj/{i}/d_base")
                reg.d_cins = pull(f"proj/{i}/d_cins")
                reg.d_cdel = pull(f"proj/{i}/d_cdel")
                reg.n_base = nval(spec["n_base"])
                reg.n_cins = nval(spec["n_cins"])
                reg.n_cdel = nval(spec["n_cdel"])
                empty = np.zeros((0, reg.arity), np.int32)
                reg.set_uncommitted(empty, empty)
            self.projections[(spec["rel"], tuple(spec["key_pos"]),
                              int(spec["ext_pos"]))] = reg
        for f, v in meta["stats"].items():
            setattr(self.stats, f, int(v))
        self._staged = None
        self._sync_compile_stats()


class DeltaBigJoin:
    """Incremental maintenance of one query over dynamic n-ary relations.

    Every atom may read any stored relation (the single binary ``edge``
    relation of subgraph queries, the ternary ``tri`` relation of §5.4, a
    4-ary relation, or a mix); each dQ_i seeds from ITS atom's relation
    batch and the engine runs the same dataflow over all of them.

    Region/commit bookkeeping lives in a :class:`RegionStore`; by default the
    engine owns a private one, but a shared store may be injected (``store=``)
    so many engines ride one graph with one commit per epoch — that is what
    :class:`repro.api.GraphSession` does.  Prefer the session facade for new
    code; this class remains the single-query engine underneath it.
    """

    def __init__(self, query: Query, initial_edges,
                 cfg: BigJoinConfig = BigJoinConfig(mode="collect"),
                 compact_ratio: float = 0.5,
                 store: Optional[RegionStore] = None,
                 device_resident: bool = True):
        self.query = query
        self.cfg = cfg
        self.compact_ratio = compact_ratio
        self.device_resident = device_resident
        self._prewarm_args: Optional[Tuple[int, Optional[int]]] = None
        self.plans: List[Plan] = [make_delta_plan(dq)
                                  for dq in delta_queries(query)]
        if store is None:
            store = self._new_store(initial_edges, compact_ratio)
        self.store = store
        for plan in self.plans:
            self.store.ensure_plan(plan)

    def _new_store(self, edges, compact_ratio: float) -> RegionStore:
        """Private store; the distributed engine overrides this to build
        worker-sharded device regions."""
        return RegionStore(edges, shard_w=0, compact_ratio=compact_ratio,
                           device_resident=self.device_resident)

    # store delegation (public surface predating RegionStore) --------------
    @property
    def edges(self) -> np.ndarray:
        return self.store.edges

    @property
    def projections(self) -> Dict[Projection, _Regions]:
        return self.store.projections

    def normalize(self, updates, weights=None):
        return self.store.normalize(updates, weights)

    def _maybe_compact(self, force: bool = False):
        self.store._maybe_compact(force)

    def _run_plan(self, plan: Plan, indices: Indices, seed: np.ndarray,
                  weights: np.ndarray) -> JoinResult:
        """Run one delta query's dataflow; overridden by the mesh engine."""
        return run_bigjoin(plan, indices, seed, weights, cfg=self.cfg)

    def prewarm(self, update_batch: int,
                horizon: Optional[int] = None) -> int:
        """AOT-compile every (step, seed_step, committed-rung) signature
        this engine's delta plans can request for batches ≤ ``update_batch``
        (the local half of ``GraphSession.prewarm``; the store's fold
        ladder is warmed separately by ``RegionStore.prewarm_folds``).
        Returns the compile events spent."""
        from repro.core.bigjoin import _compiled_fns, make_state
        ub = max(int(update_batch), 1)
        self._prewarm_args = (ub, horizon)
        snap = compilestats.snapshot()
        for plan in self.plans:
            step, seed_step = _compiled_fns(plan, self.cfg)
            state_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                make_state(plan, self.cfg))
            Sc = int(self.cfg.seed_chunk)
            pfx = jax.ShapeDtypeStruct((Sc, plan.seed_width), jnp.int32)
            wts = jax.ShapeDtypeStruct((Sc,), jnp.int32)
            valid = jax.ShapeDtypeStruct((Sc,), jnp.bool_)
            rels = {rel for _id, rel, *_ in plan.index_ids()}
            # relations cross committed rungs independently, so warm the
            # reachable rung CROSS-PRODUCT, not just the same-rung
            # diagonal (bounded subset over PREWARM_CROSS_CAP combos —
            # see _rung_combos / DESIGN.md §8)
            ladders = {rel: self.store.committed_ladder(rel, ub, horizon)
                       for rel in rels}
            for combo in _rung_combos(ladders):
                idx = self.store.indices_sds_for(plan, combo, ub)
                _warm_call(seed_step, state_sds, idx, pfx, wts, valid)
                _warm_call(step, state_sds, idx)
        return compilestats.since(snap)

    # -- overflow recovery (DESIGN.md §10) ------------------------------
    MAX_ESCALATIONS = 3  # per plan run, before the overflow surfaces

    def _escalate(self, exc: CapacityOverflow) -> None:
        """Recover from one :class:`CapacityOverflow`: bump the offending
        capacity rung(s) on the store ratchet (monotone marks — they
        serialize with snapshots, so an escalation survives failover),
        rebuild this engine's config on the new rungs, and re-prewarm so
        the replay runs on AOT-compiled signatures.  Re-raises when the
        overflow names no buffer this engine can grow."""
        qn = self.query.name
        r = self.store.ratchet
        cfg, changed = self.cfg, False
        if exc.kinds & ESCALATES_OUT:
            new_out = r.escalate(("cap", "out", qn),
                                 floor=cfg.out_capacity)
            cfg = dataclasses.replace(cfg, out_capacity=new_out)
            changed = True
        if exc.kinds & ESCALATES_BATCH:
            new_b = r.escalate(("cap", "batch", qn), floor=cfg.batch)
            cfg = dataclasses.replace(
                cfg, batch=new_b, seed_chunk=max(cfg.seed_chunk, new_b))
            changed = True
        if not changed:
            raise exc
        self.cfg = cfg
        self.store.stats.escalations += 1
        self._reprewarm()

    def _reprewarm(self) -> None:
        """Re-run prewarm (if this engine was ever prewarmed) so the new
        escalated signatures are AOT-compiled off the serving path; the
        compiles are accounted separately (``escalation_compiles``) so the
        zero-serving-compiles gate can subtract them."""
        if self._prewarm_args is None:
            return
        snap = compilestats.snapshot()
        self.prewarm(*self._prewarm_args)
        self.store.stats.escalation_compiles += compilestats.since(snap)

    def _run_plan_escalating(self, plan: Plan, seed: np.ndarray,
                             weights: np.ndarray) -> JoinResult:
        """One plan run with escalate-and-replay: the seed is host-staged
        and the store is read-only during the run, so a replay after a
        rung bump is deterministic and exact."""
        for attempt in range(self.MAX_ESCALATIONS + 1):
            try:
                return self._run_plan(plan, self.store.indices_for(plan),
                                      seed, weights)
            except CapacityOverflow as exc:
                if attempt >= self.MAX_ESCALATIONS:
                    raise
                self._escalate(exc)
                self.store.stats.replays += 1
        raise AssertionError("unreachable")

    # ------------------------------------------------------------------
    def run_delta_plans(self, ins, dels=None) -> DeltaResult:
        """Evaluate dAQ_1..dAQ_n for one staged batch (the store must have
        ``begin_epoch``-ed it); does NOT commit — the caller owns the epoch
        boundary, so a facade can run many queries off one staged batch.

        ``(ins, dels)`` edge arrays, or the per-relation batch dict —
        each dQ_i seeds from the batch of ITS seed atom's relation (n-ary
        dR tuples seed the dataflow at P_r, plan.seed_width)."""
        batches = self.store._as_batches(ins, dels)
        per_dq: List[JoinResult] = []
        total = 0
        tuples, wts = [], []
        for plan in self.plans:
            rel = plan.query.atoms[plan.seed_atom].rel
            r_ins, r_dels = batches.get(
                rel, (np.zeros((0, 2), np.int32),) * 2)
            if r_ins.size == 0 and r_dels.size == 0:
                continue  # this relation did not change: dQ_i is empty
            delta_rows = np.concatenate([r_ins, r_dels], axis=0)
            delta_w = np.concatenate([
                np.ones(r_ins.shape[0], np.int32),
                -np.ones(r_dels.shape[0], np.int32)])
            seed = delta_rows[:, list(plan.seed_cols)]
            res = self._run_plan_escalating(plan, seed, delta_w)
            per_dq.append(res)
            total += res.count
            if res.tuples is not None and res.tuples.size:
                tuples.append(res.tuples)
                wts.append(res.weights)
        out_t = np.concatenate(tuples) if tuples else None
        out_w = np.concatenate(wts) if wts else None
        return DeltaResult(total, out_t, out_w, per_dq)

    def apply(self, updates, weights=None) -> DeltaResult:
        """Process one update batch (edge arrays, or a per-relation dict
        ``{rel: (rows, weights)}``): emit output changes, then commit."""
        batches = self.store.normalize(updates, weights)
        if not isinstance(batches, dict):
            batches = {"edge": batches}
        if all(i.size == 0 and d.size == 0 for i, d in batches.values()):
            # net-zero batch (no-op inserts of live tuples, deletes of
            # absent tuples, +/- cancellations): an EXACT no-op — no region
            # rebuilds, no compaction, no dataflow run.
            return DeltaResult(0, None, None, [])
        self.store.begin_epoch(batches)
        result = self.run_delta_plans(batches)
        self.store.commit(batches)
        return result


def rows_isin(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Row-membership mask of ``a``'s rows in ``b`` (both [N, m] int).

    Packed-row diff: rows are mapped to dense ids by one ``np.unique`` over
    the concatenation, then compared with ``np.isin`` on the id vectors — no
    Python set-of-tuples.  O((Na+Nb) log) and fully vectorized; this is the
    stress suite's hot path (delta_oracle on every update batch).
    """
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape[0] == 0 or b.shape[0] == 0:
        return np.zeros(a.shape[0], bool)
    both = np.concatenate([a, b], axis=0)
    _, inv = np.unique(both, axis=0, return_inverse=True)
    inv = inv.reshape(-1)  # numpy>=2.0 may return [N,1]
    return np.isin(inv[:a.shape[0]], inv[a.shape[0]:])


def canon_signed(tuples: Optional[np.ndarray],
                 weights: Optional[np.ndarray]) -> list:
    """Canonical form of a signed tuple multiset: sorted (tuple, net
    weight != 0) pairs.  THE comparison key of every bit-exact
    differential (tests, subprocess harnesses, benchmarks, examples) —
    one implementation, so the checks can never drift."""
    if tuples is None or tuples.size == 0:
        return []
    uniq, inv = np.unique(tuples, axis=0, return_inverse=True)
    net = np.zeros(uniq.shape[0], np.int64)
    np.add.at(net, inv.reshape(-1), weights)
    return sorted((tuple(r), int(n)) for r, n in zip(uniq, net) if n != 0)


def delta_oracle(query: Query, edges_before, edges_after
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Ground truth: signed difference of full recomputation.

    ``edges_before`` / ``edges_after`` are edge arrays (sugar) or full
    relation dicts ``{rel: rows}``.  Returns (tuples [N, m] int32, weights
    [N] ±1) with the added rows first, each block in lexicographic row
    order (``np.unique`` order — the same order the old set-of-tuples
    implementation produced via ``sorted``).
    """
    from repro.core.generic_join import generic_join
    before = edges_before if isinstance(edges_before, dict) \
        else {"edge": edges_before}
    after = edges_after if isinstance(edges_after, dict) \
        else {"edge": edges_after}
    a, _ = generic_join(query, before)
    b, _ = generic_join(query, after)
    m = query.num_attrs
    a = np.unique(np.asarray(a, np.int32).reshape(-1, m), axis=0)
    b = np.unique(np.asarray(b, np.int32).reshape(-1, m), axis=0)
    added = b[~rows_isin(b, a)]
    removed = a[~rows_isin(a, b)]
    t = np.concatenate([added, removed]).astype(np.int32).reshape(-1, m)
    w = np.concatenate([np.ones(added.shape[0], np.int32),
                        -np.ones(removed.shape[0], np.int32)])
    return t, w
