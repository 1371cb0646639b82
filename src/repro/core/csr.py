"""Sorted-array extension indices (the TPU-native ``Ext``, §2.2).

The paper requires, for each relation/bound-prefix pair, an index exposing:
  (i)   |Ext(p)|            -- count          (O(1) in the paper)
  (ii)  contents of Ext(p)  -- slice          (O(|Ext(p)|))
  (iii) e in Ext(p)         -- membership     (O(1) in the paper)

Hash tables give these on CPUs; on TPUs pointer-chasing is hostile, so we use
*sorted dual arrays*: a packed 64-bit key column (the bound prefix) and a
32-bit value column (the extension), sorted lexicographically.  Counts and
slices come from two ``searchsorted`` probes; membership is a fixed-depth
binary search over the (key,val) pairs — O(log IN) instead of O(1), the same
trade EmptyHeaded makes with its sorted set layouts.

Everything here is a pytree of jnp arrays, so indices shard with
``jax.device_put`` / ``shard_map`` like any other model state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compilestats

# Sentinel keys strictly larger than any real key.  Wide (int64) keys pack
# two int32 columns as a<<32|b with a, b < 2^31, so their maximum is below
# int64-max and the int64 sentinel covers the FULL vertex-id range; narrow
# (int32) keys use int32-max, so ids must stay < 2^31 - 1 (builds auto-widen
# when they don't, and the store's id-domain guard rejects the boundary).
SENTINEL = np.int64(np.iinfo(np.int64).max)
SENTINEL32 = np.int32(2**31 - 1)

# Canonical segment length of the two-level membership kernels (one VPU lane
# row); kernels/intersect/intersect.py imports it from here.  Index
# capacities are rounded up to SEG multiples so the kernels' segment-major
# [cap/SEG, SEG] view is a free reshape (no pad/concat per probe).
SEG = 128

# The mesh axis the distributed engines shard worker stacks over.
AXIS = "workers"


def worker_sharding(mesh, spec=None):
    """``NamedSharding(mesh, spec)`` on the mesh the engines' shard_map
    programs run on (``GraphSession``/``SessionPool`` own it), or None
    without a mesh (a store that vmaps its w shards on one device keeps
    the default placement).  ``spec`` defaults to the leading worker axis
    split over all mesh axes, one worker row per device."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    if spec is None:
        names = tuple(mesh.axis_names)
        spec = PartitionSpec(names[0] if len(names) == 1 else names)
    return NamedSharding(mesh, spec)


def place_worker_stack(x: np.ndarray, mesh=None) -> jax.Array:
    """Upload a [w, ...] per-worker stack with one shard per device of
    ``mesh``, so the engines' shard_map programs and folds take it where
    it lies; default placement without a mesh."""
    sharding = worker_sharding(mesh)
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


def place_for_workers(x: np.ndarray, mesh=None) -> jax.Array:
    """Upload a delta-sized host array for use with a sharded store:
    replicated on the store's ``mesh`` (an explicit host-to-device copy,
    never a device-to-device one inside a fold), default placement
    without a mesh."""
    from jax.sharding import PartitionSpec
    sharding = worker_sharding(mesh, PartitionSpec())
    return jnp.asarray(x) if sharding is None else jax.device_put(x, sharding)


def round_capacity(cap: int) -> int:
    return -(-max(int(cap), 1) // SEG) * SEG


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IndexData:
    """One sorted (key[, lo], val) extension index.

    key: [N] int64, nondecreasing (packed bound-prefix values)
    val: [N] int32, nondecreasing within equal keys
    n:   [] int32, number of live entries (rest is sentinel padding)
    lo:  [N] int64 or None — the secondary word of a *composite* key.

    With <= 2 bound columns the prefix packs into ``key`` alone (``lo`` is
    None).  3 or 4 bound columns use the generalized lexicographic composite
    key: ``key = c0`` and ``lo = c1<<32|c2`` (3 cols) or ``key = c0<<32|c1``
    and ``lo = c2<<32|c3`` (4 cols); entries are lex-sorted by
    (key, lo, val) and every probe is a fixed-depth two-word lex binary
    search (``lex_searchsorted_cols``).  The 3-col split deliberately keeps
    the hi word a SINGLE column so it stays eligible for the narrow (int32)
    dtype — ``lo`` is always int64.
    """

    key: jax.Array
    val: jax.Array
    n: jax.Array
    lo: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.key, self.val, self.n, self.lo), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.key.shape[0]

    @property
    def composite(self) -> bool:
        return self.lo is not None

    def key_cols(self) -> Tuple[jax.Array, ...]:
        """The lex-ordered key words: (key,) or (key, lo)."""
        return (self.key,) if self.lo is None else (self.key, self.lo)


# A packed probe key: one array (<= 2 bound columns) or a (hi, lo) pair.
PackedKey = Union[jax.Array, np.ndarray, Tuple]


def pack_key(cols: Sequence) -> PackedKey:
    """Pack 1..4 non-negative int32 columns into a lexicographic key.

    1 column  -> int64 key (may be narrowed to int32 by the index builders);
    2 columns -> ``c0<<32 | c1`` int64;
    3 columns -> the composite pair ``(c0, c1<<32|c2)`` — hi stays a single
                 column so the builders may narrow it to int32;
    4 columns -> the composite pair ``(c0<<32|c1, c2<<32|c3)``.

    THE one key-packing implementation — ``bigjoin._pack_cols``,
    ``generic_join``'s host indices, and the region stores all delegate
    here, so device and host keys can never drift.
    """
    cols = tuple(cols)
    xp = jnp if isinstance(cols[0], jax.Array) else np
    if len(cols) == 1:
        return cols[0].astype(xp.int64)
    if len(cols) == 2:
        return (cols[0].astype(xp.int64) << 32) | cols[1].astype(xp.int64)
    if len(cols) == 3:
        return cols[0].astype(xp.int64), ((cols[1].astype(xp.int64) << 32)
                                          | cols[2].astype(xp.int64))
    if len(cols) == 4:
        return ((cols[0].astype(xp.int64) << 32) | cols[1].astype(xp.int64),
                (cols[2].astype(xp.int64) << 32) | cols[3].astype(xp.int64))
    raise ValueError(
        f"composite keys cover at most 4 int32 columns, got {len(cols)}")


def unpack_key(packed: PackedKey, num_cols: int) -> np.ndarray:
    """Inverse of :func:`pack_key` (host): [N, num_cols] int32 columns."""
    M = 0xFFFFFFFF
    if num_cols <= 2:
        p = np.asarray(packed, np.int64)
        if num_cols == 1:
            return p[:, None].astype(np.int32)
        return np.stack([(p >> 32).astype(np.int32),
                         (p & M).astype(np.int32)], 1)
    hi, lo = (np.asarray(packed[0], np.int64), np.asarray(packed[1],
                                                          np.int64))
    if num_cols == 3:
        cols = [hi.astype(np.int32)]
    else:
        cols = [(hi >> 32).astype(np.int32), (hi & M).astype(np.int32)]
    cols.extend([(lo >> 32).astype(np.int32), (lo & M).astype(np.int32)])
    return np.stack(cols, 1)


def unique_rows(rows: np.ndarray) -> np.ndarray:
    """``np.unique(rows, axis=0)`` for signed integer rows: the same rows in
    the same row-lex order, an order of magnitude faster at graph scale.

    Two columns that fit int32 sort as ONE packed int64 word (signed high
    word, low word offset by 2^31 so it orders as unsigned); wider rows use
    one lexsort plus an adjacent-difference mask — never the structured
    dtype sort ``np.unique(axis=0)`` runs."""
    rows = np.asarray(rows)
    if (rows.ndim != 2 or rows.shape[0] < 2
            or not np.issubdtype(rows.dtype, np.signedinteger)):
        return np.unique(rows, axis=0)
    i32 = np.iinfo(np.int32)
    if rows.shape[1] == 2 and (rows.dtype.itemsize <= 4 or (
            rows.min() >= i32.min and rows.max() <= i32.max)):
        a = rows.astype(np.int64)
        packed = np.unique((a[:, 0] << 32) | (a[:, 1] + 2**31))
        return np.stack([packed >> 32, (packed & 0xFFFFFFFF) - 2**31],
                        axis=1).astype(rows.dtype)
    s = rows[np.lexsort(rows.T[::-1])]
    keep = np.ones(s.shape[0], bool)
    keep[1:] = (s[1:] != s[:-1]).any(axis=1)
    return s[keep]


def single_word_hi(num_key_cols: int) -> bool:
    """True when the packed hi word holds at most ONE bound column, i.e. a
    single int32 id — the precondition for the narrow (int32) key dtype.
    1 bound column packs into hi alone; 3 bound columns split (c0, c1<<32|c2)
    so hi is again one column; 2/4 columns pack two ids into hi and need the
    full 64 bits."""
    return num_key_cols in (0, 1, 3)


def build_index(tuples: np.ndarray, key_pos: Tuple[int, ...], ext_pos: int,
                capacity: int | None = None,
                narrow: bool | None = None) -> IndexData:
    """Build an IndexData from relation tuples [T, arity] (numpy, host).

    Projects to (key columns, ext column), dedups, sorts.  ``capacity``
    (>= live size) allows preallocating room for future deltas.  ``narrow``
    overrides the key-dtype choice — the device-resident region folds merge
    deltas into long-lived indices, so both sides must agree on one dtype
    decided once per projection, not per build.
    """
    tuples = np.asarray(tuples)
    if tuples.ndim != 2:
        raise ValueError("tuples must be [T, arity]")
    key = pack_key(tuple(tuples[:, p].astype(np.int32) for p in key_pos)) \
        if key_pos else np.zeros(tuples.shape[0], np.int64)
    val = tuples[:, ext_pos].astype(np.int32)
    if isinstance(key, tuple):  # composite (hi, lo) key: 3-4 bound columns
        kvl = unique_rows(np.stack([key[0], key[1], val.astype(np.int64)],
                                   axis=1))
        key, lo, val = kvl[:, 0], kvl[:, 1], kvl[:, 2].astype(np.int32)
    else:
        kv = unique_rows(np.stack([key, val.astype(np.int64)], axis=1))
        key, lo, val = kv[:, 0], None, kv[:, 1].astype(np.int32)
    n = key.shape[0]
    cap = round_capacity(max(int(capacity or n), n, 1))
    # single-column hi words fit int32 -> halve hi-word bytes (HBM traffic)
    if narrow is None:
        narrow = single_word_hi(len(key_pos)) and (n == 0
                                                   or key.max() < SENTINEL32)
    narrow = narrow and single_word_hi(len(key_pos))
    kdt, sent = (np.int32, SENTINEL32) if narrow else (np.int64, SENTINEL)
    out_k = np.full(cap, sent, kdt)
    out_v = np.zeros(cap, np.int32)
    out_k[:n] = key.astype(kdt)
    out_v[:n] = val
    out_lo = None
    if lo is not None:
        out_lo = np.full(cap, SENTINEL, np.int64)
        out_lo[:n] = lo
        out_lo = jnp.asarray(out_lo)
    return IndexData(jnp.asarray(out_k), jnp.asarray(out_v),
                     jnp.asarray(n, jnp.int32), out_lo)


# Fibonacci-style multiplicative mix shared with the distributed layer:
# owner_of / shard_of MUST agree so host-built shards answer device routing.
SHARD_MIX = 0x9E3779B97F4A7C15
# second mix for folding a composite key's two words into one routing word
SHARD_MIX2 = 0xC2B2AE3D27D4EB4F


def combine_key(hi, lo):
    """Fold a composite (hi, lo) key into ONE 64-bit routing word.

    Collisions only affect placement, never answers — but host (np) and
    device (jnp) MUST agree, so both routes go through this one function."""
    xp = jnp if isinstance(hi, jax.Array) else np
    h = (hi.astype(xp.uint64) * xp.uint64(SHARD_MIX2)) ^ lo.astype(xp.uint64)
    return h.astype(xp.int64)


def shard_of(key: PackedKey, num_shards: int) -> np.ndarray:
    """Hash-partition owner of each packed key, [N] int32 in [0, num_shards)."""
    if isinstance(key, tuple):
        key = combine_key(*key)
    h = (key.astype(np.uint64) * np.uint64(SHARD_MIX)) >> np.uint64(33)
    return (h % np.uint64(max(num_shards, 1))).astype(np.int32)


def pow2_capacity(n: int) -> int:
    """SEG-aligned power-of-two capacity >= n (stable shapes across deltas).

    THE canonical capacity quantizer: every region, probe pad, seed chunk
    and AGM-derived buffer size in the repo goes through this one function
    (``delta._pow2`` and ``session._pow2`` are aliases), so the ladder of
    shapes that can ever key a jit cache is enumerable — see
    :func:`capacity_ladder` and DESIGN.md §8.
    """
    return round_capacity(1 << max(int(n) - 1, 0).bit_length())


# historical (pre-ladder) private name, kept for callers/tests
_pow2_capacity = pow2_capacity


def capacity_ladder(lo: int, hi: int) -> list:
    """All :func:`pow2_capacity` rungs covering live sizes in [lo, hi].

    ``pow2_capacity`` maps any size in (rung/2, rung] to ``rung``, so the
    rungs between ``pow2_capacity(lo)`` and ``pow2_capacity(hi)`` inclusive
    are exactly the capacities a buffer can take while its live size stays
    in the range — the shapes an AOT prewarm must compile."""
    lo_cap, hi_cap = pow2_capacity(lo), pow2_capacity(max(hi, lo))
    rungs = []
    c = lo_cap
    while c <= hi_cap:
        rungs.append(c)
        c = pow2_capacity(c + 1)
    return rungs


def build_sharded_index(tuples: np.ndarray, key_pos: Tuple[int, ...],
                        ext_pos: int, num_shards: int,
                        capacity: int | None = None,
                        narrow: bool | None = None, mesh=None) -> IndexData:
    """Hash-partition one extension index over ``num_shards`` workers.

    Returns an IndexData whose arrays carry a leading [w] worker axis
    (key/val: [w, cap]; n: [w]) ready to shard over a mesh axis.  Every
    (key, val) pair lands on exactly one worker — ``shard_of(key, w)`` —
    which is the paper's cluster-memory-linearity property (§3.2): the sum
    of live entries over workers equals the unsharded index size.

    Per-shard capacity is uniform (stacking needs one shape) and rounded to
    a SEG-aligned power of two of the largest shard, so shapes stay stable
    across update batches and the jit cache stays warm.  ``capacity`` is a
    per-shard floor.  Key narrowness (int32 vs int64) is decided globally so
    every shard row has one dtype and one sentinel.  ``mesh`` places the
    stacks one worker row per device (:func:`place_worker_stack`).
    """
    tuples = np.asarray(tuples)
    if tuples.ndim != 2:
        raise ValueError("tuples must be [T, arity]")
    w = max(int(num_shards), 1)
    key = pack_key(tuple(tuples[:, p].astype(np.int32) for p in key_pos)) \
        if key_pos else np.zeros(tuples.shape[0], np.int64)
    val = tuples[:, ext_pos].astype(np.int32)
    if isinstance(key, tuple):  # composite: ownership by the combined word
        kvl = unique_rows(np.stack([key[0], key[1], val.astype(np.int64)],
                                   axis=1))
        key, klo, val = kvl[:, 0], kvl[:, 1], kvl[:, 2].astype(np.int32)
        own = shard_of((key, klo), w)
    else:
        kv = unique_rows(np.stack([key, val.astype(np.int64)], axis=1))
        key, klo, val = kv[:, 0], None, kv[:, 1].astype(np.int32)
        own = shard_of(key, w)
    counts = np.bincount(own, minlength=w).astype(np.int64)
    cmax = int(counts.max()) if counts.size else 0
    cap = max(_pow2_capacity(cmax), round_capacity(int(capacity or 1)))
    if narrow is None:
        narrow = single_word_hi(len(key_pos)) and (key.size == 0
                                                   or key.max() < SENTINEL32)
    narrow = narrow and single_word_hi(len(key_pos))
    kdt, sent = (np.int32, SENTINEL32) if narrow else (np.int64, SENTINEL)
    out_k = np.full((w, cap), sent, kdt)
    out_v = np.zeros((w, cap), np.int32)
    out_lo = None if klo is None else np.full((w, cap), SENTINEL, np.int64)
    # rows are lexsorted by (key[, lo], val); a stable sort by owner keeps
    # each shard's rows sorted, which is the IndexData invariant.
    order = np.argsort(own, kind="stable")
    sk, sv = key[order].astype(kdt), val[order]
    sl = klo[order] if klo is not None else None
    offs = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    for i in range(w):
        lo, hi = offs[i], offs[i + 1]
        out_k[i, :hi - lo] = sk[lo:hi]
        out_v[i, :hi - lo] = sv[lo:hi]
        if out_lo is not None:
            out_lo[i, :hi - lo] = sl[lo:hi]
    def put(x):
        return place_worker_stack(x, mesh)

    return IndexData(put(out_k), put(out_v), put(counts.astype(np.int32)),
                     None if out_lo is None else put(out_lo))


def empty_index(capacity: int = 1, narrow: bool = True,
                composite: bool = False) -> IndexData:
    """Empty IndexData.  ``narrow`` applies to the hi word only (``lo`` is
    always int64); composite indices may be narrow when the hi word is a
    single column (the 3-col packing) — the caller decides, matching the
    projection's build-time dtype."""
    cap = round_capacity(capacity)
    kdt, sent = (jnp.int32, SENTINEL32) if narrow else (jnp.int64, SENTINEL)
    return IndexData(jnp.full(cap, sent, kdt),
                     jnp.zeros(cap, jnp.int32),
                     jnp.asarray(0, jnp.int32),
                     jnp.full(cap, SENTINEL, jnp.int64) if composite
                     else None)


# ---------------------------------------------------------------------------
# Queries (jnp, vectorized over a batch of probes).
# ---------------------------------------------------------------------------

def index_range(idx: IndexData, qkey: PackedKey
                ) -> Tuple[jax.Array, jax.Array]:
    """(start, count) of the extension list for each packed key [B].

    ``qkey`` is a single packed array, or a (hi, lo) pair probing a
    composite index; sentinel padding sorts above every real key, so the
    full-capacity search needs no live-count mask."""
    if idx.lo is None:
        start = jnp.searchsorted(idx.key, qkey, side="left")
        end = jnp.searchsorted(idx.key, qkey, side="right")
        return start.astype(jnp.int32), (end - start).astype(jnp.int32)
    qh, ql = qkey
    cap_n = jnp.asarray(idx.capacity, jnp.int32)
    start = lex_searchsorted_cols((idx.key, idx.lo), cap_n, (qh, ql), "left")
    end = lex_searchsorted_cols((idx.key, idx.lo), cap_n, (qh, ql), "right")
    return start.astype(jnp.int32), (end - start).astype(jnp.int32)


def index_count(idx: IndexData, qkey: jax.Array) -> jax.Array:
    return index_range(idx, qkey)[1]


def index_kth(idx: IndexData, start: jax.Array, k: jax.Array) -> jax.Array:
    """k-th extension given the range start (no bounds check: caller masks)."""
    pos = jnp.clip(start + k, 0, idx.capacity - 1)
    return idx.val[pos]


def lex_searchsorted_cols(cols: Tuple[jax.Array, ...], n: jax.Array,
                          qcols: Tuple[jax.Array, ...],
                          side: str = "left") -> jax.Array:
    """Lower/upper bound of each lex query in up-to-3 lex-sorted columns.

    The generalized fixed-depth binary search behind every probe: 2 columns
    is the classic (key, val) pair, 3 columns the composite-key
    (key, lo, val) triple.  Vectorized over the query batch; ``side="left"``
    returns the count of entries strictly below each query, ``side="right"``
    the count of entries <= it.
    """
    cap = cols[0].shape[0]
    right = side == "right"
    # +1: an interval of length 1 still needs one comparison to collapse
    depth = max(int(np.ceil(np.log2(max(cap, 2)))), 1) + 1
    lo = jnp.zeros(qcols[0].shape, jnp.int32)
    hi = jnp.broadcast_to(jnp.minimum(jnp.int32(cap), n.astype(jnp.int32)),
                          qcols[0].shape)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        midc = jnp.clip(mid, 0, cap - 1)
        less = jnp.zeros(qcols[0].shape, bool)
        eq = jnp.ones(qcols[0].shape, bool)
        for c, q in zip(cols, qcols):
            mc = c[midc]  # mixed-width compares promote, never truncate
            less = less | (eq & (mc < q))
            eq = eq & (mc == q)
        if right:
            less = less | eq
        lo = jnp.where(less & (lo < hi), mid + 1, lo)
        hi = jnp.where(~less & (lo < hi), mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, depth, body, (lo, hi))
    return lo


def lex_searchsorted(key: jax.Array, val: jax.Array, n: jax.Array,
                     qk: jax.Array, qv: jax.Array,
                     side: str = "left") -> jax.Array:
    """Two-column (key, val) lex bound — the jnp oracle mirrored by the
    Pallas ``intersect``/``merge`` kernels (see ``lex_searchsorted_cols``
    for the generalized composite-key form)."""
    return lex_searchsorted_cols((key, val), n, (qk, qv), side)


def index_member(idx: IndexData, qkey: PackedKey, qval: jax.Array
                 ) -> jax.Array:
    """Membership (qkey, qval) in the index, [B] bool — the pure-jnp oracle.

    Kernel routing happens one level up: ``VersionedIndex.signed_member``
    fuses all regions — composite (hi, lo) keys included — into one Pallas
    launch; this stays the bit-exact reference path.
    """
    qv = qval.astype(jnp.int32)
    if idx.lo is None:
        pos = lex_searchsorted(idx.key, idx.val, idx.n, qkey, qv)
        pos_c = jnp.clip(pos, 0, idx.capacity - 1)
        hit = (idx.key[pos_c] == qkey) & (idx.val[pos_c] == qv)
        return hit & (pos < idx.n)
    qh, ql = qkey
    pos = lex_searchsorted_cols((idx.key, idx.lo, idx.val), idx.n,
                                (qh, ql, qv))
    pos_c = jnp.clip(pos, 0, idx.capacity - 1)
    hit = ((idx.key[pos_c] == qh) & (idx.lo[pos_c] == ql)
           & (idx.val[pos_c] == qv))
    return hit & (pos < idx.n)


# ---------------------------------------------------------------------------
# Sorted-merge fold primitives (device-resident region maintenance).
#
# The incremental entry points of this module: instead of re-hashing and
# re-sorting all rows (``build_index``), an existing device-resident
# IndexData is updated by *rank-based sorted merge* against a sorted delta.
# The only non-trivial step is computing, for each entry of one set, its
# rank in the other (count of entries lexicographically < / <= it); with
# both ranks union/diff/intersect are pure static-shape scatters:
#
#     merge position of a[i] in a ∪ b  =  i + |{kept b < a[i]}|
#     merge position of b[j] in a ∪ b  =  |{a < b[j]}| + |{kept b before j}|
#     a[i] ∈ b                         ⇔  |{b <= a[i]}| > |{b < a[i]}|
#
# Cost is O((|a|+|b|)·log), i.e. proportional to the operands — the commit
# folds of `core/delta.py` only ever pass the committed regions and the
# update delta here, never the compacted base, which is how warm epoch cost
# stays a function of |Δ| + |committed| instead of |E|.
# ---------------------------------------------------------------------------

def index_ranks(a: IndexData, qk: PackedKey, qv: jax.Array,
                use_kernel: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(lt, le) int32 [B]: entries of ``a`` lexicographically < / <= each
    (qk[, qlo], qv) query.  ``use_kernel`` routes through the Pallas rank
    kernel (`kernels/merge`), composite (hi, lo) keys included — the jnp
    fixed-depth searches stay the bit-exact reference path."""
    qv = qv.astype(jnp.int32)
    if a.lo is not None:
        qh, ql = qk
        if use_kernel:
            from repro.kernels.merge.ops import rank_lt_le
            return rank_lt_le(a.key, a.val, a.n, qh, qv, lo=a.lo, qlo=ql)
        cols = (a.key, a.lo, a.val)
        qcols = (qh.astype(jnp.int64), ql.astype(jnp.int64), qv)
        return (lex_searchsorted_cols(cols, a.n, qcols, "left"),
                lex_searchsorted_cols(cols, a.n, qcols, "right"))
    qk = qk.astype(a.key.dtype)
    if use_kernel:
        from repro.kernels.merge.ops import rank_lt_le
        return rank_lt_le(a.key, a.val, a.n, qk, qv)
    lt = lex_searchsorted(a.key, a.val, a.n, qk, qv, side="left")
    le = lex_searchsorted(a.key, a.val, a.n, qk, qv, side="right")
    return lt, le


def _empty_like_caps(key_dtype, capacity: int, composite: bool = False):
    sent = SENTINEL32 if key_dtype == jnp.int32 else SENTINEL
    return (jnp.full(capacity, sent, key_dtype),
            jnp.zeros(capacity, jnp.int32),
            jnp.full(capacity, SENTINEL, jnp.int64) if composite else None)


def _qcols_of(d: IndexData) -> PackedKey:
    """An index's own keys viewed as a probe batch (for rank queries)."""
    return d.key if d.lo is None else (d.key, d.lo)


def _merge_core(a: IndexData, b: IndexData, capacity: int,
                use_kernel: bool = False) -> IndexData:
    """Sorted union a ∪ b into a fresh IndexData of static ``capacity``.

    Both operands are deduped lex-sorted (the IndexData invariant); entries
    present in both appear once (a's copy wins).  capacity must be
    >= |a| + |b| in the worst case; overflowing entries would be dropped,
    so callers size it from exact live counts."""
    cap = int(capacity)
    ii = jnp.arange(a.capacity, dtype=jnp.int32)
    jj = jnp.arange(b.capacity, dtype=jnp.int32)
    a_live = ii < a.n
    b_live = jj < b.n
    lt_a, le_a = index_ranks(a, _qcols_of(b), b.val, use_kernel)  # b in a
    keep_b = b_live & ~(le_a > lt_a)
    kept_cum = jnp.cumsum(keep_b.astype(jnp.int32))
    kept_excl = kept_cum - keep_b.astype(jnp.int32)
    pos_b = jnp.where(keep_b, lt_a + kept_excl, cap)
    lt_b, _ = index_ranks(b, _qcols_of(a), a.val, use_kernel)  # a in b
    # kept-b entries strictly below a[i] = prefix of keep_b over [0, lt_b)
    below = jnp.where(lt_b > 0,
                      kept_cum[jnp.clip(lt_b - 1, 0, b.capacity - 1)], 0)
    pos_a = jnp.where(a_live, ii + below, cap)
    out_k, out_v, out_lo = _empty_like_caps(a.key.dtype, cap,
                                            a.lo is not None)
    out_k = out_k.at[pos_a].set(a.key, mode="drop") \
                 .at[pos_b].set(b.key.astype(a.key.dtype), mode="drop")
    out_v = out_v.at[pos_a].set(a.val, mode="drop") \
                 .at[pos_b].set(b.val, mode="drop")
    if out_lo is not None:
        out_lo = out_lo.at[pos_a].set(a.lo, mode="drop") \
                       .at[pos_b].set(b.lo, mode="drop")
    n = a.n.astype(jnp.int32) + keep_b.sum(dtype=jnp.int32)
    return IndexData(out_k, out_v, n, out_lo)


def _select_core(a: IndexData, b: IndexData, capacity: int, keep_in_b: bool,
                 use_kernel: bool = False) -> IndexData:
    """Compact the entries of ``a`` (not) in ``b`` into static ``capacity``:
    keep_in_b=False is a \\ b (diff), True is a ∩ b (intersect)."""
    cap = int(capacity)
    ii = jnp.arange(a.capacity, dtype=jnp.int32)
    lt, le = index_ranks(b, _qcols_of(a), a.val, use_kernel)
    in_b = le > lt
    keep = (ii < a.n) & (in_b if keep_in_b else ~in_b)
    cum = jnp.cumsum(keep.astype(jnp.int32))
    pos = jnp.where(keep, cum - 1, cap)
    out_k, out_v, out_lo = _empty_like_caps(a.key.dtype, cap,
                                            a.lo is not None)
    out_k = out_k.at[pos].set(a.key, mode="drop")
    out_v = out_v.at[pos].set(a.val, mode="drop")
    if out_lo is not None:
        out_lo = out_lo.at[pos].set(a.lo, mode="drop")
    return IndexData(out_k, out_v, keep.sum(dtype=jnp.int32), out_lo)


@functools.partial(jax.jit, static_argnames=("capacity", "use_kernel"))
def merge_index(a: IndexData, b: IndexData, capacity: int,
                use_kernel: bool = False) -> IndexData:
    """Jitted sorted union (see `_merge_core`)."""
    compilestats.record("csr.merge_index")
    return _merge_core(a, b, capacity, use_kernel)


@functools.partial(jax.jit, static_argnames=("capacity", "use_kernel"))
def diff_index(a: IndexData, b: IndexData, capacity: int,
               use_kernel: bool = False) -> IndexData:
    """Jitted sorted difference a \\ b."""
    compilestats.record("csr.diff_index")
    return _select_core(a, b, capacity, False, use_kernel)


@functools.partial(jax.jit, static_argnames=("capacity", "use_kernel"))
def intersect_index(a: IndexData, b: IndexData, capacity: int,
                    use_kernel: bool = False) -> IndexData:
    """Jitted sorted intersection a ∩ b (probe-sized: O(|a|·log|b|))."""
    compilestats.record("csr.intersect_index")
    return _select_core(a, b, capacity, True, use_kernel)


# ---------------------------------------------------------------------------
# Graph convenience: the dual-CSR edge index.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Graph:
    """A directed graph as an edge list (numpy host container)."""

    edges: np.ndarray  # [E, 2] int32 (src, dst), deduped
    num_vertices: int

    @classmethod
    def from_edges(cls, edges: np.ndarray, num_vertices: int | None = None,
                   dedup: bool = True) -> "Graph":
        edges = np.asarray(edges, np.int32).reshape(-1, 2)
        if dedup and edges.size:
            edges = unique_rows(edges)
        nv = int(num_vertices if num_vertices is not None
                 else (edges.max() + 1 if edges.size else 0))
        return cls(edges, nv)

    @property
    def num_edges(self) -> int:
        return int(self.edges.shape[0])

    def forward(self, capacity: int | None = None) -> IndexData:
        """src -> dst (out-neighbour) index."""
        return build_index(self.edges, (0,), 1, capacity)

    def reverse(self, capacity: int | None = None) -> IndexData:
        """dst -> src (in-neighbour) index."""
        return build_index(self.edges, (1,), 0, capacity)

    def undirected(self) -> "Graph":
        e = np.concatenate([self.edges, self.edges[:, ::-1]], axis=0)
        return Graph.from_edges(e, self.num_vertices)

    def degree_relabel(self) -> "Graph":
        """Symmetry-breaking preprocessing (§5.4): relabel vertices by
        (degree, id) ascending and keep edges oriented low->high id."""
        deg = np.zeros(self.num_vertices, np.int64)
        np.add.at(deg, self.edges[:, 0], 1)
        np.add.at(deg, self.edges[:, 1], 1)
        order = np.lexsort((np.arange(self.num_vertices), deg))
        rank = np.empty(self.num_vertices, np.int32)
        rank[order] = np.arange(self.num_vertices, dtype=np.int32)
        e = rank[self.edges]
        lo = np.minimum(e[:, 0], e[:, 1])
        hi = np.maximum(e[:, 0], e[:, 1])
        keep = lo != hi
        return Graph.from_edges(np.stack([lo[keep], hi[keep]], 1),
                                self.num_vertices)
