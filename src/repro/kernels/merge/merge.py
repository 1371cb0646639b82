"""Pallas TPU kernel: batched merge ranks over a sorted (key, val) set.

The device-resident RegionStore (core/delta.py) maintains every index region
by *sorted merge*, never by rebuild.  The only non-trivial step of a sorted
merge/diff/intersect between deduped sets is computing, for each entry of one
set, its RANK in the other — the count of entries lexicographically `<` and
`<=` it.  With both counts every set operation is a pure scatter:

    merge position of a[i] in a ∪ b  =  i + |{b < a[i]}|
    merge position of b[j] in a ∪ b  =  j + |{a <= b[j]}|
    a[i] ∈ b                        ⇔  |{b <= a[i]}| > |{b < a[i]}|

so union/diff/intersect all reduce to one rank pass + one O(n) scatter — the
static-shape analogue of a two-pointer merge (the pointer advance *is* the
rank).  This kernel computes both counts for a BQ query tile per grid step
against the full VMEM-resident index, reusing the two-level segment-major
layout of the intersect kernel (DESIGN.md §2): a router binary search picks
each query's segment, one [BQ, SEG] row gather + lane-wise compares yield the
in-segment counts, and the segment base contributes ``seg * SEG`` entries
(everything in earlier segments is strictly below the query because the
router leader of the query's segment is `<=` it and entries are unique).

ref.py is the pure-jnp oracle (two fixed-depth lexicographic binary
searches); parity is bit-exact.  ops.py routes: compiled Mosaic on TPU
(VMEM-gated), interpreted kernel elsewhere — interpret mode lowers the
kernel body through XLA, so the CPU CI lane runs the same fused fold path
the TPU runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.csr import SEG  # canonical segment length (see csr.py)

BQ = 256  # queries per grid step


def _router_depth(num_segments: int) -> int:
    return max(int(np.ceil(np.log2(max(num_segments, 2)))), 1) + 1


def _rank_counts(keys2d: jax.Array, vals2d: jax.Array, n: jax.Array,
                 qk: jax.Array, qv: jax.Array,
                 los2d: jax.Array | None = None,
                 ql: jax.Array | None = None):
    """(lt, le) int32 [BQ]: entries lexicographically < / <= each query.

    keys2d/vals2d: [num_segments, SEG] sorted segment-major with sentinel
    padding (unique live entries); n: [] live count; qk/qv: [BQ].  For a
    composite 2-word key, ``los2d`` [num_segments, SEG] int64 carries the
    secondary word and ``ql`` [BQ] the query lo word — the router and lane
    compares become 3-word lexicographic (hi, lo, val), one extra row
    gather, same tile shapes as the intersect kernel.
    """
    num_segments = keys2d.shape[0]
    composite = los2d is not None
    rk = keys2d[:, 0]
    rl = los2d[:, 0] if composite else None
    rv = vals2d[:, 0]

    # ---- level 1: last segment whose leader <= query ----------------------
    lo = jnp.zeros(qk.shape, jnp.int32)
    hi = jnp.full(qk.shape, num_segments, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        mc = jnp.clip(mid, 0, num_segments - 1)
        mk = rk[mc]
        mv = rv[mc]
        if composite:
            ml = rl[mc]
            le = (mk < qk) | ((mk == qk)
                             & ((ml < ql) | ((ml == ql) & (mv <= qv))))
        else:
            le = (mk < qk) | ((mk == qk) & (mv <= qv))
        sel = lo < hi
        lo = jnp.where(le & sel, mid + 1, lo)
        hi = jnp.where(~le & sel, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, _router_depth(num_segments), body, (lo, hi))
    seg = jnp.maximum(lo - 1, 0)

    # ---- level 2: in-segment counts from one [BQ, SEG] gather --------------
    kseg = keys2d[seg]
    vseg = vals2d[seg]
    col = jax.lax.broadcasted_iota(jnp.int32, kseg.shape, 1)
    idx = seg[:, None] * SEG + col
    live = idx < n
    keq = kseg == qk[:, None]
    if composite:
        lseg = los2d[seg]
        leq = keq & (lseg == ql[:, None])
        ltv = live & ((kseg < qk[:, None])
                      | (keq & (lseg < ql[:, None]))
                      | (leq & (vseg < qv[:, None])))
        eqv = live & leq & (vseg == qv[:, None])
    else:
        ltv = live & ((kseg < qk[:, None]) | (keq & (vseg < qv[:, None])))
        eqv = live & keq & (vseg == qv[:, None])
    # entries in earlier segments are live (padding is a suffix) and < query
    base = seg * SEG
    lt = base + ltv.sum(axis=1).astype(jnp.int32)
    return lt, lt + eqv.sum(axis=1).astype(jnp.int32)


def rank_kernel(keys_ref, vals_ref, n_ref, qk_ref, qv_ref, lt_ref, le_ref):
    """One grid step: BQ rank queries against the full segment-major index."""
    lt, le = _rank_counts(keys_ref[...], vals_ref[...], n_ref[0],
                          qk_ref[...], qv_ref[...])
    lt_ref[...] = lt
    le_ref[...] = le


def rank_kernel_lex(keys_ref, los_ref, vals_ref, n_ref, qk_ref, ql_ref,
                    qv_ref, lt_ref, le_ref):
    """Composite-key variant: BQ (qk, ql, qv) rank queries, 3-word lex."""
    lt, le = _rank_counts(keys_ref[...], vals_ref[...], n_ref[0],
                          qk_ref[...], qv_ref[...],
                          los2d=los_ref[...], ql=ql_ref[...])
    lt_ref[...] = lt
    le_ref[...] = le


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rank_call(keys2d, vals2d, n, qk, qv, interpret: bool = True,
               los2d=None, ql=None):
    B = qk.shape[0]
    num_segments = keys2d.shape[0]
    grid = (B // BQ,)
    composite = los2d is not None
    full = pl.BlockSpec((num_segments, SEG), lambda i: (0, 0))
    qspec = pl.BlockSpec((BQ,), lambda i: (i,))
    in_specs = [full] + ([full] if composite else []) + [
        full,
        pl.BlockSpec((1,), lambda i: (0,)),
        qspec,
    ] + ([qspec] if composite else []) + [qspec]
    operands = ((keys2d, los2d, vals2d, n, qk, ql, qv) if composite
                else (keys2d, vals2d, n, qk, qv))
    return pl.pallas_call(
        rank_kernel_lex if composite else rank_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((BQ,), lambda i: (i,)),
                   pl.BlockSpec((BQ,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)),
        interpret=interpret,
    )(*operands)


def rank_counts(keys: jax.Array, vals: jax.Array, n: jax.Array,
                qk: jax.Array, qv: jax.Array, interpret: bool = True,
                lo=None, qlo=None):
    """(lt, le) [B] via the Pallas kernel, padding handled here.

    keys/vals: [cap] sorted lex (sentinel-padded, the IndexData layout);
    qk/qv: [B] queries; lo/qlo: the int64 secondary words for composite
    2-word keys.  Pads the index to a SEG multiple (segment-major reshape)
    and the query batch to a BQ multiple, then slices back.  Mixed-width
    hi words (narrow int32 index vs int64 queries, or vice versa) are
    promoted, never truncated — rank queries include sentinel-padded
    entries whose counts matter, unlike membership probes.
    """
    from repro.kernels.intersect.ops import (_pad_queries, _segment_major,
                                             _segment_major_lo)
    B = qk.shape[0]
    key_dtype = jnp.result_type(keys.dtype, qk.dtype)
    if key_dtype != keys.dtype:
        # promote a narrow index: re-sentinel the padding so the widened
        # suffix still sorts above every representable query
        live = jnp.arange(keys.shape[0], dtype=jnp.int32) < n
        keys = jnp.where(live, keys.astype(key_dtype),
                         jnp.asarray(np.iinfo(np.dtype(key_dtype.name)).max,
                                     key_dtype))
    keys2d, vals2d = _segment_major(keys.astype(key_dtype),
                                    vals.astype(jnp.int32))
    if lo is None:
        qkp, qvp = _pad_queries(qk, qv, key_dtype)
        los2d = qlp = None
    else:
        qkp, qvp, qlp = _pad_queries(qk, qv, key_dtype, ql=qlo)
        los2d = _segment_major_lo(lo)
    lt, le = _rank_call(keys2d, vals2d,
                        n.astype(jnp.int32).reshape(1), qkp, qvp,
                        interpret=bool(interpret), los2d=los2d, ql=qlp)
    return lt[:B], le[:B]
