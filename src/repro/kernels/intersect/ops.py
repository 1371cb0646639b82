"""Jit'd wrappers for the intersect kernel: router search, word split,
tiling, region fusion.

``signed_member(pos, neg, qk, qv)`` fuses *all* regions of a versioned index
into one ``pallas_call`` returning (wpos, wneg) hit counts — one launch per
membership probe regardless of how many LSM regions back the index.
``member(keys, vals, n, qk, qv)`` is the one-region case, a drop-in
replacement for ``ref.member_ref``.

Before the launch, XLA runs the level-1 router search (the segment of each
query in each region) and splits every key column into order-preserving
int32 words (:func:`split_words`); the kernel then only copies segment rows
and compares words.  The index itself is never copied: an all-int32 region
is handed over as its segment-major view (a free reshape of the SEG-aligned
capacity), and a region with a 64-bit column (int64 keys, the composite
``lo`` word) as just the queries' segment rows, gathered and split into
words in XLA — O(queries·SEG) per probe, whatever the region's size.

The kernel runs compiled on a TPU backend and in interpret mode elsewhere;
:func:`default_interpret` decides when the kernel is called, from the
backend that runs it, and callers may force either mode with ``interpret``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import lex_searchsorted_cols
from repro.kernels.intersect.intersect import (BQ, SEG, probe_call,
                                               seg_block_len)

# VMEM the fused extend kernel may plan on per core (16 MiB total minus
# pipeline headroom; DESIGN.md §3).  The membership kernel keeps the index in
# HBM and needs a fixed [W, BQ, SEG] tile, so it has no size gate.
FUSED_VMEM_BUDGET = 12 * 2**20


def default_interpret(interpret=None) -> bool:
    """Compiled Mosaic on a TPU backend, interpret mode elsewhere.

    ``interpret=None`` asks the default backend now, at call time; an
    explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def fused_fits(regions, batch: int = 0) -> bool:
    """Static check that the fused extend kernel over ``regions`` (.key/.val
    arrays, VMEM-resident) plus ~48 B/proposal of pipeline vectors fits the
    budget.  Composite regions carry the extra int64 ``lo`` word tile
    (8 B/slot) on top of the hi word and the int32 val."""
    idx_bytes = sum(
        r.key.shape[-1] * (jnp.dtype(r.key.dtype).itemsize + 4
                           + (8 if getattr(r, "lo", None) is not None
                              else 0))
        for r in regions)
    return idx_bytes + 48 * batch <= FUSED_VMEM_BUDGET


def _pad_to(x: jax.Array, size: int, fill) -> jax.Array:
    pad = size - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])


def _key_max(dtype) -> int:
    return np.iinfo(np.dtype(dtype.name)).max


def _segment_major(keys: jax.Array, vals: jax.Array):
    """Pad a sorted (key, val) index to a SEG multiple and reshape to
    [num_segments, SEG] segment-major tiles (column 0 = router)."""
    kmax = jnp.asarray(_key_max(keys.dtype), keys.dtype)
    vmax = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    padded = max(((keys.shape[0] + SEG - 1) // SEG) * SEG, SEG)
    keys2d = _pad_to(keys, padded, kmax).reshape(-1, SEG)
    vals2d = _pad_to(vals.astype(jnp.int32), padded, vmax).reshape(-1, SEG)
    return keys2d, vals2d


def _segment_major_lo(los: jax.Array) -> jax.Array:
    """The composite lo word as segment-major [num_segments, SEG] int64
    tiles, sentinel (int64-max) padded — the companion of the hi-word tiles
    from :func:`_segment_major` (same row split, column 0 joins the
    router)."""
    lmax = jnp.asarray(np.iinfo(np.int64).max, jnp.int64)
    padded = max(((los.shape[0] + SEG - 1) // SEG) * SEG, SEG)
    return _pad_to(los.astype(jnp.int64), padded, lmax).reshape(-1, SEG)


def _pad_queries(qk: jax.Array, qv: jax.Array, key_dtype, ql=None):
    kmax = jnp.asarray(_key_max(jnp.dtype(key_dtype)), key_dtype)
    vmax = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
    B = qk.shape[0]
    Bp = max(((B + BQ - 1) // BQ) * BQ, BQ)
    qk_p = _pad_to(qk.astype(key_dtype), Bp, kmax)
    qv_p = _pad_to(qv.astype(jnp.int32), Bp, vmax)
    if ql is None:
        return qk_p, qv_p
    lmax = jnp.asarray(np.iinfo(np.int64).max, jnp.int64)
    return qk_p, qv_p, _pad_to(ql.astype(jnp.int64), Bp, lmax)


def split_words(x: jax.Array) -> tuple:
    """An integer column as order-preserving int32 words.

    Up to 32 bits: the column itself.  64 bits: the signed high word, then
    the low word with its sign bit flipped, so that comparing the word pair
    lexicographically as signed int32 is comparing the int64 values."""
    if jnp.dtype(x.dtype).itemsize <= 4:
        return (x.astype(jnp.int32),)
    x = x.astype(jnp.int64)
    return ((x >> 32).astype(jnp.int32),
            x.astype(jnp.int32) ^ jnp.int32(np.iinfo(np.int32).min))


def _region_operands(key, lo, val, n, qcols):
    """One region's kernel operands: its int32 word tiles, and the row of
    each query in them and its live-lane count (the level-1 router search).

    Int32 columns are the region's own segment-major view, rows addressed
    by segment id.  A 64-bit column must be split into words, so the
    region is narrowed to the queries' segment rows first ([B, SEG], row i
    for query i): splitting the whole region would cost O(capacity) per
    probe."""
    cols2d = _segment_major(key, val)
    if lo is not None:
        cols2d = (cols2d[0], _segment_major_lo(lo), cols2d[1])
    S = cols2d[0].shape[0]
    router = tuple(c[:, 0] for c in cols2d)
    le = lex_searchsorted_cols(router, jnp.int32(S), qcols, "right")
    seg = jnp.maximum(le - 1, 0).astype(jnp.int32)
    nlive = jnp.clip(n.astype(jnp.int32) - seg * SEG, 0, SEG)
    if any(jnp.dtype(c.dtype).itemsize > 4 for c in cols2d):
        cols2d = tuple(c[seg] for c in cols2d)
        seg = jnp.arange(seg.shape[0], dtype=jnp.int32)
    words = tuple(w for c in cols2d for w in split_words(c))
    return words, seg, nlive


@functools.partial(jax.jit, static_argnames=("num_pos", "interpret"))
def _signed_member_jit(regions, qk, qv, num_pos: int, interpret: bool,
                       ql=None):
    key_dtype = jnp.result_type(*[reg[0].dtype for reg in regions])
    B = qk.shape[0]
    if ql is None:
        qcols = _pad_queries(qk, qv, key_dtype)
    else:
        qk_p, qv_p, ql_p = _pad_queries(qk, qv, key_dtype, ql=ql)
        qcols = (qk_p, ql_p, qv_p)
    Bp = qcols[0].shape[0]
    words, segs, nlives = [], [], []
    for reg in regions:
        key, lo, val, n = reg if ql is not None else (reg[0], None) + reg[1:]
        w, s, nl = _region_operands(key.astype(key_dtype), lo, val, n,
                                    qcols)
        words.append(w)
        segs.append(s)
        nlives.append(nl)
    R = len(regions)
    # segment ids grouped per query block, then region: the SMEM block of
    # grid step b is the contiguous slice b, zero-padded to the SMEM tiling
    seg = jnp.stack(segs).reshape(R, Bp // BQ, BQ).transpose(1, 0, 2)
    seg = jnp.pad(seg.reshape(Bp // BQ, R * BQ),
                  ((0, 0), (0, seg_block_len(R) - R * BQ)))
    qwords = jnp.stack([w for c in qcols for w in split_words(c)])
    wpos, wneg = probe_call(seg.reshape(-1), jnp.stack(nlives)[..., None],
                            qwords[..., None], tuple(words),
                            num_pos=num_pos, interpret=interpret)
    return wpos[:B, 0], wneg[:B, 0]


def signed_member(pos, neg, qk, qv: jax.Array, interpret=None):
    """Fused membership over all regions of a versioned index.

    ``pos``/``neg``: sequences of sorted-index regions (objects with
    .key/.val/.n and optionally the composite .lo word, e.g.
    :class:`repro.core.csr.IndexData`).  ``qk`` is one packed array, or a
    (hi, lo) pair when the regions are composite.  One ``pallas_call``
    total.  Returns (wpos, wneg) int32 [B]: hit counts over the positive /
    negative regions."""
    all_regions = tuple(pos) + tuple(neg)
    if isinstance(qk, tuple):
        qk, ql = qk
        regions = tuple((r.key, r.lo, r.val, r.n) for r in all_regions)
    else:
        ql = None
        regions = tuple((r.key, r.val, r.n) for r in all_regions)
    if not regions:
        z = jnp.zeros(qk.shape, jnp.int32)
        return z, z
    return _signed_member_jit(regions, qk, qv, num_pos=len(tuple(pos)),
                              interpret=default_interpret(interpret),
                              ql=ql)


def member(keys: jax.Array, vals: jax.Array, n: jax.Array,
           qk: jax.Array, qv: jax.Array, interpret=None,
           los=None, ql=None) -> jax.Array:
    """[B] bool membership of (qk[, ql], qv) in one sorted index.

    Pass the index's ``los`` word and the query ``ql`` word for composite
    (hi, lo) keys — the same single launch, with two more words."""
    if los is None:
        regions = ((keys, vals, n),)
    else:
        regions = ((keys, los, vals, n),)
    wpos, _ = _signed_member_jit(regions, qk, qv, num_pos=1,
                                 interpret=default_interpret(interpret),
                                 ql=ql)
    return wpos > 0
