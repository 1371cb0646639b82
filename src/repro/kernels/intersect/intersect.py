"""Pallas TPU kernel: batched sorted-membership (the Intersect hot spot).

The innermost operation of the WCOJ dataflow is "does extension e of prefix p
exist in relation R_i?" — a lookup of (key, val) in a lexicographically
sorted pair of arrays.  The paper uses CPU hash tables; the TPU-native
structure is a two-level sorted search (DESIGN.md §2):

  level 1 (router): every SEG-th entry of the index.  A fixed-depth
      vectorized binary search over the router locates each query's
      SEG-aligned segment.  It runs in XLA ahead of the kernel
      (``ops._region_operands``) and hands the kernel one segment id per
      query and region.
  level 2 (this kernel): the index stays in HBM, viewed segment-major as
      [num_segments, SEG] tiles.  The kernel DMAs each query's segment row
      into a [BQ, SEG] VMEM tile and reduces it with one lane-wise compare
      per word — the whole query block resolves with BQ row copies and
      O(words) vector ops, whatever the index size.

Mosaic has no 64-bit vectors and no in-kernel vector gather, so the kernel
sees only int32: every key column arrives as order-preserving int32 words
(``ops.split_words``: an int64 becomes its signed high word and its low word
with the sign bit flipped), and the only gathers are the row DMAs, addressed
by segment ids read from SMEM.  All index arithmetic is explicitly int32 —
the package runs with x64 on, where a bare Python int would trace as int64.

The multi-region kernel evaluates *all* positive and negative regions of a
:class:`~repro.core.dataflow_index.VersionedIndex` in one ``pallas_call``
and returns the signed hit counts (wpos, wneg) — one launch per membership
probe regardless of how many LSM regions back the index.

ref.py and ``csr.index_member`` are the pure-jnp oracle; parity is
bit-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# segment length (one VPU lane row per segment fetch) — canonical constant
# lives with the index structure so capacity rounding cannot drift from it
from repro.core.csr import SEG  # noqa: F401  (re-exported for ops.py)

BQ = 256  # queries per grid step
SMEM_TILE = 1024  # 1-D SMEM blocks are whole multiples of this

_I0 = np.int32(0)


def seg_block_len(num_regions: int) -> int:
    """Length of one grid step's SMEM block of segment ids: R*BQ, rounded
    up to the SMEM tiling."""
    return -(-num_regions * BQ // SMEM_TILE) * SMEM_TILE


def _make_probe_kernel(num_pos: int, num_neg: int, num_words: int):
    """Kernel over ``num_pos`` positive + ``num_neg`` negative regions whose
    entries are ``num_words`` int32 words each (key words, then val).

    Refs: seg (SMEM [seg_block_len(R)]: segment id of each query in each
    region, region-major), nlive (VMEM [R, BQ, 1]: live entries in that
    segment), the query words (VMEM [W, BQ, 1]), then R*W index word arrays
    (HBM [S_r, SEG]);
    outputs wpos/wneg (VMEM [BQ, 1] int32 hit counts); scratch: one
    [W, BQ, SEG] row tile and W DMA semaphores.
    """
    R, W = num_pos + num_neg, num_words

    def kernel(seg_ref, nlive_ref, q_ref, *refs):
        idx_refs = refs[:R * W]
        wpos_ref, wneg_ref, rows, sems = refs[R * W:]
        col = jax.lax.broadcasted_iota(jnp.int32, (BQ, SEG), 1)
        wpos = jnp.zeros((BQ, 1), jnp.int32)
        wneg = jnp.zeros((BQ, 1), jnp.int32)
        for r in range(R):
            words = idx_refs[r * W:(r + 1) * W]

            def copies(i, r=r, words=words):
                s = seg_ref[np.int32(r * BQ) + i]
                return [pltpu.make_async_copy(
                    words[w].at[pl.ds(s, 1)],
                    rows.at[np.int32(w), pl.ds(i, 1)],
                    sems.at[np.int32(w)]) for w in range(W)]

            def start(i, c, copies=copies):
                for cp in copies(i):
                    cp.start()
                return c

            def wait(i, c, copies=copies):
                for cp in copies(i):
                    cp.wait()
                return c

            # jnp.int32 bounds: with x64 on, Python/NumPy scalar bounds give
            # the loop an index type Mosaic cannot lower
            zero, bq = jnp.int32(0), jnp.int32(BQ)
            jax.lax.fori_loop(zero, bq, start, zero)
            jax.lax.fori_loop(zero, bq, wait, zero)
            hit = col < nlive_ref[np.int32(r)]
            for w in range(W):
                hit = hit & (rows[np.int32(w)] == q_ref[np.int32(w)])
            hits = jnp.max(hit.astype(jnp.int32), axis=1, keepdims=True)
            if r < num_pos:
                wpos = wpos + hits
            else:
                wneg = wneg + hits
        wpos_ref[...] = wpos
        wneg_ref[...] = wneg

    return kernel


@functools.partial(jax.jit, static_argnames=("num_pos", "interpret"))
def probe_call(seg, nlive, qwords, regions, num_pos: int,
               interpret: bool = True):
    """The multi-region probe launch on prepared operands.

    seg: [Bp//BQ * seg_block_len(R)] int32 segment ids, one zero-padded
    block per query block, region-major inside it; nlive: [R, Bp, 1] int32; qwords: [W, Bp, 1] int32; regions: R
    tuples of W int32 [S_r, SEG] word arrays (positives first).  Bp is a BQ
    multiple.  Returns (wpos, wneg) int32 [Bp, 1]."""
    R = len(regions)
    W, Bp = qwords.shape[0], qwords.shape[1]
    col = pl.BlockSpec((BQ, 1), lambda b: (b, _I0))
    in_specs = [
        pl.BlockSpec((seg_block_len(R),), lambda b: (b,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((R, BQ, 1), lambda b: (_I0, b, _I0)),
        pl.BlockSpec((W, BQ, 1), lambda b: (_I0, b, _I0)),
    ] + [pl.BlockSpec(memory_space=pl.ANY)] * (R * W)
    operands = [seg, nlive, qwords] + [w for reg in regions for w in reg]
    return pl.pallas_call(
        _make_probe_kernel(num_pos, R - num_pos, W),
        grid=(Bp // BQ,),
        in_specs=in_specs,
        out_specs=(col, col),
        out_shape=(jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((W, BQ, SEG), jnp.int32),
                        pltpu.SemaphoreType.DMA((W,))],
        interpret=interpret,
    )(*operands)
