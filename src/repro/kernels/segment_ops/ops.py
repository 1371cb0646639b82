"""Jit'd wrapper for the segment-sum kernel (sort, pad, combine partials)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.segment_ops.segment_ops import BE, _segment_sum_call
from repro.kernels.intersect.ops import default_interpret



@functools.partial(jax.jit, static_argnames=("num_segments", "is_sorted"))
def segment_sum(data: jax.Array, seg_ids: jax.Array, num_segments: int,
                is_sorted: bool = False) -> jax.Array:
    """[NS, D] f32 segment sum via the Pallas one-hot-matmul kernel.

    ``is_sorted``: promise that seg_ids is nondecreasing (e.g. edges stored
    dst-sorted); otherwise a sort is inserted here.
    """
    E, D = data.shape
    if not is_sorted:
        order = jnp.argsort(seg_ids)
        data, seg_ids = data[order], seg_ids[order]
    Ep = ((E + BE - 1) // BE) * BE
    if Ep != E:
        data = jnp.concatenate(
            [data, jnp.zeros((Ep - E, D), data.dtype)])
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((Ep - E,), num_segments, seg_ids.dtype)])
    partials, segmap = _segment_sum_call(
        data, seg_ids.astype(jnp.int32), num_segments,
        interpret=default_interpret())
    out = jnp.zeros((num_segments, D), jnp.float32)
    return out.at[segmap].add(partials, mode="drop")
