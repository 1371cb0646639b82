"""Versioned extension indices for the BiGJoin dataflow.

A :class:`VersionedIndex` is the multi-region structure of §4.3 flattened to
arrays: *positive* regions contribute extensions (compacted base, committed
inserts, uncommitted inserts) and *negative* regions subtract membership
(committed / uncommitted deletes).  The three logical versions map to region
subsets:

    static:  pos=(base,)                 neg=()
    old:     pos=(base, cins)            neg=(cdel,)
    new:     pos=(base, cins, uins)      neg=(cdel, udel)

Counts and proposals come from positive regions only; deletions are applied
as a post-filter on proposals and as signed membership.  Update application
(`delta.py`) maintains the invariant that inserts are new edges and deletes
target live edges, so positive regions never contain duplicates and the
signed membership is exact 0/1.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.csr import IndexData, index_member, index_range


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VersionedIndex:
    pos: Tuple[IndexData, ...]
    neg: Tuple[IndexData, ...]

    def tree_flatten(self):
        return (self.pos, self.neg), (len(self.pos), len(self.neg))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children[0]), tuple(children[1]))

    @classmethod
    def static(cls, data: IndexData) -> "VersionedIndex":
        return cls((data,), ())

    @property
    def num_regions(self) -> int:
        return len(self.pos)

    def worker_shard(self, i: int = 0) -> "VersionedIndex":
        """Select worker ``i``'s slice of a sharded index whose regions carry
        a leading [w] worker axis (``csr.build_sharded_index``).  Inside
        ``shard_map`` the per-worker block has w=1, so ``worker_shard(0)``
        strips the axis; on the host it projects any worker's shard for
        inspection and parity tests."""
        def strip(d: IndexData) -> IndexData:
            return IndexData(d.key[i], d.val[i], d.n[i],
                             None if d.lo is None else d.lo[i])
        return VersionedIndex(tuple(strip(p) for p in self.pos),
                              tuple(strip(n) for n in self.neg))

    def live_entries(self) -> int:
        """Total live rows over every region (and every worker shard)."""
        import numpy as np
        return int(sum(np.asarray(d.n).sum()
                       for d in self.pos + self.neg))

    # ---- queries (vectorized over probe batch [B]) ------------------------

    def ranges(self, qkey: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(starts [B,R], counts [B,R]) over positive regions."""
        ss, cs = [], []
        for reg in self.pos:
            s, c = index_range(reg, qkey)
            ss.append(s)
            cs.append(c)
        return jnp.stack(ss, -1), jnp.stack(cs, -1)

    def count(self, qkey: jax.Array) -> jax.Array:
        """Positive-region extension count [B] (exact when no deletions)."""
        _, c = self.ranges(qkey)
        return c.sum(-1)

    def gather(self, starts: jax.Array, counts: jax.Array,
               k: jax.Array) -> jax.Array:
        """k-th extension across concatenated positive regions.

        starts/counts: [B, R] rows already gathered per probe; k: [B].
        """
        val = jnp.zeros(k.shape, jnp.int32)
        off = k
        for r, reg in enumerate(self.pos):
            in_r = (off >= 0) & (off < counts[..., r])
            pos = jnp.clip(starts[..., r] + off, 0, reg.capacity - 1)
            val = jnp.where(in_r, reg.val[pos], val)
            off = off - counts[..., r]
        return val

    @staticmethod
    def _kernel_ok(regions) -> bool:
        """Mixed 1-word/2-word regions never share a launch; any size does
        (the member kernel keeps the index in HBM)."""
        composite = [r.lo is not None for r in regions]
        return all(composite) or not any(composite)

    def signed_member(self, qkey: jax.Array, qval: jax.Array,
                      use_kernel: bool = False,
                      interpret=None) -> Tuple[jax.Array, jax.Array]:
        """(membership, deletion) bits in ONE pass over all regions.

        With ``use_kernel`` this is a single fused ``pallas_call`` across
        every positive and negative region (R launches collapse to 1) —
        composite regions included, with ``qkey`` the (hi, lo) int64 probe
        pair; the jnp path mirrors the same signed-weight reduction.
        """
        if use_kernel and self._kernel_ok(self.pos + self.neg):
            from repro.kernels.intersect.ops import signed_member
            wpos, wneg = signed_member(self.pos, self.neg, qkey, qval,
                                       interpret=interpret)
            return (wpos - wneg) > 0, wneg > 0
        shape = qkey[0].shape if isinstance(qkey, tuple) else qkey.shape
        w = jnp.zeros(shape, jnp.int32)
        d = jnp.zeros(shape, bool)
        for reg in self.pos:
            w = w + index_member(reg, qkey, qval).astype(jnp.int32)
        for reg in self.neg:
            hit = index_member(reg, qkey, qval)
            w = w - hit.astype(jnp.int32)
            d = d | hit
        return w > 0, d

    def member(self, qkey: jax.Array, qval: jax.Array,
               use_kernel: bool = False, interpret=None) -> jax.Array:
        return self.signed_member(qkey, qval, use_kernel, interpret)[0]

    def deleted(self, qkey, qval: jax.Array,
                use_kernel: bool = False, interpret=None) -> jax.Array:
        shape = qkey[0].shape if isinstance(qkey, tuple) else qkey.shape
        if not self.neg:
            return jnp.zeros(shape, bool)
        if use_kernel and self._kernel_ok(self.neg):
            from repro.kernels.intersect.ops import signed_member
            _, wneg = signed_member((), self.neg, qkey, qval,
                                    interpret=interpret)
            return wneg > 0
        d = jnp.zeros(shape, bool)
        for reg in self.neg:
            d = d | index_member(reg, qkey, qval)
        return d
