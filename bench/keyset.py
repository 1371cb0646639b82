"""A large set of int64 keys that changes by small batches.

``KeySet`` keeps a sorted base array and two small sorted overlays: keys
added since the base was built (disjoint from it) and base keys removed
since.  Membership, range counts and range listings are vectorised
``searchsorted`` calls; a batch costs O(overlay) instead of the O(|base|)
copy that inserting into one sorted array would, and the overlays fold into
the base once they pass an eighth of it.
"""
from __future__ import annotations

import numpy as np

_EMPTY = np.zeros(0, np.int64)


def sorted_member(arr: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Boolean mask: which of ``q`` are in the sorted array ``arr``."""
    if arr.size == 0:
        return np.zeros(q.shape, bool)
    if q.size > 4096 and arr.size > 1 << 16:
        # sorted needles walk the haystack in order: an order of magnitude
        # fewer cache misses than random ones on a large array
        order = np.argsort(q, kind="stable")
        out = np.empty(q.shape, bool)
        qs = q[order]
        pos = np.searchsorted(arr, qs)
        out[order] = arr[np.minimum(pos, arr.size - 1)] == qs
        return out
    pos = np.searchsorted(arr, q)
    return arr[np.minimum(pos, arr.size - 1)] == q


def _put(arr: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Sorted ``arr`` with the sorted keys (absent from it) inserted."""
    return np.insert(arr, np.searchsorted(arr, keys), keys)


def _drop(arr: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Sorted ``arr`` without the keys (present in it)."""
    return np.delete(arr, np.searchsorted(arr, keys))


def expand_ranges(lo: np.ndarray, hi: np.ndarray):
    """For ranges [lo_i, hi_i): (owner i, position) of every element."""
    n = np.maximum(hi - lo, 0)
    total = int(n.sum())
    owner = np.repeat(np.arange(lo.size), n)
    start = np.cumsum(n) - n
    pos = lo[owner] + (np.arange(total) - start[owner])
    return owner, pos


class KeySet:
    def __init__(self, sorted_keys: np.ndarray):
        self.base = np.asarray(sorted_keys, np.int64)
        self.add = _EMPTY
        self.rem = _EMPTY

    def __len__(self) -> int:
        return self.base.size + self.add.size - self.rem.size

    def contains(self, q: np.ndarray) -> np.ndarray:
        q = np.asarray(q, np.int64)
        return (sorted_member(self.base, q) & ~sorted_member(self.rem, q)) \
            | sorted_member(self.add, q)

    def insert(self, keys: np.ndarray) -> None:
        """Insert distinct keys that are not in the set."""
        keys = np.sort(np.asarray(keys, np.int64))
        back = sorted_member(self.rem, keys)
        self.rem = _drop(self.rem, keys[back])
        self.add = _put(self.add, keys[~back])
        self._maybe_fold()

    def delete(self, keys: np.ndarray) -> None:
        """Delete distinct keys that are in the set."""
        keys = np.sort(np.asarray(keys, np.int64))
        fresh = sorted_member(self.add, keys)
        self.add = _drop(self.add, keys[fresh])
        self.rem = _put(self.rem, keys[~fresh])
        self._maybe_fold()

    def _maybe_fold(self) -> None:
        if (self.add.size + self.rem.size) * 8 > max(self.base.size, 1024):
            self.base = self.sorted()
            self.add = self.rem = _EMPTY

    def sorted(self) -> np.ndarray:
        """Every key, sorted."""
        kept = self.base[~sorted_member(self.rem, self.base)]
        return np.sort(np.concatenate([kept, self.add]), kind="stable")

    def count_range(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        """Number of keys in [lo_i, hi_i) for each i."""
        def cnt(a):
            return np.searchsorted(a, hi) - np.searchsorted(a, lo)
        return cnt(self.base) - cnt(self.rem) + cnt(self.add)

    def list_range(self, lo: np.ndarray, hi: np.ndarray):
        """(owner i, key) for every key in [lo_i, hi_i)."""
        parts_o, parts_k = [], []
        for arr, drop_removed in ((self.base, True), (self.add, False)):
            o, p = expand_ranges(np.searchsorted(arr, lo),
                                 np.searchsorted(arr, hi))
            k = arr[p]
            if drop_removed and self.rem.size:
                keep = ~sorted_member(self.rem, k)
                o, k = o[keep], k[keep]
            parts_o.append(o)
            parts_k.append(k)
        return np.concatenate(parts_o), np.concatenate(parts_k)
