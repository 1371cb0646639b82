"""The plain reference of the triangle monitor, in numpy.

Semantics (the program's ``triangle`` query on directed edges):
``tri(a, b, c) :- e(a, b), e(b, c), e(a, c)``.  One epoch takes a dirty
signed batch of edge rows and

1. normalizes it: self-loops and zero weights drop, the weights of each
   distinct edge add up, a positive sum inserts the edge if it is absent
   and a negative sum deletes it if it is live (per-batch set semantics);
2. reports the signed triangle delta: the live triangles after the epoch
   that hold an inserted edge, minus the live triangles before it that
   hold a deleted edge;
3. applies the inserts and deletes to the live edge set.

The live set is kept twice, by (src, dst) and by (dst, src), so that the
out- and in-neighbours of any vertex are one range of sorted keys.  The
triangles through an edge (x, y) are found for each of its three roles by
listing the neighbours of the endpoint with the SMALLER list and testing the
closing edge by binary search: the cost per edge is the smaller degree,
never a hub's.  Triangles found through two or three of the epoch's edges
are counted once.  Imports nothing from the program.
"""
from __future__ import annotations

import numpy as np

from bench.graph import VID_BITS, pack
from bench.keyset import KeySet

LOW = (1 << VID_BITS) - 1
TRI_BITS = 21  # vertex ids of one triangle packed into one int64


def _src(keys):
    return keys >> VID_BITS


def _dst(keys):
    return keys & LOW


def _swap(keys):
    return (_dst(keys) << VID_BITS) | _src(keys)


class Reference:
    def __init__(self, sorted_keys: np.ndarray, num_vertices: int):
        if num_vertices > 1 << TRI_BITS:
            raise ValueError(f"{num_vertices} vertices do not fit the "
                             f"{TRI_BITS}-bit triangle packing")
        self.out = KeySet(sorted_keys)
        self.inn = KeySet(np.sort(_swap(np.asarray(sorted_keys, np.int64))))

    # -- normalize ----------------------------------------------------------
    def normalize(self, rows: np.ndarray, w: np.ndarray):
        """(inserted keys, deleted keys), each sorted."""
        rows = np.asarray(rows, np.int64).reshape(-1, 2)
        w = np.asarray(w, np.int64)
        keep = (rows[:, 0] != rows[:, 1]) & (w != 0)
        keys, inv = np.unique(pack(rows[keep, 0], rows[keep, 1]),
                              return_inverse=True)
        net = np.zeros(keys.size, np.int64)
        np.add.at(net, inv, w[keep])
        live = self.out.contains(keys)
        return keys[(net > 0) & ~live], keys[(net < 0) & live]

    def apply(self, ins: np.ndarray, dels: np.ndarray) -> None:
        self.out.delete(dels)
        self.inn.delete(_swap(dels))
        self.out.insert(ins)
        self.inn.insert(_swap(ins))

    def epoch(self, rows: np.ndarray, w: np.ndarray):
        """One whole epoch: (ins, dels, signed triangle delta)."""
        ins, dels = self.normalize(rows, w)
        before = self.triangles_through(dels)
        self.apply(ins, dels)
        after = self.triangles_through(ins)
        return ins, dels, after - before

    def live_keys(self) -> np.ndarray:
        return self.out.sorted()

    # -- triangles ------------------------------------------------------------
    def _nbrs(self, ks: KeySet, v: np.ndarray):
        lo = v << VID_BITS
        return ks.list_range(lo, lo + (1 << VID_BITS))

    def _deg(self, ks: KeySet, v: np.ndarray) -> np.ndarray:
        lo = v << VID_BITS
        return ks.count_range(lo, lo + (1 << VID_BITS))

    def _closing(self, x, y, sx: KeySet, sy: KeySet, x_is_src: bool,
                 y_is_src: bool):
        """Vertices z with edge (x,z) or (z,x) per ``x_is_src`` and (y,z)
        or (z,y) per ``y_is_src``: lists the smaller neighbour list of the
        two and tests membership in the other.  ``sx``/``sy`` are the
        keysets whose ranges hold those neighbours.  Returns (edge index,
        z) pairs."""
        use_x = self._deg(sx, x) <= self._deg(sy, y)
        own, zs = [], []
        for pick, a, sa, b, b_src in ((use_x, x, sx, y, y_is_src),
                                      (~use_x, y, sy, x, x_is_src)):
            idx = np.flatnonzero(pick)
            # probes grouped by the tested endpoint come out nearly sorted,
            # which keeps the binary searches in cache
            idx = idx[np.argsort(b[idx], kind="stable")]
            o, k = self._nbrs(sa, a[idx])
            z = _dst(k)
            bb = b[idx][o]
            probe = pack(bb, z) if b_src else pack(z, bb)
            hit = self.out.contains(probe)
            own.append(idx[o[hit]])
            zs.append(z[hit])
        return np.concatenate(own), np.concatenate(zs)

    def triangles_through(self, keys: np.ndarray) -> int:
        """Distinct live triangles holding at least one of ``keys``."""
        keys = np.asarray(keys, np.int64)
        if keys.size == 0:
            return 0
        x, y = _src(keys), _dst(keys)
        found = []
        # (x, y) as e(a, b): c with e(a, c), e(b, c)  -> (x, y, c)
        i, z = self._closing(x, y, self.out, self.out, True, True)
        found.append((x[i], y[i], z))
        # (x, y) as e(b, c): a with e(a, b), e(a, c)  -> (a, x, y)
        i, z = self._closing(x, y, self.inn, self.inn, False, False)
        found.append((z, x[i], y[i]))
        # (x, y) as e(a, c): b with e(a, b), e(b, c)  -> (x, b, y)
        i, z = self._closing(x, y, self.out, self.inn, True, False)
        found.append((x[i], z, y[i]))
        tri = np.concatenate([(a << (2 * TRI_BITS)) | (b << TRI_BITS) | c
                              for a, b, c in found])
        return int(np.unique(tri).size)
