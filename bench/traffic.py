"""The one traffic generator: reads a mix file and yields update batches.

A mix (``bench/traffic/<name>.json``) is data only.  Its ``generator``
names the kind of stream; ``sliding_window`` is a graph whose edges expire
as new ones arrive, so the live edge count stays flat.  Each batch holds,
in shuffled order:

- ``insert_share`` inserts of new edges: never live in this run before,
  so an insert never revives an edge the stream deleted.  Each has as its
  source an endpoint of a uniformly drawn live edge (preferential
  attachment) and a destination drawn uniformly over the vertices;
- ``delete_share`` deletes of distinct live edges, drawn uniformly;
- ``reinsert_share`` re-inserts of other live edges and the rest deletes of
  absent edges: dirty rows that normalization has to net away.

The generator keeps its own record of the live set (the initial edges,
plus its inserts, minus its deletes) and never reads the program's output,
so the stream is a function of the seed alone.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench.graph import VID_BITS, pack, unpack
from bench.keyset import KeySet

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


class SlidingWindow:
    def __init__(self, mix: dict, keys: np.ndarray, num_vertices: int,
                 seed: int):
        self.batch = int(mix["batch"])
        b = self.batch
        self.n_ins = round(b * float(mix["insert_share"]))
        self.n_del = round(b * float(mix["delete_share"]))
        self.n_re = round(b * float(mix["reinsert_share"]))
        self.n_abs = b - self.n_ins - self.n_del - self.n_re
        if min(self.n_ins, self.n_del, self.n_re, self.n_abs) < 0:
            raise ValueError(f"mix shares do not fit a batch of {b}")
        self.nv = int(num_vertices)
        self.rng = np.random.default_rng([int(seed), 1])
        keys = np.asarray(keys, np.int64)
        # live keys in no particular order, for uniform draws; grows by at
        # most one batch of inserts before the deletes are taken out
        self.pool = np.empty(keys.size + b, np.int64)
        self.pool[:keys.size] = keys
        self.n = keys.size
        self.live = KeySet(keys)
        self.gone = KeySet(np.zeros(0, np.int64))  # deleted in this run
        if self.n < self.n_del + self.n_re:
            raise ValueError("live set smaller than one batch's deletes")

    def _fresh(self, count: int, degree_source: bool, taken: np.ndarray,
               new: bool) -> np.ndarray:
        """``count`` distinct absent keys, not in ``taken``, no self-loops,
        in the order drawn; with ``new``, none the run has deleted."""
        out = np.zeros(0, np.int64)
        while out.size < count:
            m = 2 * (count - out.size) + 16
            if degree_source:
                e = self.pool[self.rng.integers(0, self.n, m)]
                side = self.rng.integers(0, 2, m).astype(bool)
                src = np.where(side, e & ((1 << VID_BITS) - 1),
                               e >> VID_BITS)
            else:
                src = self.rng.integers(0, self.nv, m)
            dst = self.rng.integers(0, self.nv, m)
            k = pack(src, dst)
            ok = (src != dst) & ~self.live.contains(k)
            if new:
                ok &= ~self.gone.contains(k)
            k = np.concatenate([out, k[ok]])
            _, first = np.unique(k, return_index=True)
            first = np.sort(first)
            k = k[first]
            k = k[~np.isin(k, taken)]
            out = k[:count]
        return out

    def next(self):
        """One batch: (rows [B, 2] int32, weights [B] int32)."""
        rng = self.rng
        picked = rng.choice(self.n, self.n_del + self.n_re, replace=False)
        del_pos = picked[:self.n_del]
        dels = self.pool[del_pos]
        reins = self.pool[picked[self.n_del:]]
        ins = self._fresh(self.n_ins, True, np.zeros(0, np.int64),
                          new=True)
        absent = self._fresh(self.n_abs, False, ins, new=False)
        keys = np.concatenate([ins, dels, reins, absent])
        w = np.concatenate([np.ones(ins.size, np.int32),
                            -np.ones(dels.size, np.int32),
                            np.ones(reins.size, np.int32),
                            -np.ones(absent.size, np.int32)])
        order = rng.permutation(keys.size)
        self._remove_positions(del_pos)
        self.pool[self.n:self.n + ins.size] = ins
        self.n += ins.size
        self.live.delete(dels)
        self.live.insert(ins)
        self.gone.insert(dels)
        return unpack(keys[order]), w[order]

    def _remove_positions(self, pos: np.ndarray) -> None:
        """Drop the pool entries at distinct positions ``pos``: entries
        from the tail fill the holes."""
        k = pos.size
        last = self.n - k
        holes = np.sort(pos[pos < last])
        tail = np.ones(k, bool)
        tail[pos[pos >= last] - last] = False
        fillers = last + np.flatnonzero(tail)
        self.pool[holes] = self.pool[fillers]
        self.n = last


GENERATORS = {"sliding_window": SlidingWindow}


def make_stream(mix: dict, keys: np.ndarray, num_vertices: int, seed: int):
    return GENERATORS[mix["generator"]](mix, keys, num_vertices, seed)
