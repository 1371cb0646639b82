"""Graph500 Kronecker (R-MAT) edge lists, generated on the host from a seed.

A copy of the semantics of the program's ``rmat_graph`` (quadrant
probabilities a, b, c and d = 1 - a - b - c, ``edge_factor << scale``
generated edges, self-loops dropped, duplicates merged), written so the
benchmark imports nothing from the program.  Each of the ``scale`` bits
draws one uint32 per edge and compares it with fixed-point thresholds,
which is several times faster than float draws at scale 20.
"""
from __future__ import annotations

import numpy as np

# bit widths of the packed keys used throughout the benchmark
VID_BITS = 32


def pack(src, dst) -> np.ndarray:
    """(src, dst) vertex pairs as one int64 key, ordered like (src, dst)."""
    return (np.asarray(src, np.int64) << VID_BITS) | np.asarray(dst, np.int64)


def unpack(keys) -> np.ndarray:
    """int64 keys back to [N, 2] int32 rows."""
    keys = np.asarray(keys, np.int64)
    return np.stack([keys >> VID_BITS, keys & 0xFFFFFFFF], 1).astype(np.int32)


def rmat_keys(scale: int, edge_factor: int, rng: np.random.Generator,
              a: float, b: float, c: float) -> np.ndarray:
    """Sorted distinct packed edge keys of one R-MAT graph."""
    e = edge_factor << scale
    t_a = np.uint32(round(a * 2**32))
    t_ab = np.uint32(round((a + b) * 2**32))
    t_abc = np.uint32(min(round((a + b + c) * 2**32), 2**32 - 1))
    src = np.zeros(e, np.int64)
    dst = np.zeros(e, np.int64)
    for bit in range(scale):
        r = rng.integers(0, 2**32, e, dtype=np.uint32)
        down = r >= t_ab                       # quadrants c, d
        right = ((r >= t_a) & ~down) | (r >= t_abc)  # quadrants b, d
        src |= down.astype(np.int64) << bit
        dst |= right.astype(np.int64) << bit
    keep = src != dst
    return np.unique(pack(src[keep], dst[keep]))


def rmat_graph(cfg: dict, seed: int) -> np.ndarray:
    """The configuration's initial graph as sorted distinct packed keys."""
    rng = np.random.default_rng([int(seed), 0])
    return rmat_keys(int(cfg["scale"]), int(cfg["edge_factor"]), rng,
                     float(cfg["a"]), float(cfg["b"]), float(cfg["c"]))
