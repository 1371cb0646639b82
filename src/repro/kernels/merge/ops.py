"""Routing for the merge rank kernel.

The rank kernel is off the default path (``repro.kernels.OFF_DEFAULT_PATH``:
it does not compile for TPU), so production rank queries run the jnp
searches (``csr.index_ranks``).  Callers that ask for the kernel get it in
the mode ``default_interpret`` picks — interpret mode off-TPU, where it is
bit-exact against the jnp oracle (tests/test_merge_kernel.py).
"""
from __future__ import annotations

import jax

from repro.kernels.intersect.ops import default_interpret
from repro.kernels.merge.merge import rank_counts


def rank_lt_le(keys: jax.Array, vals: jax.Array, n: jax.Array,
               qk: jax.Array, qv: jax.Array, lo=None, qlo=None,
               interpret=None):
    """(lt, le) merge ranks of each (qk[, qlo], qv) in the sorted index.

    ``lo``/``qlo``: the int64 secondary words when the index carries
    composite 2-word keys.  ``interpret=None`` defers to
    :func:`default_interpret`; an explicit bool forces that kernel mode.
    """
    return rank_counts(keys, vals, n, qk, qv,
                       interpret=default_interpret(interpret), lo=lo,
                       qlo=qlo)
