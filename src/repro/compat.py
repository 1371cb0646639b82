"""One spelling for the JAX APIs the repo uses in more than one place.

Written against the installed JAX (0.9): ``jax.shard_map`` with its
``check_vma`` flag, and the jaxpr classes under ``jax.extend.core`` (the
``jax.core`` aliases are gone).
"""
from __future__ import annotations

from typing import Iterator

import jax
from jax.extend import core as jex_core

tree_flatten_with_path = jax.tree.flatten_with_path


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with keyword-only mesh/specs spelled positionally."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def subjaxprs(v) -> Iterator[jex_core.Jaxpr]:
    """The jaxprs held by one equation parameter (pjit bodies, control-flow
    branches and bodies, kernel bodies), looking inside tuples/lists."""
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from subjaxprs(x)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of ``jaxpr`` (a Jaxpr or ClosedJaxpr) and of all the
    jaxprs nested in its parameters, depth first."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                yield from iter_eqns(sub)


def primitive_names(jaxpr) -> set:
    """Names of every primitive in ``jaxpr``, nested jaxprs included."""
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr)}
