"""Pallas kernels of the WCOJ dataflow, and which of them the default path
runs.

Four kernel families exist: ``member`` (membership probes over every region
of a versioned index, ``intersect/``), ``extend`` (the fused extension step,
``extend/``), ``rank`` (merge ranks, ``merge/merge.py``) and ``fold`` (the
fused per-relation commit fold, ``merge/fold.py``).  A family is on the
default path only if it compiles for a TPU (Mosaic) at real widths.  The
choice is static and the same on every platform, so the CPU lane runs the
path the chip runs; a family that is off stays importable, is tested in
interpret mode, and is what the jnp reference path replaces.
"""

# Families off the default path, each with what Mosaic raises when it is
# compiled for TPU v5e at real widths (jax 0.9.0, cap 4096, 2048 queries).
# Under x64 the body's index arithmetic is int64 and lowering recurses; with
# int32 indices it stops at the in-kernel vector gathers of its binary
# searches and row fetches; int64 keys find no 64-bit vectors.
_MOSAIC = ("RecursionError in Mosaic dtype conversion; with int32 indices, "
           "NotImplementedError: Only 2D gather is supported")
OFF_DEFAULT_PATH = {
    "extend": _MOSAIC,
    "rank": _MOSAIC + "; int64 keys: ZeroDivisionError: integer modulo by "
            "zero",
    "fold": _MOSAIC,
}
FAMILIES = ("member", "extend", "rank", "fold")


def on_default_path(family: str) -> bool:
    """Whether the default path runs kernel ``family`` (else jnp)."""
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}")
    return family not in OFF_DEFAULT_PATH


def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call eqns in fn's jaxpr, descending into sub-jaxprs
    (pjit bodies, control-flow branches).  Used by tests and benchmarks to
    verify kernel-launch fusion (one launch per probe / per level branch)."""
    import jax

    from repro.compat import iter_eqns
    return sum(eqn.primitive.name == "pallas_call"
               for eqn in iter_eqns(jax.make_jaxpr(fn)(*args)))
