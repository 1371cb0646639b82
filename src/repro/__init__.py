"""repro: worst-case optimal low-memory dataflows (BiGJoin) in JAX.

x64 is enabled globally: the join engine packs 2-column index keys into
int64.  All model code uses explicit dtypes (bf16/f32/int32) so this does not
change numeric behaviour elsewhere.  Importing the package also turns on the
persistent compilation cache (:mod:`repro.core.compilestats`), before any of
its functions is compiled.
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.core import compilestats  # noqa: E402,F401

__version__ = "1.0.0"
