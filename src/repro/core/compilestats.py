"""Compile-event observability and the persistent compilation cache knob.

Two small facilities that make the latency tail a *measured* quantity
(DESIGN.md §8):

**Trace counters.**  A Python statement inside a jitted function's body runs
exactly when jax traces the function — i.e. once per distinct shape
signature, which on a single backend is once per XLA compilation.  Every
jitted fold in the repo calls :func:`record` with a stable name as its first
body statement, generalizing the old ``distributed._PROGRAM_BUILDS`` counter
to `merge_index`/`_commit_fold`/`_compact_fold`/dataflow steps.  ``StoreStats``
and ``EpochResult`` surface :func:`total` snapshots so tests and benchmarks
can assert "zero recompiles after warmup" instead of eyeballing medians.

**Persistent cache.**  :func:`enable_persistent_cache` turns on JAX's
persistent compilation cache so a restarted worker or CI run deserializes
XLA executables instead of recompiling them.  The directory is placed from
outside: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself;
no other directory is set in code), else the fixed in-checkout
``<repo>/.jax_cache`` — a path that never moves, since the path is part of
what a later process must find again.  Importing ``repro`` enables it,
before anything is compiled.
"""
from __future__ import annotations

import os
import pathlib
import threading
from typing import Dict, Mapping, Optional

_LOCK = threading.Lock()
_COUNTS: Dict[str, int] = {}
_PERSISTENT_HITS = [0]
_CACHE_DIR: Optional[str] = None

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = str(pathlib.Path(__file__).resolve().parents[3]
                     / ".jax_cache")
# LRU bound of the cache; a bound also makes JAX serialize every read and
# write of the directory behind a file lock, so concurrent processes (test
# workers, a pool's replicas) never read an entry another one is writing
CACHE_MAX_BYTES = 4 << 30


def record(name: str) -> None:
    """Count one trace (= compile) event.  Call as the FIRST statement of a
    jitted function body: the Python side of the body runs once per trace,
    never on cached concrete calls."""
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + 1


def total() -> int:
    """Total compile events since process start (or :func:`reset`)."""
    with _LOCK:
        return sum(_COUNTS.values())


def snapshot() -> int:
    """Alias of :func:`total` — pair with :func:`since` around a region."""
    return total()


def since(snap: int) -> int:
    """Compile events recorded after a :func:`snapshot`."""
    return total() - snap


def reset() -> None:
    with _LOCK:
        _COUNTS.clear()
        _PERSISTENT_HITS[0] = 0


def persistent_hits() -> int:
    """Executables deserialized from the persistent cache (0 unless
    :func:`enable_persistent_cache` ran and hits occurred)."""
    return _PERSISTENT_HITS[0]


def cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when disabled."""
    return _CACHE_DIR


def cache_dir_for(environ: Mapping[str, str]) -> str:
    """Where the cache goes under ``environ``: ``JAX_COMPILATION_CACHE_DIR``
    if set, else the fixed in-checkout path."""
    return environ.get(ENV_VAR) or CHECKOUT_CACHE


def enable_persistent_cache() -> str:
    """Turn on the persistent compilation cache (idempotent); returns its
    directory.  Must run before the process's first jit execution — later
    calls still help future compilations but cannot recover ones already
    done.  The thresholds are zeroed so even sub-second CPU kernels (our
    folds) persist; jax's own default would skip anything compiling in
    < 1s, which on the CPU CI lane is everything."""
    global _CACHE_DIR
    if _CACHE_DIR is not None:
        return _CACHE_DIR
    import jax
    from jax import monitoring

    path = cache_dir_for(os.environ)
    if ENV_VAR not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def _listener(event: str, **kw):
        if "cache_hit" in event:
            _PERSISTENT_HITS[0] += 1

    monitoring.register_event_listener(_listener)
    _CACHE_DIR = path
    return _CACHE_DIR


# ``repro/__init__`` imports this module, so the cache is on before the
# process compiles anything of the package
enable_persistent_cache()
