"""The one door for blocking device-to-host reads on the served epoch path.

Every read the epoch loop makes of a device value (normalize's counts and
rows, the commit's region counts, the compaction checks, the dataflow's
queue sizes, end-of-run scalars and collected output) goes through
:func:`read`, which

- opens a ``read.<site>`` profiler span around it, so a trace shows where
  the host waited on the device (``jax.profiler.TraceAnnotation``: a few
  hundred nanoseconds when no profiler runs);
- allows the transfer, so the path serves under
  ``jax.transfer_guard_device_to_host("disallow")`` and any read that
  bypasses this door raises;
- adds one read and the array's bytes to the caller's counters
  (``host_reads`` / ``host_read_bytes``: ``StoreStats``, or :class:`Reads`
  for one dataflow run, copied into its ``JoinResult``).

The read itself is the caller's ``convert`` (``int``, ``bool``,
``np.asarray``, ...), applied exactly as the caller would have.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, TypeVar

import jax

T = TypeVar("T")


@dataclasses.dataclass
class Reads:
    """Reads and bytes read during one dataflow run."""

    host_reads: int = 0
    host_read_bytes: int = 0


def read(site: str, counts, convert: Callable[..., T], x) -> T:
    """``convert(x)``; when ``x`` is a device array, under a ``read.<site>``
    span and counted in ``counts``."""
    if not isinstance(x, jax.Array):
        return convert(x)  # already on the host: nothing to wait for
    with jax.profiler.TraceAnnotation("read." + site), \
            jax.transfer_guard_device_to_host("allow"):
        out = convert(x)
    counts.host_reads += 1
    counts.host_read_bytes += x.nbytes
    return out
