"""Wall-clock spans and counters of the served Get and update path, and of
the replica set and retry stage around it.

The :class:`~repro.obs.hub.TelemetryHub` keys everything to the op clock
and simulated microseconds, so its exports are bit-identical across seeded
reruns; wall time would break that contract.  This module is the other
half of ``repro.obs``: where the host time of a real call goes.

* :func:`span` is a ``jax.profiler.TraceAnnotation``: it is recorded only
  while a profiler trace runs (on the device trace's clock, so Perfetto,
  TensorBoard and ``jax.profiler.ProfileData`` show it beside the device
  ops) and costs under a microsecond otherwise.
* :func:`count` bumps a process-wide cumulative counter and keeps a
  bounded ring of ``(time.perf_counter(), total)`` samples per name, so
  :func:`delta` can read a counter's growth over any recent interval.

Nothing here has an option or feeds the hub; spans and counts are emitted
once per call, never per lane.  Names are listed in docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import threading
import time

import jax
from jax.profiler import TraceAnnotation

GET_UPLOAD = "repro.get.upload"
GET_DISPATCH = "repro.get.dispatch"
GET_FETCH = "repro.get.fetch"
GET_MAKEUP = "repro.get.makeup"
CACHE_PROBE = "repro.cache.probe"
CACHE_OBSERVE = "repro.cache.observe"
CACHE_NOTE = "repro.cache.note"
REPLICA_FANOUT = "repro.replica.fanout"
REPLICA_FAILOVER = "repro.replica.failover"
REPLICA_RESYNC = "repro.replica.resync"
RETRY_BACKOFF = "repro.retry.backoff"
H2D_BYTES = "get.h2d_bytes"
MN_UPLOADS = "get.mn_uploads"
MAKEUP_LANES = "get.makeup_lanes"
REPLICA_FAILOVERS = "replica.failovers"
REPLICA_RESYNCS = "replica.resyncs"
REPLICA_RESYNC_BYTES = "replica.resync_bytes"
RETRY_BACKOFF_LANES = "retry.backoff_lanes"

RING_SAMPLES = 1 << 16  # samples kept per counter

_lock = threading.Lock()
_totals: dict[str, int] = {}
_rings: dict[str, collections.deque] = {}
_dropped: set[str] = set()  # counters whose ring has lost its oldest samples


def span(name: str) -> TraceAnnotation:
    """A profiler span around the work of a ``with`` block."""
    return TraceAnnotation(name)


def device_span(name: str, *arrays):
    """:func:`span` when any of ``arrays`` is a device array, else a no-op:
    reading host numpy arrays waits for nothing."""
    if any(isinstance(a, jax.Array) for a in arrays):
        return TraceAnnotation(name)
    return contextlib.nullcontext()


def count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        total = _totals.get(name, 0) + int(n)
        _totals[name] = total
        ring = _rings.get(name)
        if ring is None:
            ring = _rings[name] = collections.deque(maxlen=RING_SAMPLES)
        elif len(ring) == ring.maxlen:
            _dropped.add(name)
        ring.append((time.perf_counter(), total))


def _at(samples, dropped: bool, t: float) -> int | None:
    i = bisect.bisect_right(samples, t, key=lambda s: s[0])
    if i:
        return samples[i - 1][1]
    return None if dropped else 0


def delta(name: str, t0: float, t1: float) -> int | None:
    """Growth of counter ``name`` over ``[t0, t1]`` (``perf_counter``
    seconds); None where the ring has dropped a sample the interval needs,
    so a truncated ring never undercounts."""
    with _lock:
        samples = list(_rings.get(name, ()))
        dropped = name in _dropped
    lo, hi = _at(samples, dropped, t0), _at(samples, dropped, t1)
    return None if lo is None or hi is None else hi - lo


def totals() -> dict[str, int]:
    """Every counter's cumulative value since the process started."""
    with _lock:
        return dict(_totals)
