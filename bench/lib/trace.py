"""Reduction of a profiler trace to device busy time and host spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` line of every ``/device:``
plane; host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events, whose names start with ``bench.``, on the host plane.  Both are on
the profiler's one clock, in seconds here.  The functions below take plain
interval arrays, so they read the same whatever produced the device work.
"""

from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list[tuple[str, float, float, int]]  # (name, start_s, end_s, device)
    spans: dict[str, list[tuple[float, float]]]  # bench span name -> intervals
    host: list[tuple[str, float, float]]  # other events of the bench's thread
    n_devices: int

    def span(self, name: str) -> list[tuple[float, float]]:
        return self.spans.get(name, [])

    def busy(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per device, the disjoint intervals in which an operation ran."""
        return [union([(s, e) for _, s, e, d in self.ops if d == dev])
                for dev in range(self.n_devices)]


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_profile(profile) -> Trace:
    """Build a ``Trace`` from a ``jax.profiler.ProfileData``."""
    ops, spans, host_lines = [], {}, []
    n_devices = 0
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            for ln in lines:
                for e in ln.events:
                    s = e.start_ns * 1e-9
                    ops.append((e.name, s, s + e.duration_ns * 1e-9,
                                n_devices))
            n_devices += bool(lines)
            continue
        for ln in plane.lines:
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9) for e in ln.events]
            mine = [ev for ev in events if ev[0].startswith(SPAN_PREFIX)]
            for name, s, t in mine:
                spans.setdefault(name, []).append((s, t))
            if mine:
                host_lines.append([ev for ev in events
                                   if not ev[0].startswith(SPAN_PREFIX)])
    host = [ev for line in host_lines for ev in line]
    ops.sort(key=lambda ev: ev[1])
    for v in spans.values():
        v.sort()
    host.sort(key=lambda ev: ev[1])
    return Trace(ops=ops, spans=spans, host=host, n_devices=n_devices)


def load(trace_dir) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(find_xplane(trace_dir))))


def union(intervals) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted ``(starts, ends)`` covering the given intervals."""
    if len(intervals) == 0:
        return np.zeros(0), np.zeros(0)
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new block starts where an interval begins after all before it end
    new = np.ones(iv.shape[0], bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.nonzero(new)[0]
    starts = iv[idx, 0]
    stops = ends[np.append(idx[1:] - 1, iv.shape[0] - 1)]
    return starts, stops


def covered(merged, a: float, b: float) -> float:
    """Seconds of ``[a, b]`` that the disjoint ``merged`` intervals cover."""
    starts, ends = merged
    if b <= a or starts.shape[0] == 0:
        return 0.0
    return float(np.clip(np.minimum(ends, b) - np.maximum(starts, a),
                         0.0, None).sum())


def covered_in(merged, spans) -> float:
    return sum(covered(merged, a, b) for a, b in spans)


def mean_covered(busy, spans) -> float:
    """Device seconds inside ``spans``, averaged over the devices."""
    if not busy:
        return 0.0
    return sum(covered_in(m, spans) for m in busy) / len(busy)


def gaps(merged, a: float, b: float) -> list[tuple[float, float]]:
    """The intervals of ``[a, b]`` that ``merged`` leaves uncovered."""
    starts, ends = merged
    keep = (ends > a) & (starts < b)
    s, e = np.maximum(starts[keep], a), np.minimum(ends[keep], b)
    lo = np.concatenate([[a], e])
    hi = np.concatenate([s, [b]])
    return [(x, y) for x, y in zip(lo.tolist(), hi.tolist()) if y > x]


def innermost(events, t: float) -> str | None:
    """Name of the shortest event that holds time ``t``."""
    best, width = None, np.inf
    for name, s, e in events:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def top_ops(trace: Trace, a: float, b: float, k: int = 10):
    """The ``k`` device operations with the most time inside ``[a, b]``."""
    total: dict[str, float] = {}
    for name, s, e, _ in trace.ops:
        d = min(e, b) - max(s, a)
        if d > 0:
            total[name] = total.get(name, 0.0) + d
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def top_gaps(trace: Trace, merged, a: float, b: float, k: int = 10):
    """The ``k`` longest idle gaps of the devices inside ``[a, b]``, each
    named by the bench span and the host event that held its midpoint."""
    longest = sorted(gaps(merged, a, b), key=lambda g: g[0] - g[1])[:k]
    spans = [(name, s, e) for name, ivs in trace.spans.items()
             for s, e in ivs]
    out = []
    for s, e in longest:
        mid = 0.5 * (s + e)
        label = innermost(spans, mid) or "outside"
        host = innermost(trace.host, mid)
        out.append((f"{label}/{host}" if host else label, e - s))
    return out
