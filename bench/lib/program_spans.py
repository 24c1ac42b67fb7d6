"""Per-call time of the program's own spans, and its counters, read for
the metrics of single steps of the Get and update path.

The program marks those steps with ``jax.profiler.TraceAnnotation``s named
``repro.*`` (``repro.obs.wall``) on the calling thread, so ``lib.trace``
keeps them in ``Trace.host``, on the clock of the benchmark's own
``bench.*`` spans.  A program without them (one older than its
instrumentation) reads None, never zero: no ``repro.`` span inside any call
of the op, or no ``repro.obs.wall`` module.
"""

from __future__ import annotations

import bisect

PROGRAM_PREFIX = "repro."


def seconds_in_calls(run, op: str, names) -> tuple[float, int] | None:
    """(seconds of the spans named ``names`` that lie inside a
    ``bench.<op>`` span, number of such calls); None without a trace, a
    call, or any program span inside a call."""
    calls = run.trace.span(f"bench.{op}") if run.trace else []
    if not calls:
        return None
    starts = [s for s, _ in calls]

    def inside(s: float, e: float) -> bool:
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= calls[i][1]

    mine = [(n, s, e) for n, s, e in run.trace.host
            if n.startswith(PROGRAM_PREFIX) and inside(s, e)]
    if not mine:
        return None
    return sum(e - s for n, s, e in mine if n in names), len(calls)


def ms_per_call(run, op: str, *names: str) -> float | None:
    """Mean ms per ``bench.<op>`` call spent in the named program spans."""
    got = seconds_in_calls(run, op, names)
    return None if got is None else got[0] / got[1] * 1e3


def ms_per_kop(run, op: str, *names: str) -> float | None:
    """ms in the named program spans per 1,000 lanes of ``op``."""
    got = seconds_in_calls(run, op, names)
    lanes = run.ops(op)
    if got is None or lanes == 0:
        return None
    return got[0] / lanes * 1e3 * 1e3


def counter_per_op(run, name: str, op: str) -> float | None:
    """Window growth of the program counter ``name`` per lane of ``op``;
    None where the program keeps no such counter or has lost samples."""
    try:
        from repro.obs import wall
    except ImportError:
        return None
    grown = wall.delta(name, run.window_start, run.window_end)
    lanes = run.ops(op)
    if grown is None or lanes == 0 or name not in wall.totals():
        return None
    return grown / lanes
