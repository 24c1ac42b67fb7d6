"""Mean per Get call of (its span - the device time inside it), in ms: the
time the API stack and the host Get path hold a call."""

from lib import trace


def read(run):
    spans = run.trace.span("bench.get") if run.trace else []
    if not spans:
        return None
    wall = sum(e - s for s, e in spans)
    return (wall - trace.mean_covered(run.busy, spans)) / len(spans) * 1e3
