"""Device busy time inside the Get spans per 1,000 Gets issued, in us."""

from lib import trace


def read(run):
    spans = run.trace.span("bench.get") if run.trace else []
    gets = run.ops("get")
    busy = trace.mean_covered(run.busy, spans)
    if not spans or busy <= 0 or gets == 0:
        return None
    return busy / gets * 1e3 * 1e6
