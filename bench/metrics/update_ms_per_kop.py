"""Host clock inside the update calls per 1,000 updates, in ms."""


def read(run):
    calls = [c for c in run.calls if c.op == "update"]
    n = sum(c.lanes for c in calls)
    if n == 0:
        return None
    return sum(c.end - c.start for c in calls) / n * 1e3 * 1e3
