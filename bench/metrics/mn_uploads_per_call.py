"""Get calls that re-sent the MN arrays to the device, per Get call of the
window, from the program's ``get.mn_uploads`` counter: 0 where the arrays
stay resident between Gets, 1 where every Get call follows a write."""

COUNTER = "get.mn_uploads"


def read(run):
    """None where the program keeps no such counter (a program older than
    it) or its ring has lost a sample the window needs."""
    try:
        from repro.obs import wall
    except ImportError:
        return None
    calls = sum(1 for c in run.calls if c.op == "get")
    grown = wall.delta(COUNTER, run.window_start, run.window_end)
    if grown is None or calls == 0 or COUNTER not in wall.totals():
        return None
    return grown / calls
