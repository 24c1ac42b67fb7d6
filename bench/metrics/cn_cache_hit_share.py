"""Share (%) of the window's Gets answered by the CN cache (window delta of
``meter_totals().cache_hits`` over Gets issued)."""


def read(run):
    gets = run.ops("get")
    if gets == 0:
        return None
    return 100.0 * run.meter["cache_hits"] / gets
