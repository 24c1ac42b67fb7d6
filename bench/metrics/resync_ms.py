"""ms in the window's calls spent re-installing a restarted replica's MN
state from a live one (``repro.replica.resync``).  None where the program
keeps no ``replica.resyncs`` counter (one older than its replication
spans)."""

from lib import program_spans

SPANS = ("repro.replica.resync",)


def read(run):
    if program_spans.counter_per_op(run, "replica.resyncs", None) is None:
        return None
    got = [g[0] for g in (program_spans.seconds_in_calls(run, op, SPANS)
                          for op in ("get", "update")) if g is not None]
    return sum(got) * 1e3 if got else None
