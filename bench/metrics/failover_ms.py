"""ms in the window's calls spent switching the primary
(``repro.replica.failover``: away from a crashed replica, and back to it
once it has restarted, each with the drop of the old primary's device
copies) and retrying the calls answered backoff (``repro.retry.backoff``,
with the new primary's first upload).  None where the program keeps no
``replica.failovers`` counter (one older than its replication spans)."""

from lib import program_spans

SPANS = ("repro.replica.failover", "repro.retry.backoff")


def read(run):
    if program_spans.counter_per_op(run, "replica.failovers", None) is None:
        return None
    got = [g[0] for g in (program_spans.seconds_in_calls(run, op, SPANS)
                          for op in ("get", "update")) if g is not None]
    return sum(got) * 1e3 if got else None
