"""ms in the program's ``repro.replica.fanout`` spans per 1,000 updates:
applying each write batch to the replicas other than the primary, the
host cost of K-safety.  None where no program span opens in an update
call (a program older than its replication spans)."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_kop(run, "update", "repro.replica.fanout")
