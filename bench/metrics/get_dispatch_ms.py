"""Mean ms per Get call in the program's ``repro.get.dispatch`` spans: the
eager dispatch of the device Get, and any compile of a new batch shape."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_call(run, "get", "repro.get.dispatch")
