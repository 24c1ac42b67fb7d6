"""Mean ms per Get call in the program's ``repro.get.makeup`` spans: the
host Makeup-Get of lanes the device Get left unmatched."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_call(run, "get", "repro.get.makeup")
