"""Mean ms per Get call in the program's ``repro.get.upload`` spans: the
host-to-device copies of the CN and MN arrays, the key halves and any
Makeup-Get answers."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_call(run, "get", "repro.get.upload")
