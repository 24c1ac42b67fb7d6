"""Mean ms per Get call in the program's ``repro.get.fetch`` spans: the
device-to-host readback of a Get's answer, where the host waits for the
device."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_call(run, "get", "repro.get.fetch")
