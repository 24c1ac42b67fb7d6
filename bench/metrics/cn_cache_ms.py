"""Mean ms per Get call in the program's ``repro.cache.probe`` and
``repro.cache.observe`` spans: the CN cache's lookup and its learning from
the answers."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_call(run, "get", "repro.cache.probe",
                                     "repro.cache.observe")
