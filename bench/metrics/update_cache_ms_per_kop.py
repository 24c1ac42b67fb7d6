"""ms in the program's ``repro.cache.note`` spans per 1,000 updates: the CN
cache's per-lane coherence on the write path."""

from lib import program_spans


def read(run):
    return program_spans.ms_per_kop(run, "update", "repro.cache.note")
