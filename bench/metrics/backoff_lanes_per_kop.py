"""Lanes answered backoff (and retried) per 1,000 ops of the window, from
the program's ``retry.backoff_lanes`` counter; None where the program
keeps no such counter."""

from lib import program_spans


def read(run):
    per_op = program_spans.counter_per_op(run, "retry.backoff_lanes", None)
    return None if per_op is None else per_op * 1e3
