"""Lanes resolved by the host Makeup-Get per 1,000 Gets, from the
program's ``get.makeup_lanes`` counter over the window."""

from lib import program_spans


def read(run):
    per_get = program_spans.counter_per_op(run, "get.makeup_lanes", "get")
    return None if per_get is None else per_get * 1e3
