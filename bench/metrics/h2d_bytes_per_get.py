"""Bytes the Get path hands to the device per Get, from the program's
``get.h2d_bytes`` counter over the window."""

from lib import program_spans


def read(run):
    return program_spans.counter_per_op(run, "get.h2d_bytes", "get")
