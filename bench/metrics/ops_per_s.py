"""Ops answered correctly in the window over the window's seconds."""


def read(run):
    return run.ops_correct() / run.window_s
