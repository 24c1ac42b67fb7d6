"""Backend compiles that start inside the measured window."""


def read(run):
    return float(run.compiles_in_window())
