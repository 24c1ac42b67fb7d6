"""Process start to the first op of the window: device init, data, host
build, cache fill and warm-up."""


def read(run):
    return run.setup_s
