"""Host seconds of ``open_store`` (the host build of the index)."""


def read(run):
    return run.build_s
