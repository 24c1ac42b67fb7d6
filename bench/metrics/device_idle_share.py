"""Share (%) of the traced window in which no operation ran on the device,
averaged over the devices."""

from lib import trace


def read(run):
    window = run.traced_window()
    if window is None or not run.busy:
        return None
    busy = trace.mean_covered(run.busy, [window])
    return 100.0 * (1.0 - busy / (window[1] - window[0]))
