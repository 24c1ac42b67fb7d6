"""95th percentile over every update of the window of (return of the call
that answered it - issue of its round), in ms."""

from lib import stats


def read(run):
    lat, lanes = run.latencies("update")
    if lanes.sum() == 0:
        return None
    return stats.percentile(lat, lanes, 95) * 1e3
