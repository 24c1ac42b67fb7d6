"""Share (%) of the HBM roofline taken by the device Get: the bytes the
algorithm needs for the lanes that reached the device (Gets less CN-cache
hits and negative hits), over device busy time inside the Get spans times
the chip's HBM bandwidth."""

from lib import roofline, trace


def read(run):
    spans = run.trace.span("bench.get") if run.trace else []
    lanes = (run.ops("get") - run.meter["cache_hits"]
             - run.meter["cache_neg_hits"])
    if not spans or lanes <= 0:
        return None
    return roofline.roofline_share(roofline.get_bytes(lanes),
                                   trace.mean_covered(run.busy, spans),
                                   run.peaks["hbm_bytes_per_s"])
