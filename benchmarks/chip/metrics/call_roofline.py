"""call_roofline.<part> (call_roofline.ops, .scan): least time of the
calls, the bytes of their inputs and outputs in the form the cell passes
them at the peak HBM bandwidth of peaks.json, over the device-busy time
inside the call spans."""
from tracereduce import call_roofline


def read(run):
    return call_roofline(run.trace, run.least_bytes,
                         run.peaks["hbm_bytes_per_s"])
