"""call_roofline.q1: least time of the calls, the bytes of their inputs
and outputs in the form the cell passes them at the peak HBM bandwidth of
peaks.json, over the device-busy time inside the call spans, over the
calls that the device trace holds (``tracecover.covered``, the window's
first calls)."""
from tracecover import covered
from tracereduce import call_roofline


def read(run):
    return call_roofline(covered(run.trace), run.least_bytes,
                         run.peaks["hbm_bytes_per_s"])
