"""launches_per_call.q1: programs enqueued on the chip inside each call
span, per call, over the calls that the device trace holds
(``tracecover.covered``)."""
from tracecover import covered
from tracereduce import launches_per_call


def read(run):
    return launches_per_call(covered(run.trace))
