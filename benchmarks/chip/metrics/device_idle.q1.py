"""device_idle.q1: share of the traced window in which no program ran on
the chip, over the part of the window that the device trace holds
(``tracecover.covered``: a Q1 window runs more device events than the
profiler keeps)."""
from tracecover import covered
from tracereduce import idle_percent


def read(run):
    return idle_percent(covered(run.trace))
