"""device_idle.<part> (device_idle.ops, device_idle.scan): share of the
traced window in which no program ran on the chip (profiler trace, XLA
Modules line)."""
from tracereduce import idle_percent


def read(run):
    return idle_percent(run.trace)
