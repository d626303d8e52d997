"""launches_per_call.<part> (launches_per_call.ops, .scan): programs
enqueued on the chip inside each call span, per call (profiler trace,
matched by run_id)."""
from tracereduce import launches_per_call


def read(run):
    return launches_per_call(run.trace)
