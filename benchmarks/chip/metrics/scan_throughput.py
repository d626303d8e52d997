"""scan_throughput: column rows of the predicate chunks completed in
the window, each counted only once its match count is read, over the
window's wall seconds, in millions per second."""


def read(run):
    return run.elements / run.window_s / 1e6
