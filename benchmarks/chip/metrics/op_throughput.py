"""op_throughput: elements of all machine op calls completed in the
window, each ready before it counts, over the window's wall seconds, in
millions per second."""


def read(run):
    return run.elements / run.window_s / 1e6
