"""setup_s: seconds from process start to the first measured call:
imports, chip start-up, data from the seed and one pass over every shape
(compilation included, from the persistent cache after the first run)."""


def read(run):
    return run.setup_s
