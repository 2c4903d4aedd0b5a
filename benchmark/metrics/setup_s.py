"""Seconds from the process's start to the start of traffic: imports,
weights from the seed, compile or cache load, warm-up of the cell's own
shapes, the correctness check."""


def read(run):
    return run["setup_s"]
