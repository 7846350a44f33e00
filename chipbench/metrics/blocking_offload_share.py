"""Blocking offloads over all offloads in the window (the engine's own
counters), in percent: the paper's ratio of stalls to background flushes."""


def read(record):
    c = record["counters"]
    if not c["offloads"]:
        return None
    return 100.0 * c["blocking_offloads"] / c["offloads"]
