"""Gaps between consecutive tokens of a request in the window, preemption
stalls included, 95th percentile."""


def read(record):
    return record["e2e"].get("itl_p95_ms")
