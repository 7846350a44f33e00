"""Time to first token of the requests due in the window (from the due time
to the first token on the host), 90th percentile. Above the knee the queue
grows all through the run, so this tail swings with the smallest change:
a per-layer reading, not a bounded one."""


def read(record):
    return record["e2e"].get("ttft_p90_s")
