"""Host time in the harness's spans around the pool's blocking paths
(``offload_now``, ``offload_now_evicted``, ``fetch``), per decode step."""

SPANS = ("bench.offload_now", "bench.offload_evicted", "bench.fetch")


def read(record):
    steps = record["steps"]["decode"]
    if not steps:
        return None
    return 1e3 * sum(record["spans_s"].get(s, 0.0) for s in SPANS) / steps
