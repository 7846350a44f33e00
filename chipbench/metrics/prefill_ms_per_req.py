"""Device time of the modules that ran inside the harness's admission spans
(``bench.prefill``: the jitted prefill and the page writes into the pool),
per admission in the traced window."""


def read(record):
    trace = record.get("trace")
    n = record["spans_n"].get("bench.prefill", 0)
    modules = trace and trace["module_s_in_span"].get("bench.prefill")
    if not modules or not n:
        return None
    return 1e3 * sum(modules.values()) / n
