"""The share of the traced window in which the device ran nothing, in
percent: 1 - busy / window, busy being the union of its modules' runs."""


def read(record):
    trace = record.get("trace")
    if not trace or not trace["devices"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
