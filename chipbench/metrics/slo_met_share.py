"""Share of the requests due in the window that met both limits of the cell
(time to first token, and mean time per output token), in percent."""


def read(record):
    req = record["requests"]
    if not req["due"]:
        return None
    return 100.0 * req["slo_met"] / req["due"]
