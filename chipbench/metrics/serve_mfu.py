"""The whole serving step's share of the chip's peak FLOP/s, in percent: the
FLOPs that every prompt token prefilled and every decode step in the window
require (chipbench/work.py), over the window times the peak."""


def read(record):
    peak, steps = record.get("peak"), record["steps"]
    if not peak or not record.get("trace"):
        return None
    flops = sum(f for f, _ in steps["per_step"]) + steps["prefill_flops"]
    return 100.0 * flops / (record["window_s"] * peak["flops_per_s"])
