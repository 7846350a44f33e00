"""The decode step's share of its roofline, in percent: for each step the
least time the chip could take (the larger of the required FLOPs over peak
FLOP/s and the required bytes over peak bandwidth, from chipbench/work.py),
summed, over the device time of ``jit_step``."""
from chipbench import work


def read(record):
    trace, peak = record.get("trace"), record.get("peak")
    per_step = record["steps"]["per_step"]
    if not trace or not peak or not per_step or not trace["module_s"].get("jit_step"):
        return None
    least = sum(work.roofline_seconds(work.Work(f, b), peak)[0] for f, b in per_step)
    return 100.0 * least / trace["module_s"]["jit_step"]
