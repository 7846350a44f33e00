"""Device time of the jitted decode step (module ``jit_step``), per decode
step in the traced window."""


def read(record):
    trace = record.get("trace")
    steps = record["steps"]["decode"]
    if not trace or not steps or "jit_step" not in trace["module_s"]:
        return None
    return 1e3 * trace["module_s"]["jit_step"] / steps
