"""The serving program's own spans in a profiler trace, per host thread.

The program opens ``jax.profiler`` spans named ``serve.*`` around each phase
of a step and of an admission, and around each copy of its offload workers
(``src/repro/serving/engine.py``, ``kv_pool.py``). The profiler writes them
into the same ``.xplane.pb`` as the device's modules, on the same clock, one
host line per thread. This reduces them:

  * self time (a span's duration less the union of its children) and count
    per span path, such as ``serve.step/serve.admit/serve.prefill``, for the
    main thread and for the worker threads apart. The main thread is the
    line that holds ``bench.window``, else the one with most ``serve.step``
    spans;
  * each stretch in which the device ran nothing, found as
    ``trace_reduce.reduce`` finds it, put down to the path of the main
    thread's spans open at its middle, or to "none";
  * four readings per decode step or admission (``readings``);
  * the engine's window counters set against each other and against the
    worker threads' spans (``counter_readings``);
  * the shared clock: how long after the device ends the argmax module a
    ``serve.sync`` waits on that span ends on the host (``sync_lag``).

  python -m chipbench.program_trace --workload <cell> --seed <n> --seconds <s>

runs the cell traced, as ``python -m chipbench.run ... --trace 1`` does, and
prints after its result line one JSON line with this reduction of its trace.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace_reduce  # noqa: E402

PREFIX = "serve."
MAIN_SPAN = "serve.step"
SYNC = "serve.sync"
PREFILL = "serve.prefill"
IO_SPANS = ("serve.offload_io", "serve.fetch_io")
SYNC_MODULE = "jit__argmax"          # the step's argmax, which serve.sync waits on


def read_lines(path: Path) -> tuple[list, int | None]:
    """The ``serve.*`` spans of each host line, ``[[(name, start, end,
    args)]]`` in seconds, and the index of the main thread's line (None if
    no line holds a ``serve.step``)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    lines, window_line = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if e.name == trace_reduce.WINDOW:
                    window_line = len(lines)
                elif e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    spans.append((e.name, s, s + e.duration_ns * 1e-9,
                                  dict(e.stats)))
            lines.append(spans)
    return lines, main_line(lines, window_line)


def main_line(lines: list, window_line: int | None) -> int | None:
    if window_line is not None:
        return window_line
    counts = [sum(1 for s in spans if s[0] == MAIN_SPAN) for spans in lines]
    if not counts or max(counts) == 0:
        return None
    return counts.index(max(counts))


def tree(spans: list) -> list:
    """Spans of one thread as a forest of ``[name, start, end, children]``:
    on one thread a span opened inside another closes inside it."""
    roots, stack = [], []
    for name, a, b, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        node = [name, a, b, []]
        while stack and a >= stack[-1][2]:
            stack.pop()
        (stack[-1][3] if stack else roots).append(node)
        stack.append(node)
    return roots


def _walk(nodes: list, prefix: str, self_s: dict, n: dict) -> None:
    for name, a, b, children in nodes:
        path = f"{prefix}/{name}" if prefix else name
        inner = trace_reduce.union([(max(x, a), min(y, b))
                                    for _, x, y, _ in children])
        self_s[path] += (b - a) - sum(y - x for x, y in inner)
        n[path] += 1
        _walk(children, path, self_s, n)


def path_at(roots: list, t: float) -> str:
    """The path of the spans open at ``t``, outermost first, or "none"."""
    names, nodes = [], roots
    while nodes:
        k = bisect.bisect_right([nd[1] for nd in nodes], t) - 1
        if k < 0 or not nodes[k][1] <= t <= nodes[k][2]:
            break
        names.append(nodes[k][0])
        nodes = nodes[k][3]
    return "/".join(names) or "none"


def window_of(modules: dict, spans: list) -> tuple[float, float]:
    """The window as ``trace_reduce.reduce`` takes it."""
    win = [(a, b) for n, a, b in spans if n == trace_reduce.WINDOW]
    if win:
        return win[0]
    ends = [x for evs in modules.values() for _, a, b in evs for x in (a, b)]
    return (min(ends), max(ends)) if ends else (0.0, 0.0)


def idle_stretches(modules: dict, lo: float, hi: float) -> dict:
    """Per device, the stretches of the window in which it ran nothing, as
    ``trace_reduce.reduce`` finds them."""
    out = {}
    for dev, events in sorted(modules.items()):
        busy = trace_reduce.union([(max(a, lo), min(b, hi)) for _, a, b in events
                                   if b > lo and a < hi])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        out[dev] = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return out


def reduce(modules: dict, spans: list, lines: list, main: int | None) -> dict:
    """The ``program`` reduction. ``modules`` and ``spans`` are what
    ``trace_reduce.read_events`` gives; ``lines`` and ``main`` what
    ``read_lines`` gives. Spans are clipped to the window, where the trace
    has one (a window or a device)."""
    lo, hi = window_of(modules, spans)
    if hi <= lo:
        lo, hi = float("-inf"), float("inf")
    parts = {side: (defaultdict(float), defaultdict(int))
             for side in ("main", "worker")}
    roots = []
    for i, line in enumerate(lines):
        forest = tree([(nm, max(a, lo), min(b, hi)) for nm, a, b, *_ in line
                       if b > lo and a < hi])
        if i == main:
            roots = forest
        _walk(forest, "", *parts["main" if i == main else "worker"])
    program = {side: {"self_s": dict(self_s), "n": dict(n)}
               for side, (self_s, n) in parts.items()}
    gaps = defaultdict(float)
    stretches = idle_stretches(modules, lo, hi)
    for ivs in stretches.values():
        for a, b in ivs:
            gaps[path_at(roots, 0.5 * (a + b))] += b - a
    n_dev = max(len(stretches), 1)
    program["idle_gap_s"] = {k: v / n_dev for k, v in gaps.items()}
    return program


def reduce_file(path: Path) -> dict:
    modules, spans = trace_reduce.read_events(path)
    lines, main = read_lines(path)
    program = reduce(modules, spans, lines, main)
    program["sync_lag"] = sync_lag(modules, lines[main] if main is not None else [])
    return program


def readings(program: dict, steps: dict) -> dict:
    """Four readings of the reduction, in ms, each None where there is
    nothing to read. ``steps`` is the harness's count of the window's decode
    steps and admissions (``record["steps"]``).

    * ``sched_idle_ms_per_step``: device idle under ``serve.step`` and not
      under ``serve.prefill``, per decode step;
    * ``admit_idle_ms_per_req``: device idle under ``serve.prefill``, per
      admission;
    * ``sched_host_ms_per_step``: the main thread's self time under
      ``serve.step``, less ``serve.sync`` and all under ``serve.prefill``,
      per decode step;
    * ``offload_io_ms_per_step``: the worker threads' time in
      ``serve.offload_io`` and ``serve.fetch_io``, per decode step.
    """
    decode, prefills = steps.get("decode", 0), steps.get("prefills", 0)
    idle = program["idle_gap_s"]
    host, io = program["main"]["self_s"], program["worker"]["self_s"]
    # idle is read only where the trace has a device and the program spans
    traced_device = bool(idle) and bool(host)

    def per(paths, seconds, count, found=True):
        return 1e3 * sum(seconds[p] for p in paths) / count if found and count else None

    def seg(p):
        return p.split("/")

    sched_idle = [p for p in idle if seg(p)[0] == MAIN_SPAN and PREFILL not in seg(p)]
    admit_idle = [p for p in idle if PREFILL in seg(p)]
    sched_host = [p for p in host if seg(p)[0] == MAIN_SPAN
                  and PREFILL not in seg(p) and seg(p)[-1] != SYNC]
    io_paths = [p for p in io if seg(p)[-1] in IO_SPANS]
    return {
        "sched_idle_ms_per_step": per(sched_idle, idle, decode, traced_device),
        "admit_idle_ms_per_req": per(admit_idle, idle, prefills, traced_device),
        "sched_host_ms_per_step": per(sched_host, host, decode, bool(sched_host)),
        "offload_io_ms_per_step": per(io_paths, io, decode, bool(io_paths)),
    }


def counter_readings(program: dict, counters: dict) -> dict:
    """The engine's window counters (``record["counters"]``) set against
    each other and against the trace, each None where a counter is missing
    or its denominator is 0. Background offloads are ``offloads`` less
    ``blocking_offloads`` (each blocking offload counts once in both).

    * ``stale_share``: queued flushes dropped as stale, in percent of the
      flushes the flusher queued (``stale_discards / flush_requests``);
    * ``flushed_share``: queued flushes that put a page in the host tier, in
      percent (background offloads over ``flush_requests``); above 100
      where flushes queued before the window complete in it;
    * ``offload_spans_per_offload``: the worker threads' ``serve.offload_io``
      spans in the trace per background offload; below 1 where copies were
      counted that the trace did not record, as where the closing counters
      are read after the profiler stopped while the workers went on;
    * ``alloc_failure_share``: page allocations the full pool refused, in
      percent of all (``alloc_failures / allocs``);
    * ``unflushed_share_at_preempt``: blocking offloads of full pages that
      the flusher had not yet cleaned when their sequence was preempted, in
      percent of all blocking offloads.
    """
    def ratio(num, den, scale=100.0):
        if num is None or den is None or not den:
            return None
        return scale * num / den

    c = counters
    background = (c["offloads"] - c["blocking_offloads"]
                  if "offloads" in c and "blocking_offloads" in c else None)
    io = program["worker"]["n"]
    spans = sum(n for p, n in io.items() if p.split("/")[-1] == IO_SPANS[0])
    return {
        "stale_share": ratio(c.get("stale_discards"), c.get("flush_requests")),
        "flushed_share": ratio(background, c.get("flush_requests")),
        "offload_spans_per_offload": ratio(spans, background, 1.0),
        "alloc_failure_share": ratio(c.get("alloc_failures"), c.get("allocs")),
        "unflushed_share_at_preempt": ratio(c.get("unflushed_at_preempt"),
                                            c.get("blocking_offloads")),
    }


def sync_lag(modules: dict, main_spans: list, slack: float = 0.002) -> dict:
    """For each ``serve.sync`` on the main thread, the first argmax module
    on the device that starts no earlier than ``slack`` before the span: the
    one the span waits on. The lag is the span's end less the module's end,
    which is at least 0 where the clocks agree (the token still has to reach
    the host). Gives the count, the share of lags above ``-slack``, and the
    lags' 1st, 50th and 99th percentiles in ms."""
    ends = sorted((a, b) for evs in modules.values() for n, a, b in evs
                  if n == SYNC_MODULE)
    starts = [a for a, _ in ends]
    lags = []
    for name, a, b, *_ in main_spans:
        if name != SYNC:
            continue
        k = bisect.bisect_left(starts, a - slack)
        if k < len(ends):
            lags.append(b - ends[k][1])
    if not lags:
        return {"n": 0}
    lags.sort()

    def q(p):
        return 1e3 * lags[min(len(lags) - 1, int(p * len(lags)))]

    return {"n": len(lags),
            "share_within_slack": sum(x >= -slack for x in lags) / len(lags),
            "lag_ms": {"p1": q(0.01), "p50": q(0.5), "p99": q(0.99)}}


def traced(args, **execute_kw) -> tuple[str, dict, dict]:
    """Runs a cell as ``run.execute`` does with ``--trace 1`` and reduces
    the program's spans from its trace before the trace is deleted. Returns
    (result line, record, reduction with its readings and counter
    readings)."""
    from chipbench import run
    args.trace = 1
    found = {}
    inner = trace_reduce.reduce_file

    def reduce_both(path):
        found["program"] = reduce_file(path)
        return inner(path)

    trace_reduce.reduce_file = reduce_both
    try:
        line, record = run.execute(args, **execute_kw)
    finally:
        trace_reduce.reduce_file = inner
    program = found["program"]
    program["readings"] = readings(program, record["steps"])
    program["counters"] = counter_readings(program, record["counters"])
    return line, record, program


def main(argv=None) -> int:
    from chipbench import bench, run
    args = run.parse(argv)
    try:
        line, _, program = traced(args)
    except bench.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    print(json.dumps(program), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
