"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

  * device busy intervals: the union of the XLA modules' executions on each
    device (``/device:TPU:<n>``, line "XLA Modules"), clipped to the window;
  * device time per module name (the name without its hash), and per module
    name inside each kind of harness span;
  * idle gaps: each stretch in which a device ran nothing, attributed to the
    innermost harness span (host events named ``bench.*``) open at its
    middle, or to "none".

The window is the harness's ``bench.window`` span. Times are seconds.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
_HASH = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    return _HASH.sub("", name)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def read_events(path: Path):
    """(device modules {device: [(name, start, end)]}, host spans
    [(name, start, end)]) from an xplane file, in seconds."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    modules: dict[str, list] = defaultdict(list)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    modules[plane.name].append(
                        (module_name(e.name), s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return dict(modules), spans


def containing(ivs: list[tuple[float, float]], t: float):
    """The interval of the sorted, disjoint ``ivs`` that holds ``t``."""
    k = bisect.bisect_right(ivs, (t, float("inf"))) - 1
    if k >= 0 and ivs[k][0] <= t <= ivs[k][1]:
        return ivs[k]
    return None


def innermost(by_kind: dict, t: float) -> str:
    """The kind of the shortest harness span that holds ``t``."""
    best, best_len = "none", float("inf")
    for kind, ivs in by_kind.items():
        iv = containing(ivs, t)
        if iv is not None and iv[1] - iv[0] < best_len:
            best, best_len = kind, iv[1] - iv[0]
    return best


def reduce(modules: dict, spans: list, slack: float = 0.002) -> dict:
    """The reduced trace. ``slack`` widens each span on both sides when
    device work is attributed to it: device and host clocks agree only to
    about a millisecond."""
    win = [(a, b) for n, a, b in spans if n == WINDOW]
    if win:
        lo, hi = win[0]
    else:
        ends = [x for evs in modules.values() for _, a, b in evs for x in (a, b)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    window_s = hi - lo
    ordered = sorted((s for s in spans if s[0] != WINDOW), key=lambda s: s[1])

    busy_per_device, per_module = [], defaultdict(float)
    in_span = defaultdict(lambda: defaultdict(float))
    gaps = defaultdict(float)
    exact, widened = defaultdict(list), defaultdict(list)
    for name, a, b in ordered:
        exact[name].append((a, b))
        widened[name].append((a - slack, b + slack))
    exact = {k: union(v) for k, v in exact.items()}
    widened = {k: union(v) for k, v in widened.items()}
    for dev, events in sorted(modules.items()):
        events = [(n, max(a, lo), min(b, hi)) for n, a, b in events
                  if b > lo and a < hi]
        busy = union([(a, b) for _, a, b in events])
        busy_per_device.append(sum(b - a for a, b in busy))
        for n, a, b in events:
            per_module[n] += b - a
            mid = 0.5 * (a + b)
            for kind, ivs in widened.items():
                if containing(ivs, mid) is not None:
                    in_span[kind][n] += b - a
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[innermost(exact, 0.5 * (a + b))] += b - a
    n_dev = max(len(busy_per_device), 1)
    return {
        "window_s": window_s,
        "busy_s": sum(busy_per_device) / n_dev,
        "devices": len(busy_per_device),
        "module_s": dict(per_module),
        "module_s_in_span": {k: dict(v) for k, v in in_span.items()},
        "idle_gap_s": {k: v / n_dev for k, v in gaps.items()},
        "span_count": {k: sum(1 for n, _, _ in ordered if n == k)
                       for k in exact},
    }


def reduce_file(path: Path) -> dict:
    return reduce(*read_events(path))


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ten device modules that took most time, and the ten largest
    idle totals by what the host was doing."""
    ops = sorted(reduced["module_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gap_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
