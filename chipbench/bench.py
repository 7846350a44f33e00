"""What every part of the harness shares: where the files are, how a cell is
found by its name, the run context, and the result line.

Nothing here imports JAX at module level, so the loaders can be used (and
tested) without touching a device.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

PKG = Path(__file__).resolve().parent          # chipbench/
ROOT = PKG.parent                              # the checkout
BENCHMARK = ROOT / "BENCHMARK.json"


class BenchError(RuntimeError):
    """A run that cannot go on: it exits nonzero and prints no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: Path = BENCHMARK) -> dict:
    if not path.exists():
        raise BenchError(f"no {path.name} at {path.parent}")
    return load_json(path)


def workload_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""

    name: str
    entry: dict                 # the workloads entry
    config: dict                # chipbench/configs/<config>.json
    cell: dict                  # chipbench/workloads/<cell>.json
    traffic: dict               # chipbench/traffic/<traffic>.json
    end_to_end: list = field(default_factory=list)   # metric entries it reports
    per_layer: list = field(default_factory=list)


def load_cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``bench`` (BENCHMARK.json at ``root`` by
    default), with its files, found under ``root`` by their names."""
    bench = benchmark(root / "BENCHMARK.json") if bench is None else bench
    entry = workload_entry(bench, name)
    cfg_entry = config_entry(bench, entry["config"])
    # an end-to-end metric without ``workloads`` is reported by every cell;
    # a per-layer metric always lists its cells
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return Cell(
        name=name, entry=entry,
        config=load_json(root / cfg_entry["file"]),
        cell=load_json(root / "chipbench" / "workloads" / f"{name}.json"),
        traffic=load_json(root / "chipbench" / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: Path = ROOT):
    """The reader of per-layer metric ``name``: ``chipbench/metrics/<name>.py``,
    a module with ``read(record) -> float | None``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    """``chipbench/drivers/<kind>.py``, which has ``run(ctx) -> record``."""
    try:
        return importlib.import_module(f"chipbench.drivers.{kind}")
    except ModuleNotFoundError as e:
        raise BenchError(f"no driver {kind!r}") from e


def use_program() -> None:
    """Puts the system under test (``src/`` of the checkout) on the path."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def cache_dir() -> Path:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``.jax_cache/`` at the root of the checkout. A fixed path: the
    path is part of the cache's key."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = Path(placed) if placed else ROOT / ".jax_cache"
    path.mkdir(parents=True, exist_ok=True)
    return path


def enable_compile_cache() -> Path:
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    # every program, the small eager ones too: a later run loads them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the executables JAX builds or loads (backend compile events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


@dataclass
class Context:
    """What a driver is given."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float              # process start, time.perf_counter()
    compiles: CompileCounter | None = None
    out_dir: Path | None = None   # where a traced run writes its profile
    check: bool = True            # compare with the reference after the window
    control: str | None = None    # compare the reference in this precision instead

    def say(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()


def device_info(devices) -> dict:
    """The ``device`` object of the result: as JAX reports it, with the peak
    memory of the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # the numbers compared, each beside its limit: under a key of its own,
    # last in the line
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return json.dumps(out)
