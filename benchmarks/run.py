"""Benchmark aggregator: one section per paper table/figure + the
roofline table (if dry-run artifacts exist).

Every registered section runs even if an earlier one fails its self-check or
raises — a single broken sweep must not mask the rest (the same failure mode
the CI pipeline fixed by dropping ``-x`` from the nightly). The exit code is
nonzero iff any section failed, and a summary table names the failures.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run --list      # section keys
  PYTHONPATH=src python -m benchmarks.run --only faults --fast
  PYTHONPATH=src python -m benchmarks.run --json results.json

``--only <key>`` runs a single registered section — CI smoke steps invoke
sections through it instead of duplicating per-benchmark subprocess
incantations in ci.yml. ``--json <path>`` writes a machine-readable summary
(per-section key/status/wall time + overall exit code) alongside the human
table, so CI consumes results without log-scraping.
"""
from __future__ import annotations

import argparse
import importlib
import json
import time
import traceback
from pathlib import Path

# (key, module, title, takes the --smoke tier args?) — in run order. The
# non-tier sections import jax; they are registered LAST so the sharded
# sims' worker pools (first sections) can still use the fast 'fork'
# start method (forking after the multithreaded JAX runtime initializes
# risks worker deadlock, and the fallback 'spawn' pool is slower to start).
_SECTIONS: list[tuple[str, str, str, bool]] = [
    ("perf", "perf_bench",
     "Engine perf -- events/sec (calendar-queue engine)", True),
    ("scale", "scale_sweep",
     "Array scale -- sharded 100+ SSD qd sweep", True),
    ("safs_scale", "safs_scale_sweep",
     "SAFS scale -- sharded SAFS pattern sweep @ 18/64/128 SSDs", True),
    ("raid", "raid_sweep",
     "Array layouts -- JBOD vs RAID-0 vs RAID-5 under active GC", True),
    ("qos", "qos_sweep",
     "Per-tenant QoS -- weighted shares + SLO protection under GC", True),
    ("gc_coord", "gc_coord_sweep",
     "GC coordination -- staggered/idle policies vs reactive trigger", True),
    ("faults", "faults_sweep",
     "Faults -- fail-slow/crash injection vs hedging + quarantine", True),
    ("telemetry", "telemetry_demo",
     "Telemetry -- GC rotation timeline, latency budget, overhead gate",
     True),
    ("monitor", "monitor_demo",
     "Monitor -- online alert rules, root causes, alert-vs-quarantine race",
     True),
    ("serving_replay", "serving_replay",
     "Serving replay -- KV-spill trace emit -> sharded replay under QoS+GC",
     True),
    ("paper_tables", "paper_tables",
     "Paper -- Table 1 / Table 2 / Figure 2 (raw array under GC)", False),
    ("paper_figs", "paper_figs",
     "Paper -- Figures 3-5, Table 3 (SAFS + dirty-page flusher)", False),
    ("roofline", "roofline",
     "Roofline -- per (arch x shape), single-pod 16x16 (from dry-run)",
     False),
]


def _run_section(results: list, key: str, title: str, fn, *fn_args) -> None:
    """Run one section, capturing its exit code (a raised exception counts
    as rc=1 and is printed, not propagated)."""
    print("=" * 72)
    print(title)
    print("=" * 72)
    t0 = time.time()
    try:
        rc = fn(*fn_args) or 0
    except Exception:
        traceback.print_exc()
        rc = 1
    results.append((key, title, rc, time.time() - t0))
    print()


def main(argv=None):
    keys = [k for k, _, _, _ in _SECTIONS]
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller op counts (CI)")
    ap.add_argument("--only", choices=keys, metavar="SECTION",
                    help=f"run a single section: {', '.join(keys)}")
    ap.add_argument("--list", action="store_true",
                    help="list registered section keys and exit")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write a machine-readable summary (per-section "
                         "status, wall time, exit code) to PATH")
    args = ap.parse_args(argv)
    if args.list:
        for key, _, title, _ in _SECTIONS:
            print(f"{key:14s} {title}")
        return 0
    tier = ["--smoke"] if args.fast else []
    sections = [s for s in _SECTIONS if args.only is None or s[0] == args.only]

    t0 = time.time()
    results: list[tuple[str, str, int, float]] = []
    for key, mod, title, takes_tier in sections:
        # lazy per-section import: --only never pays for (or breaks on) the
        # other sections' imports, and jax-importing sections stay unimported
        # until every fork-pool section has run
        module = importlib.import_module(f".{mod}", __package__)
        if takes_tier:
            _run_section(results, key, title, module.main, tier)
        else:
            _run_section(results, key, title, module.main)

    print("=" * 72)
    print("summary")
    print("=" * 72)
    for _key, title, rc, dt in results:
        status = "ok" if rc == 0 else f"FAIL (rc={rc})"
        print(f"  {status:12s} {dt:6.0f}s  {title}")
    n_failed = sum(1 for _, _, rc, _ in results if rc)
    total_wall_s = time.time() - t0
    print(f"\n{len(results) - n_failed}/{len(results)} sections passed; "
          f"total benchmark wall time: {total_wall_s:.0f}s")
    exit_code = 1 if n_failed else 0
    if args.json:
        Path(args.json).write_text(json.dumps({
            "fast": args.fast,
            "only": args.only,
            "sections": [
                {"key": key, "title": title, "status":
                 "ok" if rc == 0 else "fail", "exit_code": rc,
                 "wall_s": dt}
                for key, title, rc, dt in results
            ],
            "n_sections": len(results),
            "n_failed": n_failed,
            "total_wall_s": total_wall_s,
            "exit_code": exit_code,
        }, indent=1))
    return exit_code


if __name__ == "__main__":
    import sys
    sys.exit(main())
