"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only mriq,ga,...]

  bench_mriq         — §4.2/Fig.5: MRI-Q time & Watt*seconds, CPU vs offload
  bench_ga           — §3.1/Fig.2: GA evolution + power-fitness ablation
  bench_narrowing    — §3.2/Fig.3: candidate narrowing funnel
  bench_destinations — §3.3: mixed-destination selection + early exit
  bench_transfer     — §3.1: collective census / transfer batching
  bench_roofline     — §Roofline: three-term table from the dry-run
  bench_kernels      — Pallas kernel micro-bench (interpret mode)
  bench_power        — §4/Fig.5: Ws A/B via the telemetry stack (sampled
                       traces, phase energy, CPU-only vs offloaded)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from benchmarks import (bench_destinations, bench_ga, bench_kernels,
                        bench_mriq, bench_narrowing, bench_power,
                        bench_roofline, bench_transfer)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "mriq": bench_mriq,
    "ga": bench_ga,
    "narrowing": bench_narrowing,
    "destinations": bench_destinations,
    "transfer": bench_transfer,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
    "power": bench_power,
}


def _export_fleet_baseline() -> None:
    """Mirror the committed fleet baseline to the repo root.

    Every power-suite run leaves ``BENCH_fleet.json`` next to the
    checkout root so the CI artifact step (and anyone triaging a local
    run) always has the file, even when a later step fails before the
    fresh report is composed — CI then overwrites it with the
    fresh-composed doc from ``power-report.json``."""
    src = Path(__file__).resolve().parent / "data" / "BENCH_fleet.json"
    if not src.is_file():
        return
    dst = Path.cwd() / "BENCH_fleet.json"
    try:
        dst.write_text(src.read_text())
        print(f"# fleet baseline -> {dst}", flush=True)
    except OSError as e:  # read-only checkout: artifact is best-effort
        print(f"# fleet baseline copy skipped: {e}", flush=True)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json-out", default=None,
                    help="write a machine-readable report here: per-suite "
                         "output lines plus any structured numbers a suite "
                         "exposes via LAST_REPORT (bench_power's Ws "
                         "comparisons — the CI artifact)")
    ap.add_argument("--profile", default=None, metavar="OUT",
                    help="run each suite under cProfile and write the "
                         "top functions by cumulative time here (text; "
                         "the perf-triage artifact)")
    ap.add_argument("--profile-top", type=int, default=40,
                    help="how many rows --profile keeps per suite")
    args = ap.parse_args()
    names = (args.only.split(",") if args.only else list(SUITES))

    # the report always leads with what ran and what it measured: suites
    # fold their LAST_METRICS entries ({"workload", "metrics"}) into the
    # top-level metrics block keyed by workload
    doc: dict = {"workload": ",".join(names), "metrics": {}, "suites": {}}
    failures = 0
    profile_chunks: list[str] = []
    for name in names:
        mod = SUITES[name]
        print(f"\n# === {name} ({mod.__name__}) ===", flush=True)
        t0 = time.time()
        entry: dict = {}
        try:
            if args.profile:
                import cProfile
                import io
                import pstats
                prof = cProfile.Profile()
                lines = prof.runcall(mod.run)
                buf = io.StringIO()
                (pstats.Stats(prof, stream=buf)
                 .sort_stats("cumulative")
                 .print_stats(args.profile_top))
                profile_chunks.append(f"=== {name} ===\n{buf.getvalue()}")
            else:
                lines = mod.run()
            for line in lines:
                print(line, flush=True)
            entry["lines"] = lines
            entry["seconds"] = round(time.time() - t0, 2)
            report = getattr(mod, "LAST_REPORT", None)
            if report:
                entry["report"] = list(report)
            doc["metrics"].setdefault(name, {})["suite_seconds"] = \
                entry["seconds"]
            for m in getattr(mod, "LAST_METRICS", None) or []:
                doc["metrics"][m["workload"]] = dict(m["metrics"])
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # report and continue
            failures += 1
            entry["error"] = f"{type(e).__name__}: {e}"
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
        doc["suites"][name] = entry
        if name == "power":
            _export_fleet_baseline()
    if args.json_out:
        out = Path(args.json_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
        print(f"# json report -> {out}", flush=True)
    if args.profile:
        out = Path(args.profile)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("\n".join(profile_chunks))
        print(f"# profile -> {out}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
