"""Benchmark harness: one module per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig11] [--fast]

Each benchmark prints ``name,us_per_call,derived`` CSV rows, and every
suite's rows are also appended to ``BENCH_<suite>.json`` (in --bench-dir,
default the repo root) as one commit-stamped entry per run — the
machine-readable perf trajectory across PRs.  Entry shape:

    {"commit": "<git short sha>", "timestamp": <unix seconds>,
     "fast": bool, "rows": [{"name", "us_per_call", "derived"}, ...]}
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, check=True).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def append_bench_json(bench_dir: Path, suite: str, rows: list[dict],
                      commit: str, fast: bool,
                      error: str | None = None) -> Path:
    """Append one run's rows to BENCH_<suite>.json (created on first use).

    A suite that raised mid-run still lands (its partial rows are real
    measurements) but carries an "error" field, so trajectory consumers
    can tell truncated entries from complete ones.
    """
    path = bench_dir / f"BENCH_{suite}.json"
    entries = []
    if path.exists():
        try:
            entries = json.loads(path.read_text())
        except json.JSONDecodeError as e:
            # a truncated file from an interrupted run must not take the
            # whole harness down — start the trajectory over, loudly
            print(f"{suite}/json-reset,0.0,corrupt {path.name}: {e!r}",
                  file=sys.stderr)
    entry = {"commit": commit, "timestamp": int(time.time()),
             "fast": fast, "rows": rows}
    if error is not None:
        entry["error"] = error
    entries.append(entry)
    path.write_text(json.dumps(entries, indent=1) + "\n")
    return path


def main() -> None:
    p = argparse.ArgumentParser("benchmarks.run")
    p.add_argument("--only", default="",
                   help="comma-separated subset (table1,fig11,...)")
    p.add_argument("--fast", action="store_true")
    p.add_argument("--bench-dir", default=str(REPO_ROOT),
                   help="where BENCH_<suite>.json trajectories live")
    args = p.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"

    from . import (common, fig7_distributions, fig8_batchsize, fig9_10_e3,
                   fig11_cost, roofline_bench, serve_bench, table1_accuracy,
                   table2_sensitivity, train_bench)
    benches = {
        "table1": table1_accuracy.main,
        "table2": table2_sensitivity.main,
        "fig7": fig7_distributions.main,
        "fig8": fig8_batchsize.main,
        "fig9_10": fig9_10_e3.main,
        "fig11": fig11_cost.main,
        "roofline": roofline_bench.main,
        "serve": serve_bench.main,
        "train": train_bench.main,
    }
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    commit = git_commit()
    bench_dir = Path(args.bench_dir)
    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.time()
        common.take_records()                   # drop any stale rows
        error = None
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            error = repr(e)
            failures.append((name, error))
            print(f"{name}/ERROR,0.0,{error}")
        rows = common.take_records()
        if rows or error is not None:   # errored zero-row runs land too
            try:
                path = append_bench_json(bench_dir, name, rows, commit,
                                         args.fast, error=error)
                print(f"{name}/json,0.0,{path.name}", file=sys.stderr)
            except OSError as e:        # unwritable dir: keep benching
                print(f"{name}/json-error,0.0,{e!r}", file=sys.stderr)
        print(f"{name}/total,{(time.time() - t0) * 1e6:.0f},done",
              file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
