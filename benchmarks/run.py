"""Benchmark orchestrator: one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only quality,localization,...]

Each bench emits a machine-readable BENCH_<name>.json record
(benchmarks/record.py; directory from $BENCH_OUT, default
experiments/bench/) which this orchestrator collects into a combined
summary, plus the historical `name,us_per_call,derived` CSV lines and a
roofline summary table if dry-run records exist
(experiments/dryrun/*.json).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args, _ = ap.parse_known_args()
    only = set(filter(None, args.only.split(","))) if args.only else None

    from repro import compile_cache

    compile_cache.enable()
    run_started = time.time()
    benches = {}
    from . import bench_kernels, bench_quality, bench_localization, \
        bench_scaling, bench_serving, bench_weak_scaling

    benches["kernels"] = bench_kernels.main          # §IV-C hot path
    benches["quality"] = bench_quality.main          # Table I
    benches["localization"] = bench_localization.main  # Fig 3
    benches["scaling"] = bench_scaling.main          # Fig 4/5
    benches["weak_scaling"] = bench_weak_scaling.main  # Table II
    benches["serving"] = bench_serving.main          # job-server throughput

    if only:
        unknown = only - set(benches)
        if unknown:
            # a typo'd --only must not produce a green no-op run (the CI
            # bench gate depends on the named benches actually running)
            print(f"unknown bench name(s) {sorted(unknown)}; "
                  f"valid: {sorted(benches)}", file=sys.stderr)
            sys.exit(2)

    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"\n===== bench: {name} =====")
        t0 = time.time()
        try:
            fn()
            print(f"[{name} done in {time.time() - t0:.1f}s]")
        except Exception:
            failed.append(name)
            traceback.print_exc()
    # collect the machine-readable records; records from EARLIER runs are
    # kept (the perf trajectory spans runs) but flagged stale so the
    # combined summary never passes old numbers off as this run's
    from . import record

    records = record.collect()
    if records:
        print(f"\n===== bench records ({record.out_dir()}) =====")
        for name, payload in records.items():
            payload["stale"] = payload.get("written_at", 0) < run_started
            # a bench emits its record before its acceptance assert, so a
            # fresh record can still belong to a FAILED bench — flag it
            payload["bench_failed"] = name in failed
            # persist the flags into the per-bench file so a standalone
            # check_regression (which reads BENCH_<name>.json, not the
            # combined summary) sees them too
            record.emit(name, payload.get("rows", []),
                        derived=payload.get("derived"),
                        extra={"stale": payload["stale"],
                               "bench_failed": payload["bench_failed"],
                               "written_at": payload.get("written_at", 0)})
            derived = payload.get("derived") or {}
            headline = ", ".join(
                f"{k}={v}" for k, v in sorted(derived.items())
                if not isinstance(v, (dict, list))
            )
            marker = (" [stale: earlier run]" if payload["stale"] else
                      " [bench FAILED]" if payload["bench_failed"] else "")
            print(f"BENCH_{name}.json: {len(payload.get('rows', []))} rows"
                  + (f" ({headline})" if headline else "") + marker)
        combined = os.path.join(record.out_dir(), "bench_summary.json")
        with open(combined, "w") as f:
            json.dump(records, f, indent=2, sort_keys=True, default=float)
            f.write("\n")
        print(f"combined summary -> {combined}")
    # roofline summary (if the dry-run has produced records)
    try:
        from repro.launch import roofline

        table = roofline.summarize("experiments/dryrun/*.json")
        if table.count("\n") > 1:
            print("\n===== roofline summary (from dry-run) =====")
            print(table)
    except Exception:
        pass
    if failed:
        print(f"\nFAILED benches: {failed}")
        sys.exit(1)
    print("\nall benches passed")


if __name__ == "__main__":
    main()
