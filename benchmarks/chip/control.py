"""Readings of the checks' numbers: sound runs, the control, planted faults.

    python3 benchmarks/chip/control.py --workload phage_cami.s3000 \
        --seconds 45 --runs sound:101 control:201 drop_contig:301

Runs everything in one process on the chip, so the programs compile once
(a planted fault clears JAX's caches, and its run compiles them anew):
each `<plant>:<seed>` is one short run of the cell (a window of
`--seconds`, the reference check) through `run.run_cell`, without the
warm-up assembly.  `sound` runs the program as it is, `control` under the
control (alignment without its verification), any other name under that
fault of `lib/plants.py`.  Prints one JSON line per run with the numbers
compared; the limits in the configuration are set from these readings.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import run
from lib import plants

PLANTS = dict(plants.FAULTS, sound=contextlib.nullcontext,
              control=plants.skip_verify)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--runs", nargs="+", required=True,
                    help="<plant>:<seed>, plant one of " + ", ".join(PLANTS))
    args = ap.parse_args(argv)
    runs = [(p, int(s)) for p, s in (r.split(":") for r in args.runs)]
    unknown = sorted({p for p, _ in runs} - set(PLANTS))
    if unknown:
        ap.error(f"unknown plants {unknown}")
    cell = run.load_cell(args.workload)
    jax = run.use_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print("error: JAX finds no TPU", file=sys.stderr)
        return 1
    peaks = run.device_peaks(device["kind"])
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    for plant, seed in runs:
        with PLANTS[plant]():
            line = run.run_cell(cell, seed, args.seconds, False, device,
                                peaks, warmup=False)
        print(json.dumps({"run": plant, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": {k: c["value"]
                                     for k, c in line["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
