#!/usr/bin/env python3
"""Run a cell several times, each run with another seed, as the driver does,
and print each end-to-end metric's median and spread (the distance between
the quartiles over the median).

    python benchmark/measure.py --workload <name> --runs 6 --sets 2 \
        [--first-seed 1] [--trace-run] [--out chiprun_out/<tag>]

This process never touches JAX: every run is a child that has the chip to
itself, one after the other.  Each run's stdout goes to ``<out>.log`` and its
last line to ``<out>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values) -> float:
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / abs(statistics.median(values))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-run", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    out = args.out or os.path.join(ROOT, "chiprun_out", args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    plan = [(s, 0) for s in range(args.sets) for _ in range(args.runs)]
    if args.trace_run:
        plan.append((args.sets, 1))
    lines, seed = [], args.first_seed
    with open(out + ".log", "a") as log, open(out + ".jsonl", "a") as rows:
        for set_index, trace in plan:
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(trace),
            ]
            proc = subprocess.run(
                cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=log, text=True
            )
            log.write(proc.stdout)
            log.flush()
            last = proc.stdout.strip().splitlines()[-1:] or [""]
            row = dict(set=set_index, seed=seed, trace=trace, rc=proc.returncode)
            try:
                row["result"] = json.loads(last[0])
            except ValueError:
                row["result"] = None
            rows.write(json.dumps(row) + "\n")
            rows.flush()
            lines.append(row)
            print(json.dumps(row)[:600], flush=True)
            seed += 1
    summary = {}
    for set_index in range(args.sets):
        good = [
            r["result"]["metrics"] for r in lines
            if r["set"] == set_index and not r["trace"] and r["result"]
        ]
        # As the driver: the first run of all compiled and is left out of
        # setup_s.
        for name in (good[0] if good else {}):
            values = [m[name]["value"] for m in good if name in m]
            if name == "setup_s" and set_index == 0:
                values = values[1:]
            summary.setdefault(name, []).append(dict(
                median=statistics.median(values), spread=spread(values),
                n=len(values),
            ))
    print(json.dumps(dict(workload=args.workload, seconds=seconds,
                          summary=summary)), flush=True)
    return 0 if all(r["rc"] == 0 for r in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
