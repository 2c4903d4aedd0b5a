"""Measure a cell's run-to-run spread, to set or check a bound.

    python3 benchmark/spread.py --workload <cell> [--sets 2] [--runs 6] [--out FILE]

makes ``sets`` sets of ``runs`` runs of `run.py` (the same seeds in
every set, another seed for each run of a set, each run a process of
its own, one after the other) at the manifest's ``run_seconds`` and
prints, for each end-to-end metric (and each per-layer metric that an
untraced run can read, which it prints on its ``also`` line), each
set's values, median and spread. A spread is the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median; a
bound is about five times the widest, never under 1 %. This process
never touches JAX: the chip belongs to the run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (2147483659, 2147483693, 2147483713, 1234567891, 987654321, 19,
         2147483777, 1000000007, 77, 2147483801)


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values) -> float:
    """The spread with the run farthest from the median left out: what
    the driver reads (the mean of it over the sets) when it asks whether
    a bound is too tight, which is so over half the bound."""
    mid = statistics.median(values)
    far = max(values, key=lambda v: abs(v - mid))
    rest = list(values)
    rest.remove(far)
    return spread(rest)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--out", default=None, help="append every run's lines")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    sets = []
    for s in range(args.sets):
        rows = []
        for seed in SEEDS[:args.runs]:
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", str(seed), "--seconds",
                str(manifest["run_seconds"]), "--trace", "0"]
            done = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            if done.returncode:
                sys.stderr.write(done.stderr[-3000:])
                return done.returncode
            lines = done.stdout.strip().splitlines()
            if args.out:
                with open(args.out, "a") as f:
                    f.write("\n".join(lines[-2:]) + "\n")
            last = json.loads(lines[-1])
            also = json.loads(lines[-2]).get("also", {})
            print(json.dumps({"set": s, "seed": seed, **last}), flush=True)
            if not last["correct"] or last["failed"]:
                return 1
            rows.append({k: v["value"]
                         for k, v in {**also, **last["metrics"]}.items()})
        sets.append(rows)
    for name in sets[0][0]:
        per_set = [[row[name] for row in rows] for rows in sets]
        # setup_s: each set's first run is the one that may compile.
        judged = [v[1:] if name == "setup_s" else v for v in per_set]
        print(json.dumps({
            "metric": name, "values": per_set,
            "median": [statistics.median(v) for v in judged],
            "spread": [spread(v) for v in judged],
            "widest_spread": max(spread(v) for v in judged),
            "mean_trimmed_spread": statistics.mean(trimmed(v) for v in judged),
            "bound_at_5x": max(0.01, 5 * max(spread(v) for v in judged)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
