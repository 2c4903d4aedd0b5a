"""The whole table of device operations of one traced run of a cell:
what section 5 of PERF.md reads its bottlenecks from, where `run.py`
prints the first few.

    python3 benchmark/op_table.py --workload <cell> --seed <n> [--seconds 45] [--top 120] --out chiprun_out/ops.json

Runs the cell as `run.py --trace 1` does and writes, as JSON: every
program's executions and device seconds in the traced stretch
(``program_s``), the ``top`` operations by self time on device 0
(``ops``: [short name, seconds], a fusion named by its kind and the
shape it produces), the stretch's busy and window seconds, the
counters' numbers, the check's readings and ``correct``. Needs the
chip, like `run.py`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--top", type=int, default=120)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from benchmark.harness import context, device

    manifest, ctx, dev = context.build(
        ROOT, args.workload, seed=args.seed, seconds=args.seconds,
        t_start=T_START, trace=True, rehearse=False)
    result = manifest.driver(ctx.config["driver"]).run(ctx)
    trace = result["trace"]
    numbers = lambda d: {k: v for k, v in d.items()
                         if isinstance(v, (int, float))}
    with open(args.out, "w") as f:
        json.dump({
            "program_s": {k: [len(v), sum(v)]
                          for k, v in trace["program_s"].items()},
            "ops": sorted(trace["op_self_s"].items(),
                          key=lambda kv: -kv[1])[:args.top],
            "busy_s": trace["busy_s"], "window_s": trace["window_s"],
            "counters": {k: numbers(v)
                         for k, v in result["counters"].items()},
            "checks": result.get("checks"), "correct": result["correct"],
            "device": dict(dev, memory_peak_bytes=device.memory_peak_bytes())},
            f, indent=1)
    print(json.dumps({"correct": result["correct"], "out": args.out}))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
