"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on
and prints, as the LAST line of its standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``. Earlier lines
are observations, one JSON object each: the cell's metrics of the
other kind that this run could read (``also``; every metric comes from
its one reader), counts and checks.

Without a TPU holding the chips the cell asks for it fails at once:
exit code 1 and no result. ``--rehearse`` runs the same code at the
tiny sizes of the files' ``rehearse`` groups on the CPU; its device
says ``cpu`` and it prints no metric — a CPU timing is never written
under a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # as near the process's start as Python allows

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _say(**row) -> None:
    print(json.dumps(row), flush=True)


def run(args) -> dict:
    from benchmark.harness import context, device, peaks, stats

    manifest, ctx, dev = context.build(
        ROOT, args.workload, seed=args.seed, seconds=args.seconds,
        t_start=T_START, trace=bool(args.trace), rehearse=args.rehearse)
    cell, cache = ctx.cell, ctx.cache
    result = manifest.driver(ctx.config["driver"]).run(ctx)
    result.update(cell=cell, config=ctx.config, traffic=ctx.traffic,
                  seed=args.seed,
                  peaks=None if args.rehearse else peaks.of(dev["kind"]),
                  chips=cell["chips"])

    def read(kind):
        out = {}
        for entry in manifest.metrics_of(cell["name"], kind):
            value = manifest.reader(entry["name"])(result)
            if value is not None:
                out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return out

    kind, other = (("per_layer", "end_to_end") if args.trace
                   else ("end_to_end", "per_layer"))
    metrics = read(kind)
    dev["memory_peak_bytes"] = max(device.memory_peak_bytes(),
                                   result.get("program_bytes") or 0)
    trace = result.get("trace")
    if trace:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
    _say(also={} if args.rehearse else read(other),
         observed=stats.observations(result), checks=result["checks"],
         allocator_peak_bytes=device.memory_peak_bytes(),
         program_bytes=result.get("program_bytes"), cache_requests=cache.requests,
         cache_hits=cache.hits,
         compiles_in_window=result["compiles_in_window"])
    if not result["correct"]:
        _say(incorrect=result["why_incorrect"])
    line = {"correct": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {} if args.rehearse else metrics, "device": dev}
    if args.rehearse:
        line["rehearsal_metric_names"] = sorted(metrics)
    if trace:
        line["breakdown"] = trace["breakdown"]
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args()
    try:
        line = run(args)
    except Exception:  # noqa: BLE001 — the run ends here, with no result
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
