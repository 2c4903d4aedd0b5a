"""Find a serving cell's knee, once, when the cell is defined.

    python3 benchmark/sweep.py --workload mistral7b.chat.steady --seconds 45 --lead-in 20

brings the cell's replica up once and offers its open-loop mix at 1, 2,
... requests/s, one lead-in + window + drain each. A rate is SUSTAINED
if the requests completed per second of the window reach 0.97 of the
rate offered and fewer requests than the engine has slots wait at the
window's end. The knee is the highest sustained rate; the cell's
``rate_rps`` is then written into its traffic file as 0.8 x knee, by
hand: the benchmark never searches. Offer the cell's own window
(``run_seconds``): near the knee the slots are still filling when a
shorter one ends, and it reads a sustained rate as missed. Needs the
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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default="1,2,3,4,5,6,7,8,9,10")
    ap.add_argument("--lead-in", type=float, default=None,
                    help="seconds of arrivals before each window (the "
                         "mix's own if not given): long enough for the "
                         "longest answer, or the batch is still filling")
    args = ap.parse_args()

    from benchmark.harness import context, stats

    manifest, ctx, dev = context.build(ROOT, args.workload, seed=args.seed,
                                       seconds=args.seconds, t_start=T_START)
    config, mix = ctx.config, ctx.traffic
    if args.lead_in is not None:
        mix = dict(mix, lead_in_s=args.lead_in)
    if "rate_rps" not in mix:
        raise SystemExit("only a mix offered at a rate has a knee to find")
    driver = manifest.driver(config["driver"])
    handle, engine, cfg, checks = driver.bring_up(ctx)
    slots = config["driver_args"]["engine"]["max_batch"]
    knee, misses = None, 0
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            out = driver.offer(ctx, handle, engine, cfg,
                               dict(mix, rate_rps=rate), args.seconds)
            done = sum(1 for r in out["requests"]
                       if r["done"] and 0.0 <= r["last"] < args.seconds)
            waiting = out["counters"]["end"]["waiting"]
            ok = (done / args.seconds >= 0.97 * rate and waiting < slots)
            print(json.dumps({
                "rate_rps": rate, "completed_rps": done / args.seconds,
                "waiting_at_end": waiting, "sustained": ok,
                "observed": stats.observations(out)}), flush=True)
            knee, misses = (rate, 0) if ok else (knee, misses + 1)
            if misses == 2:
                break
    finally:
        engine.close()
    print(json.dumps({"knee_rps": knee, "rate_rps_at_0.8": knee and 0.8 * knee,
                      "checks": checks, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
