"""Show that ``correct`` can fail: a serving cell's replica brought up
with the engine's own weight-only int8 (``quantize="int8"``), held to
the float32 reference on the bf16 weights the driver made.

    python3 benchmark/degraded.py --workload mistral7b.chat.steady [--layers 8]

exits 0 if the check refused the degraded replica (and prints what it
said), 1 if it let it pass. ``--layers`` cuts the depth where bf16 and
int8 weights together do not fit the chip. Needs the chip, like
`run.py`; ``--rehearse`` runs the tiny sizes on the CPU.
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
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark.drivers import common
    from benchmark.harness import context

    manifest, ctx, dev = context.build(
        ROOT, args.workload, seed=args.seed, seconds=0.0, t_start=T_START,
        rehearse=args.rehearse)
    config = ctx.config
    if args.layers:
        config["num_hidden_layers"] = args.layers
    config["driver_args"]["engine"]["quantize"] = "int8"
    try:
        _, engine, _, checks = manifest.driver(config["driver"]).bring_up(ctx)
    except common.Incorrect as refused:
        print(json.dumps({"refused": str(refused), "device": dev}))
        return 0
    engine.close()
    print(json.dumps({"passed_degraded": checks, "device": dev}))
    return 1


if __name__ == "__main__":
    sys.exit(main())
