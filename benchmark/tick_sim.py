"""What a closed-loop cell's ``serve_tok_s`` would spread by over seeds
if the programs took fixed times: the engine's tick replayed on the CPU
from the cell's traffic file and engine sizes alone, no chip, no JAX.

    python3 benchmark/tick_sim.py --workload <cell> --ms-per-ktok 68.5 --chunk-ms 128.5 [--seconds 45] [--seeds 60]

A tick is one chunk of every prefill under way (``ms-per-ktok`` x the
chunk's bucket, a little more for each chunk already behind it:
``--grow``), then one decode chunk of ``decode_chunk`` steps for every
slot that answers (``chunk-ms``). The loop is the traffic kind
``closed``: the lengths dealt as `harness/traffic.py` deals them, a
client's next request sent when its last returns, ``lead_in_s`` before
the window. Prints the mean of the window's tokens a second over the
seeds, its standard deviation, the spread (`spread.py`'s) of sets of
six seeds drawn from them, and how often two such sets both stay under
``--admit`` (half the bound of ``serve_tok_s``). PERF.md (PR 35) holds
it against the chip: the chip's six-seed spread lies in what this
draws, so the spread is the traffic's, not the program's.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import spread as spread_of  # noqa: E402
from benchmark.harness import traffic  # noqa: E402


def requests(mix: dict, max_len: int, seed: int):
    """The pool's (prompt, answer) lengths as the seed deals them."""
    rng = np.random.default_rng(seed)
    n, block = mix["pool"], mix["deal_block"]
    answers = traffic.dealt(
        traffic.stratified_lengths(mix["answer_len"], n), rng, block)
    prompts = traffic.dealt(
        traffic.stratified_lengths(mix["prompt_len"], n), rng, block)
    return list(zip(np.minimum(prompts, max_len - answers).tolist(),
                    answers.tolist()))


def plan(n: int, chunk: int, buckets) -> list:
    """The buckets of a prompt's prefill chunks (`scheduler.prefill_plan`)."""
    out = []
    while chunk and n > chunk:
        out.append(chunk)
        n -= chunk
    return out + [min(b for b in buckets if b >= n)]


def window_tok_s(mix, eng, seed, *, ms_per_ktok, chunk_ms, grow, window):
    pool = requests(mix, eng["max_len"], seed)
    cursor, waiting = mix["clients"], list(pool[:mix["clients"]])
    t, free, tokens = -float(mix["lead_in_s"]), eng["max_batch"], 0
    prefilling, answering = [], []
    while t < window:
        while waiting and free:
            prompt, answer = waiting.pop(0)
            free -= 1
            prefilling.append([plan(prompt, eng.get("prefill_chunk", 0),
                                    eng["prompt_buckets"]), 0, answer])
        for job in list(prefilling):
            t += job[0][job[1]] * ms_per_ktok / 1e6 * (1 + grow * job[1])
            job[1] += 1
            if job[1] == len(job[0]):
                prefilling.remove(job)
                tokens += 0 <= t < window
                answering.append(job[2] - 1)
        if answering:
            t += chunk_ms / 1e3
            still = []
            for left in answering:
                n = min(eng["decode_chunk"], left)
                tokens += n * (0 <= t < window)
                if left - n > 0:
                    still.append(left - n)
                else:
                    free += 1
                    waiting.append(pool[cursor % len(pool)])
                    cursor += 1
            answering = still
        elif not prefilling:
            t += 0.001
    return tokens / window


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ms-per-ktok", type=float, required=True)
    ap.add_argument("--chunk-ms", type=float, required=True)
    ap.add_argument("--grow", type=float, default=0.012)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, default=60)
    ap.add_argument("--admit", type=float, default=0.05)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        eng = json.load(f)["driver_args"]["engine"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    values = [window_tok_s(mix, eng, 1000 + s, ms_per_ktok=args.ms_per_ktok,
                           chunk_ms=args.chunk_ms, grow=args.grow,
                           window=args.seconds) for s in range(args.seeds)]
    draw = random.Random(1)
    sets = [(spread_of.spread(draw.sample(values, 6)),
             spread_of.spread(draw.sample(values, 6))) for _ in range(2000)]
    singles = sorted(a for a, _ in sets)
    print(json.dumps({
        "workload": args.workload, "seconds": args.seconds,
        "mean_tok_s": statistics.mean(values),
        "sd_over_mean": statistics.pstdev(values) / statistics.mean(values),
        "six_seed_spread_p10_p50_p90": [singles[200], singles[1000],
                                        singles[1800]],
        "both_sets_under_admit": sum(a < args.admit and b < args.admit
                                     for a, b in sets) / len(sets)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
