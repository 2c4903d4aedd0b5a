"""Mean milliseconds an admission's prefill programs took on the device
themselves, from the end of the program ahead (or their dispatch to a
dry device) to the return of the fetch that waited for them:
Δ``prefill_own_s`` / Δ``prefill_split`` of ``engine.stats()``, over the
same admissions as ``engine_prefill_behind_ms``. A program without the
counters reads nothing."""

from benchmark.harness import counters


def read(run):
    return counters.mean_ms(run, "prefill_own_s", "prefill_split")
