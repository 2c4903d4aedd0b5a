"""Mean milliseconds an admission's prefill programs sat on the device
BEHIND other programs (the decode chunk in flight, other arrivals'
prefills) before they ran: Δ``prefill_behind_s`` / Δ``prefill_split``
of ``engine.stats()``, over the admissions whose every prefill program
had its start and end seen by a fetch that waited (the engine's
``DeviceQueue``; Δ``prefill_split`` against Δ``requests`` is how many
those are). With ``engine_prefill_own_ms`` it splits
``engine_prefill_wait_ms``. A program without the counters reads
nothing."""

from benchmark.harness import counters


def read(run):
    return counters.mean_ms(run, "prefill_behind_s", "prefill_split")
