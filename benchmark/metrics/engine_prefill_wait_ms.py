"""Mean milliseconds from a request's first prefill dispatch to its
first token on the host: Δ``prefill_wait_s`` / Δ``requests`` of
``engine.stats()``. It holds the device running the prefill
(``prefill_ms_per_ktok`` says how little that is), whatever the device
had queued before it (a decode chunk already dispatched) and the fetch
of the whole logits."""

from benchmark.harness import counters


def read(run):
    return counters.mean_ms(run, "prefill_wait_s", "requests")
