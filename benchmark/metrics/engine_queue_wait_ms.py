"""Mean milliseconds a request waited inside the engine before its
first prefill chunk was dispatched, over the requests that reached
their first token in the window: Δ``queue_wait_s`` / Δ``requests`` of
``engine.stats()`` (arrival in ``_make_request`` to the stamp that
opens the first ``engine.tick.prefill_dispatch``). With
``engine_prefill_wait_ms`` it sums to the engine's own mean TTFT."""

from benchmark.harness import counters


def read(run):
    return counters.mean_ms(run, "queue_wait_s", "requests")
