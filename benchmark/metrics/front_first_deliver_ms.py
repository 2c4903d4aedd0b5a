"""Mean milliseconds a stream's first token lay on the stream queue
before the consumer's thread took it: Δ``first_deliver_s`` / Δ``streams``
of ``engine.stats()``, written in ``generate_stream``. The serve
front's own share of TTFT, measured inside the layer."""

from benchmark.harness import counters


def read(run):
    return counters.mean_ms(run, "first_deliver_s", "streams")
