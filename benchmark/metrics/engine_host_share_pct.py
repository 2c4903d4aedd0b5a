"""Of the time the engine thread had work, the share it spent outside
its two fetches: 100 x (loop − idle − decode_fetch − prefill_fetch) /
(loop − idle), from the ``tick_*_s`` counters of ``engine.stats()`` over
the window. The fetches are where the thread is MEANT to wait for the
device; this is everything else — admission, padding, dispatch,
delivery. It is host work only where a dispatch returns at once. On
the v5e it does not yet (PERF.md section 5, PR 25): with the cache not
donated a dispatch blocks until the chunk in flight has ended, so most
of this share is the thread waiting for the device inside
``prefill_dispatch`` and ``decode_dispatch``. Read it beside
``device_idle_pct``: a high share with a busy device is a blocked
dispatch, a high share with an idle device is a slow host."""

from benchmark.harness import counters


def read(run):
    loop, idle, decode_fetch, prefill_fetch = (
        counters.delta(run, f"tick_{k}_s")
        for k in ("loop", "idle", "decode_fetch", "prefill_fetch"))
    if None in (loop, idle, decode_fetch, prefill_fetch) or loop <= idle:
        return None
    busy = loop - idle
    return (busy - decode_fetch - prefill_fetch) / busy * 100.0
