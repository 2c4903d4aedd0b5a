"""Of the engine thread's seconds over the WHOLE window, the share in
which it knew the device dry: 100 x Δ``device_dry_s`` / Δ``tick_loop_s``
of ``engine.stats()``. ``device_dry_s`` grows where a program is
dispatched to a queue the thread has seen run empty, by the time since
it knew (exact after a fetch that waited, a lower bound otherwise; the
engine's ``DeviceQueue``). ``device_idle_pct.steady`` is the trace's
reading of the same over the 5 traced seconds. A program without the
counter reads nothing."""

from benchmark.harness import counters


def read(run):
    dry = counters.delta(run, "device_dry_s")
    loop = counters.delta(run, "tick_loop_s")
    if dry is None or not loop:
        return None
    return dry / loop * 100.0
