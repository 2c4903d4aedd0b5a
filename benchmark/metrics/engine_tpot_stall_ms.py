"""Milliseconds of a roster member's gap between two tokens that are
NOT its own chunk's device time: the cadence at which chunks retire
(Δ``chunk_period_s`` / Δ``chunk_steps_retired``) less a chunk's device
seconds a step where the engine's ``DeviceQueue`` saw both its ends
(Δ``chunk_own_s`` / Δ``chunk_steps_timed``). What is left is what went
onto the device between two chunks (arrivals' prefills) and the time
the device stood dry. A program without the counters reads nothing."""

from benchmark.harness import counters


def read(run):
    period = counters.mean_ms(run, "chunk_period_s", "chunk_steps_retired")
    own = counters.mean_ms(run, "chunk_own_s", "chunk_steps_timed")
    if period is None or own is None:
        return None
    return period - own
