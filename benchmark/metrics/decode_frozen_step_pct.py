"""Of the slot-steps the decode program ran for a slot live at its
chunk's first step, the share scanned after the slot's request had
ended inside that chunk: 100 x Δ``decode_steps_frozen`` /
Δ``decode_steps`` of ``engine.stats()``. ``engine_occupancy_pct``
counts such a slot whole; this is the loss it cannot see. A program
without the counter reads nothing."""

from benchmark.harness import counters


def read(run):
    frozen = counters.delta(run, "decode_steps_frozen")
    steps = counters.delta(run, "decode_steps")
    if frozen is None or not steps:
        return None
    return frozen / steps * 100.0
