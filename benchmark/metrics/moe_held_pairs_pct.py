"""Of the (token, expert) pairs the router chose, the share that fell
on the experts THIS CHIP holds and were multiplied here:
Δ``moe_pairs_held`` / Δ``moe_pairs_routed`` over the window (prefill
and decode, every expert layer). About 12.5 where 32 of 256 experts are
held and the router still ranks all 256; 100 where a program holds
every expert, and a reading far off the held share says that the
router's range and the held range have come apart. A program without
the counters reads nothing."""

from benchmark.harness import counters


def read(run):
    held = counters.delta(run, "moe_pairs_held")
    routed = counters.delta(run, "moe_pairs_routed")
    if held is None or not routed:
        return None
    return held / routed * 100.0
