"""Of the window's admissions (slots acquired: Δ``prefix_hits`` +
Δ``prefix_misses`` of ``engine.stats()``), the share made into a slot
whose last holder was still in the decode chunk in flight (the engine's
early hand-over, `core.py` ``_hand_over``: the holder's budget or row
cap ended inside that chunk, somebody waited and no slot was free):
100 x Δ``admissions_ahead`` / Δ admissions. Near 100 in a closed loop
whose every finish is the budget's; 0 where a slot is always free or
every finish is an EOS. A program without the counter reads nothing."""

from benchmark.harness import counters


def read(run):
    ahead = counters.delta(run, "admissions_ahead")
    hits = counters.delta(run, "prefix_hits")
    misses = counters.delta(run, "prefix_misses")
    if ahead is None or hits is None or misses is None:
        return None
    if not hits + misses:
        return None
    return ahead / (hits + misses) * 100.0
