"""Of the window's admissions (slots acquired: Δ``prefix_hits`` +
Δ``prefix_misses`` of ``engine.stats()``), the share whose prefill went
out in a program that carried TWO of them (the engine's paired prefill,
`core.py` ``_partner``: two waiting prompts, each at the only chunk of
its plan, twice the larger of their two buckets within `_PAIR_ROWS`):
100 x 2 x Δ``prefill_pairs`` / Δ admissions. 0 where no two short
prompts ever wait together or every prompt is past the rule; a closed
loop that admits 2 to 3 a tick with half its prompts under the rule
reads a third. A program without the counter reads nothing."""

from benchmark.harness import counters


def read(run):
    pairs = counters.delta(run, "prefill_pairs")
    hits = counters.delta(run, "prefix_hits")
    misses = counters.delta(run, "prefix_misses")
    if pairs is None or hits is None or misses is None:
        return None
    if not hits + misses:
        return None
    return 2 * pairs / (hits + misses) * 100.0
