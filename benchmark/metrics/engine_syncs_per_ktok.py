"""Device-to-host fetches of the decode loop per 1000 tokens the engine
generated in the window (both exact counts of ``engine.stats()``)."""

from benchmark.harness import stats


def read(run):
    tokens = stats.counter_delta(run, "tokens_generated")
    if not tokens:
        return None
    return stats.counter_delta(run, "decode_host_syncs") / tokens * 1e3
