"""95th percentile of send time minus due time: whether the load
generator kept up (a starved generator reads as a fast server)."""

from benchmark.harness import stats


def read(run):
    return stats.percentile([stats.late_ms(r) for r in stats.timed(run)], 95)
