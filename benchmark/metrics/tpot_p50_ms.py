"""Median over the timed requests of
(last token - first token) / (tokens - 1): the steadier statistic that
stands beside the judged tail ``tpot_p90_ms``."""

from benchmark.harness import stats


def read(run):
    return stats.tpot_percentile(run, 50)
