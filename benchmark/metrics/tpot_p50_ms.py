"""Median over the timed requests of
(last token - first token) / (tokens - 1), judged end to end; the
tail stands beside it as ``tpot_tail_p90_ms``, recorded."""

from benchmark.harness import stats


def read(run):
    return stats.tpot_percentile(run, 50)
