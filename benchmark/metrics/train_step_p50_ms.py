"""Median seconds of one step: the steadier statistic beside
``train_tok_s``."""

from benchmark.harness import stats


def read(run):
    return stats.percentile(run["steps"], 50) * 1e3
