"""Median, over the window's timed requests, of first streamed token
received minus the time the request was DUE. Judged end to end in
place of the tail ``ttft_p90_ms``, which is recorded beside it (see
there for why)."""

from benchmark.harness import stats


def read(run):
    return stats.ttft_percentile(run, 50)
