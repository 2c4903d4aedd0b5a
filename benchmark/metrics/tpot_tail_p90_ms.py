"""90th percentile over the timed requests of
(last token - first token) / (tokens - 1): the tail of TPOT. Recorded,
not judged: in the driver's two checks of it (PR 27, PR 28) its runs
spread by 0.043 to 0.050 of the median by the driver's statistic,
which no bound up to 10 % carries; the median ``tpot_p50_ms`` is
judged (PERF.md section 2). It was the end-to-end metric
``tpot_p90_ms`` until PR 28."""

from benchmark.harness import stats


def read(run):
    return stats.tpot_percentile(run, 90)
