"""90th percentile over the timed requests of
(last token - first token) / (tokens - 1): the tail, judged end to
end."""

from benchmark.harness import stats


def read(run):
    return stats.tpot_percentile(run, 90)
