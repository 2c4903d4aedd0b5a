"""90th percentile, over the timed requests of the WHOLE window (216 at
4.8 requests/s), of first streamed token received minus the time the
request was DUE: the tail a client sees. Recorded, not judged: over 12
runs its spread was 6 to 7 % (4.7 % by the driver's trimmed mean),
which a bound of at most 10 % cannot carry; the median ``ttft_p50_ms``
is judged (PERF.md section 2)."""

from benchmark.harness import stats


def read(run):
    return stats.ttft_percentile(run, 90)
