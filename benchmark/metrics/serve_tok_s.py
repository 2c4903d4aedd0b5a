"""Output tokens received inside the window / the window's seconds."""

from benchmark.harness import stats


def read(run):
    return stats.tokens_in_window(run) / run["window_s"]
