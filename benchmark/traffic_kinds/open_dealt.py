"""Kind ``open_dealt``: an open loop whose arrivals are REGULARISED.

The requests are sent on a schedule whatever the server does, at
``rate_rps``. The inter-arrival gaps are the mid-quantiles of the
exponential distribution (a Poisson process's gaps), but their order is
dealt: every ``deal_block`` consecutive gaps hold one from each
``deal_block``-th of the distribution, and so do the prompt and answer
lengths. Every few seconds of every run therefore offer the same work:
there are short gaps and long prompts, but no long run of either — no
burst such as a true Poisson process (or real traffic, which is
burstier still) produces. That is the price of a tail that can carry a
bound of at most 10 %: with a free order the 90th percentile of TTFT
over six seeds spread by 19 % (PERF.md, PR 24). Bursty arrivals are a
cell of their own (``mistral7b.chat.burst``, PERF.md section 7).
"""

import numpy as np

from benchmark.harness import loadgen, traffic


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             max_total: int) -> list:
    """The requests of one run in order of their due time, the lead-in's
    (due before 0) first."""
    rng = np.random.default_rng(seed)
    rate = mix["rate_rps"]
    n = max(1, round(rate * (mix["lead_in_s"] + seconds)))
    gaps = traffic.dealt(traffic.exponential_gaps(rate, n), rng,
                         mix["deal_block"])
    # Half a mean gap before the first, so the last falls inside.
    due = np.cumsum(gaps) - 0.5 / rate - mix["lead_in_s"]
    return traffic.sized(mix, rng, due, vocab, max_total)


def drive(stream_fn, reqs, mix, *, seconds, vocab, t0, on_window_end):
    return loadgen.open_loop(stream_fn, reqs, seconds=seconds,
                             drain_s=mix["drain_s"], vocab=vocab, t0=t0,
                             on_window_end=on_window_end)
