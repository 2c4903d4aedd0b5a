"""Kind ``open_dealt``: an open loop whose arrivals are REGULARISED, and
whose every seed offers the window the same work.

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

The lead-in and the window are dealt SEPARATELY. The window holds
``round(rate_rps * seconds)`` requests, each due at the start of its
gap, the gaps filling ``[0, seconds)`` exactly; the lead-in holds
``round(rate_rps * lead_in_s)`` of its own before 0. Each stretch has
its own mid-quantiles of gaps and of both lengths, so every seed times
the same number of requests with the same multisets of gaps, prompt
lengths and answer lengths (up to the ``max_total`` cut, which applies
to a pair: one or two requests a window, a few tokens each); a seed
changes their order inside the dealing, the token ids and the weights.
"""

import numpy as np

from benchmark.harness import loadgen, traffic


def _stretch(mix: dict, rng, start: float, length: float, n: int,
             vocab: int, max_total: int) -> list:
    """``n`` requests of their own mid-quantiles, dealt, each due at the
    start of its gap; the gaps fill ``[start, start + length)``."""
    if n == 0:
        return []
    gaps = traffic.dealt(traffic.exponential_gaps(n / length, n), rng,
                         mix["deal_block"])
    due = start + np.concatenate(([0.0], np.cumsum(gaps)[:-1]))
    return traffic.sized(mix, rng, due, vocab, max_total)


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             max_total: int) -> list:
    """The requests of one run in order of their due time: the lead-in's
    (due before 0), then the window's (due in ``[0, seconds)``)."""
    rng = np.random.default_rng(seed)
    rate, lead_in = mix["rate_rps"], mix["lead_in_s"]
    return (_stretch(mix, rng, -lead_in, lead_in, round(rate * lead_in),
                     vocab, max_total)
            + _stretch(mix, rng, 0.0, seconds, max(1, round(rate * seconds)),
                       vocab, max_total))


def drive(stream_fn, reqs, mix, *, seconds, vocab, t0, on_window_end):
    return loadgen.open_loop(stream_fn, reqs, seconds=seconds,
                             drain_s=mix["drain_s"], vocab=vocab, t0=t0,
                             on_window_end=on_window_end)
