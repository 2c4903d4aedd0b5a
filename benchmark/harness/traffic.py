"""What the traffic kinds share: the request, lengths at the quantiles
of a stated distribution, exponential gaps, and the dealt order. Pure
functions of a mix file's parameters and a seed: no clock and no JAX.

A traffic KIND is a file of its own, ``benchmark/traffic_kinds/<kind>.py``
(found by the ``kind`` of a mix file, as drivers and metric readers are
found by their names): a serving kind gives ``requests(mix, seed,
seconds, vocab, max_total)`` and ``drive(stream_fn, requests, mix, *,
seconds, vocab, t0, on_window_end)``; a training kind gives
``batch(mix, seed, step, vocab)``. A later PR with arrivals of another
shape (sessions, bursts, packed documents) adds a kind file and edits
nothing here.

Every seed gives the SAME multiset of sizes (and, in the open loop, of
inter-arrival gaps, for the lead-in and for the window each) in another
order, with other token ids: seeds change which requests meet in a
batch, not the work offered.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float             # relative to the window's start; < 0 = lead-in
    prompt_ids: tuple
    answer_len: int


def stratified_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a clipped lognormal, in
    ascending order: ``median * exp(sigma * z)``, rounded, clipped to
    [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    inv = NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def dealt(ascending: np.ndarray, rng, block: int) -> np.ndarray:
    """``ascending`` in an order drawn from ``rng`` in which every
    ``block`` consecutive values hold one from each of ``block`` equal
    slices of the sorted values (the last, short run from some)."""
    strata = [rng.permutation(part)
              for part in np.array_split(np.asarray(ascending), block)]
    out = []
    for j in range(max(len(part) for part in strata)):
        run = np.array([part[j] for part in strata if j < len(part)])
        out.append(rng.permutation(run))
    return np.concatenate(out)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the mid-quantiles of Exp(rate),
    rescaled so that they sum to exactly ``n / rate``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate / gaps.sum())


def sized(mix: dict, rng, due: np.ndarray, vocab: int, max_total: int) -> list:
    """One request for each due time: answer and prompt lengths at the
    quantiles of the mix's distributions, dealt in blocks of the mix's
    ``deal_block``, token ids from ``rng``. ``max_total`` is the most
    rows a slot holds: a prompt is cut so that prompt + answer fits (no
    request may fail by its size)."""
    n, block = len(due), mix["deal_block"]
    answers = dealt(stratified_lengths(mix["answer_len"], n), rng, block)
    prompts = dealt(stratified_lengths(mix["prompt_len"], n), rng, block)
    prompts = np.minimum(prompts, max_total - answers)
    if prompts.min() < 1:
        raise ValueError(f"answers of {answers.max()} leave no prompt "
                         f"in {max_total} rows")
    return [Request(float(due[i]),
                    tuple(int(t) for t in rng.integers(1, vocab, prompts[i])),
                    int(answers[i]))
            for i in range(n)]
