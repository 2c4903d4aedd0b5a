"""The traffic kinds: pure functions of the seed that reproduce their
stated distributions, each found as a file by the mix's ``kind``."""

import json

import numpy as np
import pytest

from benchmark.harness import manifest, traffic

MIXES = manifest.BENCH_DIR / "traffic"
M = manifest.load()


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _requests(mix, *args):
    return M.kind(mix).requests(mix, *args)


def _batch(mix, *args):
    return M.kind(mix).batch(mix, *args)


def test_same_seed_same_requests_other_seed_another_order():
    mix = _mix("chat.steady")
    a = _requests(mix, 2**31 + 11, 20.0, 32768, 1016)
    b = _requests(mix, 2**31 + 11, 20.0, 32768, 1016)
    c = _requests(mix, 12, 20.0, 32768, 1016)
    assert a == b
    assert a != c
    assert [r.answer_len for r in a] != [r.answer_len for r in c]
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


# The cell's own sizes: 45 s, a 32768-word vocabulary, 1016 rows a slot.
WINDOW_S, ROWS = 45.0, 1016
SEEDS = [2147483659, 2147483693, 1234567891, 19, 2**31 + 2**20, 0]


def _stretches(mix, seed):
    """(lead-in, window) of one seed's run, each as sorted gaps, sorted
    answer lengths and sorted prompt lengths; a request's gap is the
    time from its due time to the next one's (the stretch's end for the
    last)."""
    reqs = _requests(mix, seed, WINDOW_S, 32768, ROWS)
    out = []
    for lo, hi in ((-mix["lead_in_s"], 0.0), (0.0, WINDOW_S)):
        part = [r for r in reqs if lo <= r.due_s < hi]
        due = np.array([r.due_s for r in part] + [hi])
        out.append({"n": len(part), "gaps": np.sort(np.diff(due)),
                    "answers": sorted(r.answer_len for r in part),
                    "prompts": sorted(len(r.prompt_ids) for r in part),
                    "last_due": part[-1].due_s, "requests": part})
    assert out[0]["n"] + out[1]["n"] == len(reqs)
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_offers_the_window_the_same_work(seed):
    """The steady cell's sizes: whatever the seed, the window times
    exactly ``round(rate * seconds)`` requests whose gaps and answer
    lengths are the mid-quantiles of their distributions, and whose
    prompt lengths are too, up to the ``max_total`` cut (a pair's: a
    prompt over 760 that meets an answer over 248); the lead-in
    likewise, with quantiles of its own."""
    mix = _mix("chat.steady")
    for got, (stretch, seconds) in zip(_stretches(mix, seed), (
            ("lead-in", mix["lead_in_s"]), ("window", WINDOW_S))):
        n = round(mix["rate_rps"] * seconds)
        assert got["n"] == n, stretch
        np.testing.assert_allclose(
            got["gaps"], traffic.exponential_gaps(n / seconds, n), atol=1e-9)
        assert got["gaps"].min() > 0
        assert got["answers"] == list(
            traffic.stratified_lengths(mix["answer_len"], n)), stretch
        uncut = traffic.stratified_lengths(mix["prompt_len"], n)
        cut = uncut - np.array(got["prompts"])
        assert (cut >= 0).all() and (cut > 0).sum() <= 8, stretch
        assert cut.sum() <= 1e-3 * uncut.sum(), stretch
    assert got["last_due"] < WINDOW_S
    assert got["requests"][0].due_s == 0.0
    assert _stretches(mix, seed)[0]["requests"][0].due_s == -mix["lead_in_s"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_window_is_dealt_in_blocks_of_its_own(seed):
    """Inside the window every 8 consecutive requests hold one answer
    length, one prompt length and one gap from each eighth of the
    WINDOW's own distribution (the lead-in has its own)."""
    mix = _mix("chat.steady")
    window = _stretches(mix, seed)[1]
    reqs, n = window["requests"], window["n"]
    due = np.array([r.due_s for r in reqs] + [WINDOW_S])
    uncut = traffic.stratified_lengths(mix["prompt_len"], n)
    for got, ascending in (
            (np.array([r.answer_len for r in reqs]),
             traffic.stratified_lengths(mix["answer_len"], n)),
            (np.diff(due), np.sort(np.diff(due)))):
        edges = ascending[np.arange(1, 8) * n // 8]
        for run in got[:n - n % 8].reshape(-1, 8):
            counts = np.bincount(np.searchsorted(edges, run, side="right"),
                                 minlength=8)
            assert counts.max() <= 2          # ties at an edge may share
    # The cut only ever shortens, and by little.
    prompts = np.sort([len(r.prompt_ids) for r in reqs])
    assert (prompts <= uncut).all() and (uncut - prompts).sum() <= 64


@pytest.mark.parametrize("name", ["chat.steady", "chat.flood"])
def test_lengths_follow_the_stated_lognormal(name):
    mix = _mix(name)
    for key in ("prompt_len", "answer_len"):
        d = mix[key]
        n = traffic.stratified_lengths(d, 2000)
        assert n.min() >= d["min"] and n.max() <= d["max"]
        assert abs(np.median(n) - d["median"]) <= 1
        inside = n[(n > d["min"]) & (n < d["max"])]
        # log-lengths of the unclipped part: the stated sigma, cut by
        # the clipping (so a little under it).
        assert 0.6 * d["sigma"] < np.std(np.log(inside)) <= d["sigma"] * 1.02


def test_open_loop_rate_window_and_fit():
    mix = _mix("chat.steady")
    seconds, rows = 30.0, 1016
    reqs = _requests(mix, 7, seconds, 32768, rows)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == (round(mix["rate_rps"] * mix["lead_in_s"])
                         + round(mix["rate_rps"] * seconds))
    assert due.min() == -mix["lead_in_s"]
    assert due.max() < seconds
    assert ((due >= 0) & (due < seconds)).sum() \
        == round(mix["rate_rps"] * seconds)
    # Exponential gaps: mean 1/rate, coefficient of variation near 1.
    gaps = np.diff(due)
    assert abs(gaps.mean() * mix["rate_rps"] - 1) < 0.02
    assert 0.85 < gaps.std() / gaps.mean() < 1.05
    for r in reqs:
        assert len(r.prompt_ids) + r.answer_len <= rows
        assert all(1 <= t < 32768 for t in r.prompt_ids)


def test_every_stretch_of_a_run_offers_the_same_work():
    """Dealt order: any 8 consecutive requests hold one length from
    each eighth of the distribution, whatever the seed."""
    mix = _mix("chat.flood")
    assert mix["deal_block"] == 8
    want = np.sort(traffic.stratified_lengths(mix["answer_len"],
                                              mix["pool"]))
    edges = want[np.arange(1, 8) * mix["pool"] // 8]
    means = []
    for seed in (1, 2**31 + 99):
        got = np.array([r.answer_len for r in
                        _requests(mix, seed, 10.0, 1000, 1016)])
        assert (np.sort(got) == want).all()
        for run in got.reshape(-1, 8):
            counts = np.bincount(np.searchsorted(edges, run, side="right"),
                                 minlength=8)
            assert counts.max() <= 2          # ties at an edge may share
        means.append(got.reshape(-1, 8).mean(axis=1))
    assert np.std(means) / np.mean(means) < 0.15
    span = _requests(_mix("chat.steady"), 5, 45.0, 1000, 1016)
    due = np.array([r.due_s for r in span])
    per_block = np.diff(due[::8])
    # A free order gives 1 / sqrt(8) = 0.35; the longest gaps keep it wide.
    assert per_block.std() / per_block.mean() < 0.33


def test_closed_pool_and_too_small_a_slot():
    mix = _mix("chat.flood")
    reqs = _requests(mix, 3, 10.0, 1000, 1016)
    assert len(reqs) == mix["pool"]
    with pytest.raises(ValueError):
        _requests(mix, 3, 10.0, 1000, mix["answer_len"]["max"])
    # A training kind makes batches, not requests.
    assert not hasattr(M.kind(_mix("sft.fsdp2tp2")), "requests")


def test_train_batches_differ_by_step_and_seed_only():
    mix = _mix("sft.fsdp2tp2")
    a = _batch(mix, 2**31 + 5, 3, 49152)
    assert a.shape == (mix["batch"], mix["seq"]) and a.dtype == np.int32
    assert (a == _batch(mix, 2**31 + 5, 3, 49152)).all()
    assert (a != _batch(mix, 2**31 + 5, 4, 49152)).any()
    assert (a != _batch(mix, 6, 3, 49152)).any()
    assert 0 <= a.min() and a.max() < 49152
