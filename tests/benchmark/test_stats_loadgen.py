"""Percentile and due-time arithmetic, and the two loops against a fake
stream with known timing."""

import time

import numpy as np
import pytest

from benchmark.harness import loadgen, stats
from benchmark.harness.traffic import Request


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
def test_percentile_is_numpys_linear_interpolation(q):
    rng = np.random.default_rng(q)
    for n in (1, 2, 7, 200):
        v = list(rng.normal(size=n))
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))
    with pytest.raises(ValueError):
        stats.percentile([], q)


def test_request_arithmetic_counts_from_the_due_time():
    rec = {"due": 1.0, "sent": 1.25, "first": 1.5, "last": 2.5, "n_got": 11}
    assert stats.ttft_ms(rec) == pytest.approx(500.0)   # not 250: from due
    assert stats.late_ms(rec) == pytest.approx(250.0)
    assert stats.tpot_ms(rec) == pytest.approx(100.0)
    assert stats.tpot_ms(dict(rec, n_got=1)) is None
    run = {"window_s": 2.0, "requests": [
        {"token_times": [-0.5, 0.0, 1.9, 2.0, 2.5]}, {"token_times": [1.0]}]}
    assert stats.tokens_in_window(run) == 3


def _fake_stream(delay_first, gap):
    def stream(request):
        def gen():
            time.sleep(delay_first)
            for i in range(request["max_new_tokens"]):
                if i:
                    time.sleep(gap)
                yield 7
        return gen()
    return stream


def test_open_loop_times_requests_due_in_the_window():
    reqs = [Request(due, (1, 2, 3), 3) for due in (-0.2, 0.0, 0.1, 0.2, 0.35)]
    t0 = time.perf_counter() + 0.25
    recs = loadgen.open_loop(_fake_stream(0.05, 0.01), reqs, seconds=0.3,
                             drain_s=5.0, vocab=10, t0=t0)
    assert [r["timed"] for r in recs] == [False, True, True, True, False]
    for r in recs:
        assert r["done"] and r["n_got"] == 3 and not r["bad_ids"]
        assert 0.0 <= r["sent"] - r["due"] < 0.5     # never early
        assert 0.05 <= r["first"] - r["sent"] < 1.0
        assert r["last"] >= r["first"] and len(r["token_times"]) == 3


def test_open_loop_counts_what_runs_past_the_drain_as_failed():
    t0 = time.perf_counter()
    recs = loadgen.open_loop(_fake_stream(5.0, 0.0), [Request(0.0, (1,), 2)],
                             seconds=0.05, drain_s=0.1, vocab=10, t0=t0)
    assert recs[0]["error"] == "ran past the drain" and not recs[0]["done"]
    assert recs[0]["first"] == pytest.approx(0.15, abs=0.05)


def test_open_loop_counts_ids_out_of_range_and_errors():
    def bad(request):
        raise RuntimeError("refused")
    t0 = time.perf_counter()
    recs = loadgen.open_loop(bad, [Request(0.0, (1,), 2)], seconds=0.05,
                             drain_s=0.5, vocab=10, t0=t0)
    assert "refused" in recs[0]["error"]
    recs = loadgen.open_loop(_fake_stream(0, 0), [Request(0.0, (1,), 2)],
                             seconds=0.05, drain_s=0.5, vocab=5,
                             t0=time.perf_counter())
    assert recs[0]["bad_ids"] == 2


def test_closed_loop_keeps_its_clients_busy_and_cuts_the_rest():
    pool = [Request(0.0, (1,), 2)] * 5
    t0 = time.perf_counter() + 0.1           # lead-in
    recs = loadgen.closed_loop(_fake_stream(0.02, 0.01), pool, clients=3,
                               seconds=0.3, vocab=10, t0=t0)
    timed = [r for r in recs if r["timed"]]
    # 3 clients x 0.3 s / 0.03 s a request = about 30 come back inside
    # the window; those that came back in the lead-in or were cut at
    # the end do not count.
    assert 5 <= len(timed) <= 32        # fewer on a loaded machine
    assert all(0 <= r["last"] < 0.3 and r["done"] for r in timed)
    assert any(not r["timed"] and r["last"] < 0 for r in recs)
