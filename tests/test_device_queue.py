"""`DeviceQueue` (`serve/engine/metrics.py`) under hand-made stamps: no
engine, no jax, no sleep. A program's ``start`` is the end of the one
ahead of it where a fetch that waited saw that end; what no fetch saw
stays unknown and splits nothing; the device's dry time is exact after
a fetch that waited and a lower bound after one that found its result
ready."""

from __future__ import annotations

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.serve.engine.metrics import DeviceQueue, EngineMetrics, TickClock
from ray_tpu.util import tracing


class _Annotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        pass

    def __exit__(self, *exc):
        pass


def _never():
    raise AssertionError("nobody asked for a poll")


@pytest.fixture
def q():
    m = EngineMetrics("queue-test")
    queue = DeviceQueue(m, TickClock(m, _Annotation))
    queue.m = m.device_queue            # the counters, for the asserts
    return queue


def _seen(q, program, t_end, now=None):
    """The tick's fetch of ``program`` returned at ``t_end`` (None: it
    found the result ready, by ``now``)."""
    q.fetched(t_end)
    return q.seen(program, t_end if now is None else now)


def test_programs_run_in_dispatch_order_and_behind_plus_own_is_the_wait(q):
    a = q.put("chunk", 10.0, _never)
    b = q.put("prefill", 11.0, _never)
    c = q.put("chunk", 12.0, _never)
    assert a.start == 10.0 and b.start is None and c.start is None
    assert _seen(q, a, 15.0) == (0.0, 5.0)
    assert b.start == 15.0              # where the program ahead ended
    behind, own = _seen(q, b, 18.0)
    assert (behind, own) == (4.0, 3.0)
    assert behind + own == 18.0 - b.t_dispatch
    assert _seen(q, c, 23.5) == (6.0, 5.5)
    assert [p.open for p in (a, b, c)] == [False] * 3


def test_a_program_dispatched_after_the_one_ahead_ended_starts_at_its_own(q):
    a = q.put("chunk", 10.0, _never)
    b = q.put("chunk", 16.0, _never)    # (a's end is not seen yet)
    _seen(q, a, 15.0)
    assert b.start == 16.0              # max(t_dispatch, end ahead)
    assert _seen(q, b, 20.0) == (0.0, 4.0)


@pytest.mark.parametrize("unfetched", [1, 3])
def test_an_end_seen_closes_what_is_ahead_and_splits_none_of_it(q, unfetched):
    """Prefill chunks that are not their admission's last (and a
    trailing chunk dropped) are fetched by nobody: the next fetch closes
    them, end unseen, and so the start of the program behind them."""
    first = q.put("chunk", 1.0, _never)
    ahead = [q.put("prefill", 2.0 + i, _never) for i in range(unfetched)]
    final = q.put("prefill", 8.0, _never)
    behind = q.put("chunk", 9.0, _never)
    _seen(q, first, 5.0)
    assert ahead[0].start == 5.0
    assert _seen(q, final, 20.0) is None        # its start was never seen
    assert all(p.split() is None and not p.open for p in ahead + [final])
    assert final.end == 20.0 and behind.start == 20.0
    assert q.m["prefill_split"] == 0 == q.m["device_dry_dispatches"]
    assert _seen(q, behind, 26.0) == (11.0, 6.0)
    # A program closed by a later one's end: its own fetch finds it so.
    assert q.seen(ahead[0], 27.0) is None and not q._open


def test_a_fetch_that_found_its_result_ready_leaves_both_stamps_unknown(q):
    a = q.put("chunk", 10.0, _never)
    b = q.put("chunk", 11.0, _never)
    assert _seen(q, a, None, now=15.0) is None      # ended by 15, not at
    assert a.end is None and b.start is None
    assert _seen(q, b, 19.0) is None                # end seen, start not
    assert b.end == 19.0 and not q.chunk_owns


@pytest.mark.parametrize("t_end, now, dry", [
    (15.0, 15.2, 2.0),      # a fetch that waited: the end, exactly
    (None, 16.0, 1.0),      # found ready at 16: dry since 16 at least
], ids=["exact_after_a_blocked_fetch", "a_lower_bound_after_a_ready_one"])
def test_a_dispatch_to_a_queue_known_empty_counts_the_dry_time(q, t_end, now,
                                                               dry):
    a = q.put("chunk", 10.0, _never)    # the first: nothing known before
    assert q.m["device_dry_dispatches"] == 0 and q.m["device_dry_s"] == 0.0
    _seen(q, a, t_end, now)
    b = q.put("prefill", 17.0, _never)
    assert b.start == 17.0              # a dry device starts it at once
    assert q.m["device_dry_dispatches"] == 1
    assert q.m["device_dry_s"] == pytest.approx(dry)
    # ... and a program behind one still open counts nothing.
    q.put("chunk", 17.5, _never)
    assert q.m["device_dry_dispatches"] == 1


@pytest.mark.parametrize("left", [True, False])
def test_a_poll_after_the_listening_wait_finds_the_device_dry_or_not(q, left):
    """The pipelined case: chunk N+1 goes out after the wait; asked
    then, chunk N has left the device already (or not). Dry since the
    wait last found it busy, at most."""
    a = q.put("chunk", 10.0, lambda: left)
    q.busy_at(10.5)
    b = q.put("chunk", 10.6, _never, poll=True)
    assert q.m["device_dry_dispatches"] == int(left)
    assert q.m["device_dry_s"] == pytest.approx(0.1 if left else 0.0)
    assert a.open is not left and b.start == (10.6 if left else None)
    # The retire of chunk N still comes: it has nothing left to close.
    got = _seen(q, a, None, now=11.0) if left else _seen(q, a, 11.0)
    assert got == (None if left else (0.0, 1.0))
    assert b.open and q.ahead() == (1, 0)


def test_nobody_is_polled_unless_the_tick_listened(q):
    """(`_never` raises if asked.)"""
    q.put("chunk", 10.0, _never)
    q.put("chunk", 10.6, _never)                    # poll=False: no probe
    assert q.ahead() == (2, 0)
    q2 = DeviceQueue(EngineMetrics("e"), q._clock)
    q2.put("chunk", 10.0, _never, poll=True)        # nothing ahead to ask
    assert q2.ahead() == (1, 0)


def test_ahead_counts_the_open_programs_by_kind(q):
    assert q.ahead() == (0, 0)
    a = q.put("chunk", 1.0, _never)
    q.put("prefill", 2.0, _never)
    q.put("prefill", 3.0, _never)
    assert q.ahead() == (1, 2)
    _seen(q, a, 4.0)
    assert q.ahead() == (0, 2)


def test_only_the_last_few_timed_chunks_are_kept_for_the_deadline(q):
    t = 0.0
    for i in range(12):
        kind = "chunk" if i % 3 else "prefill"
        p = q.put(kind, t, _never)
        t += 1.0 + i / 100
        _seen(q, p, t)
    assert len(q.chunk_owns) == q.chunk_owns.maxlen == 8
    assert list(q.chunk_owns) == pytest.approx(
        [1.0 + i / 100 for i in range(12) if i % 3])


def test_a_closed_program_lets_go_of_its_probe(q):
    """A chunk's probe holds its record (device arrays among it)."""
    a = q.put("chunk", 1.0, lambda: False)
    _seen(q, a, 2.0)
    assert a.done is None


@pytest.fixture
def sink():
    spans = []
    tracing.flush()
    tracing.set_sink(spans.extend)
    old = cfg.get("tracing_enabled")
    yield spans
    cfg.set("tracing_enabled", old)
    tracing.set_sink(None)


@pytest.mark.parametrize("traced", [True, False])
def test_a_split_program_is_one_span_outside_engine_dot(q, sink, traced):
    cfg.set("tracing_enabled", traced)
    a = q.put("chunk", 10.0, _never, slots=3)
    b = q.put("prefill", 11.0, _never, tokens=220, bucket=256)
    c = q.put("chunk", 12.0, _never, slots=4)
    _seen(q, a, 15.0)
    _seen(q, b, None, now=18.0)         # no end: no span
    _seen(q, c, 25.0)                   # no start: no span
    d = q.put("prefill", 30.0, _never, tokens=17, bucket=32)
    _seen(q, d, 31.0)
    cfg.set("tracing_enabled", False)
    tracing.flush()
    if not traced:
        assert sink == []
        return
    root, *spans = sink
    assert root["name"] == "serve.engine" and root["parent_id"] == ""
    assert [s["name"] for s in spans] == ["device.chunk", "device.prefill"]
    assert all(s["parent_id"] == root["span_id"] for s in spans)
    assert spans[0]["attrs"] == {"slots": 3, "behind_s": 0.0, "own_s": 5.0}
    assert spans[1]["attrs"] == {"tokens": 17, "bucket": 32,
                                 "behind_s": 0.0, "own_s": 1.0}
    # On the offset every span of the process shares.
    assert spans[0]["start"] == tracing.wall(10.0)
    assert spans[0]["end"] == tracing.wall(15.0)
    assert not any(s["name"].startswith("engine.") for s in sink)
