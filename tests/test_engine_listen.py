"""The tick's listening wait (`core.py` ``_listen``): while a chunk is
in flight and an arrival could be admitted at once, the engine thread
waits on its mailbox, not in that chunk's fetch, and puts the next
chunk on the device late.

On the CPU's tiny engine a chunk ends in a millisecond or so, about
what its dispatch takes: the deadline the tick derives has passed before
the wait could begin. These tests STEER it (the chunk in flight reads as
running, its end as so-and-so far away) and prove the order of the
loop's calls and the bookkeeping; nothing here sleeps in the wait or
proves speed.
"""

from __future__ import annotations

import concurrent.futures
import random
import threading
import time

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.serve.engine.metrics import TICK_PHASES, EngineMetrics
from ray_tpu.util import tracing

ENGINE_KW = {"max_batch": 2, "max_len": 1024, "prompt_buckets": [8, 16],
             "decode_chunk": 2}
PHASE_KEYS = [f"tick_{p}_s" for p in TICK_PHASES]


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(**{**ENGINE_KW, **kw})


@pytest.fixture
def engine():
    """An engine that listens only where a test steers it."""
    eng = _engine()
    eng._listen_deadline = lambda rec: None
    yield eng
    eng.close()


def _steer(eng, listen_s, done=lambda rec: False):
    """The chunk in flight reads as running until ``done`` says
    otherwise, and its end as ``listen_s`` away."""
    eng._chunk_done = done
    eng._listen_deadline = lambda rec: time.perf_counter() + listen_s


def _log_the_loop(eng):
    """The order of the loop's calls, as a list of names."""
    log = []

    def logged(owner, attr, name):
        inner = getattr(owner, attr)

        def call(*args, **kwargs):
            log.append(name)
            return inner(*args, **kwargs)

        setattr(owner, attr, call)

    logged(eng, "_admit", "admit")
    logged(eng.loop, "prefill_inplace", "prefill")
    logged(eng.loop, "decode_chunk", "chunk")
    logged(eng, "_retire_chunk", "retire")
    logged(eng, "_land_prefill", "land")
    logged(eng._queue, "put", "submit")
    return log


def _decoding(eng):
    """One stream a few chunks in, and hundreds from its end (no test
    waits for it: closing the engine ends it): a chunk is in flight for
    as long as the test runs, and a slot is free."""
    stream = eng.generate_stream([3, 1, 4, 1, 5], max_new_tokens=1000)
    for _ in range(5):
        assert isinstance(next(stream), int)
    return stream


def _when_listening(eng):
    """An event set whenever the thread begins to wait on its mailbox
    (an arrival sent off then is heard, not found at the top of a
    tick)."""
    listening = threading.Event()
    get = eng._queue.get

    def heard_get(*args, **kwargs):
        if kwargs.get("timeout"):       # the wait's, not a get_nowait
            listening.set()
        return get(*args, **kwargs)

    eng._queue.get = heard_get
    return listening


def test_an_arrival_is_prefilled_behind_the_chunk_in_flight(engine):
    """(a) Heard in the wait, its prefill goes out BEFORE the next
    decode chunk with no top of a tick between, and lands after the
    retire of the chunk it queued behind."""
    want = engine.generate([2, 7, 1, 8], max_new_tokens=7)["token_ids"]
    stream = _decoding(engine)
    listening = _when_listening(engine)
    log = _log_the_loop(engine)
    # The chunk "runs" until an arrival has been heard.
    _steer(engine, 5.0, done=lambda rec: engine.metrics.admissions_heard > 0)
    assert listening.wait(5.0)
    fetches = []
    fetch = engine._fetch
    engine._fetch = lambda tree, tag="decode": (fetches.append(tag),
                                                fetch(tree, tag))[1]
    got = engine.generate([2, 7, 1, 8], max_new_tokens=7)["token_ids"]
    at = log.index("submit")
    assert log[at:at + 5] == ["submit", "prefill", "chunk", "retire", "land"]
    # ... and the wait fetched nothing: a chunk's, then the token's.
    assert fetches[:2] == ["decode", "prefill"]
    assert got == want
    assert isinstance(next(stream), int)        # decoding on beside it
    stats = engine.stats()
    assert stats["admissions_heard"] == 1 and stats["requests"] == 3


def _until(what, seconds=10.0):
    end = time.perf_counter() + seconds
    while not what():
        assert time.perf_counter() < end, "never happened"
        time.sleep(0.001)


@pytest.mark.parametrize("why", ["no_free_slot", "fetch_did_not_block",
                                 "chunk_already_done"])
def test_the_tick_keeps_its_order_where_it_must_not_listen(engine, why):
    """(b) With every slot taken (an arrival then stands in the waiting
    line), after a fetch that found its result ready, or with the chunk
    in flight done already, the wait never begins: an arrival is
    admitted at the top of a tick, as it always was."""
    streams = [_decoding(engine)]
    engine._devq.chunk_owns.append(2.0)     # a trusted estimate would wait
    asked, heard = [], []
    if why == "no_free_slot":
        streams.append(_decoding(engine))
        _steer(engine, 2.0)
    elif why == "chunk_already_done":
        _steer(engine, 2.0, done=lambda rec: True)
    else:
        fetch = engine._fetch

        def ready_fetch(tree, tag="decode"):
            out = fetch(tree, tag)
            engine._devq.fetched(None)
            return out

        engine._fetch = ready_fetch
        engine._chunk_done = lambda rec: False
    deadline, hear = engine._listen_deadline, engine._hear
    engine._listen_deadline = lambda rec: (asked.append(1), deadline(rec))[1]
    engine._hear = lambda *args: (heard.append(1), hear(*args))[1]
    log = _log_the_loop(engine)
    dry = engine.stats()["device_dry_dispatches"]
    t0 = time.perf_counter()
    if why == "no_free_slot":
        engine._queue.put(engine._make_request([2, 7, 1, 8], 3, None))
        _until(lambda: engine.scheduler.queue_depth() == 1)
        at = engine.stats()["decode_chunks_dispatched"]
        _until(lambda: engine.stats()["decode_chunks_dispatched"] >= at + 5)
        assert not asked and "prefill" not in log
    else:
        assert len(engine.generate([2, 7, 1, 8], 3)["token_ids"]) == 3
        at = log.index("prefill")
        assert log[at - 1] == "admit"
        assert log[at:at + 4] == ["prefill", "chunk", "retire", "land"]
        _until(lambda: asked)       # it would have listened, if it could
    assert time.perf_counter() - t0 < 1.5           # nobody waited 2 s
    assert not heard
    stats = engine.stats()
    assert stats["admissions_heard"] == 0
    # ... and no chunk went out to a device the thread knew dry: one is
    # in flight whenever the next is dispatched.
    assert stats["device_dry_dispatches"] == dry


@pytest.mark.parametrize("chunk_ends", ["before_its_successor_is_out",
                                        "after"])
def test_a_wait_that_left_the_device_dry_is_counted(engine, chunk_ends):
    """``device_dry_dispatches``: the chunk dispatched after a wait
    found the program ahead of it gone from the device already (the
    queue asks it, ``DeviceQueue.put``), dry since the wait's last look
    at most."""
    stream = _decoding(engine)
    dry = chunk_ends == "before_its_successor_is_out"
    before = engine.stats()
    waits = []
    # The chunk "ends" at the wait's own deadline, or not at all.
    engine._chunk_done = lambda rec: bool(
        dry and waits and time.perf_counter() >= waits[-1])
    engine._listen_deadline = lambda rec: (
        waits.append(time.perf_counter() + 0.002), waits[-1])[1]
    _until(lambda: len(waits) >= 5)
    engine._listen_deadline = lambda rec: None
    assert isinstance(next(stream), int)
    stats = engine.stats()
    late = stats["device_dry_dispatches"] - before["device_dry_dispatches"]
    assert late >= 4 if dry else late == 0
    dry_s = stats["device_dry_s"] - before["device_dry_s"]
    assert 0.0 <= dry_s < late * 0.05 + 1e-9
    assert stats["admissions_heard"] == 0


def test_a_seeded_schedule_of_arrivals_gives_the_serial_tokens():
    """(c) Whatever tick an arrival is heard in, a request's tokens are
    those of the serial schedule (``multi_step=False``, which never
    listens): greedy, per slot."""
    rng = random.Random(41)
    requests = [([rng.randrange(1, 200) for _ in range(rng.randrange(2, 15))],
                 rng.randrange(4, 24), rng.uniform(0.0, 0.15))
                for _ in range(12)]

    def run(eng):
        t0 = time.perf_counter()

        def one(prompt, n, due):
            time.sleep(max(0.0, t0 + due - time.perf_counter()))
            return eng.generate(prompt, max_new_tokens=n)["token_ids"]

        with concurrent.futures.ThreadPoolExecutor(len(requests)) as pool:
            futures = [pool.submit(one, *r) for r in requests]
            return [f.result(timeout=120) for f in futures]

    serial = _engine(multi_step=False, max_batch=4, max_len=64)
    eng = _engine(max_batch=4, max_len=64)
    try:
        want = run(serial)
        # Once as it comes (every program compiles under the first
        # arrivals, which pile up behind it), then listening.
        assert run(eng) == want
        _steer(eng, 0.003)
        got = run(eng)
        stats = eng.stats()
    finally:
        serial.close()
        eng.close()
    assert got == want
    assert [len(g) for g in got] == [n for _, n, _ in requests]
    assert serial.stats()["admissions_heard"] == 0
    assert 0 < stats["admissions_heard"] <= 12 < stats["requests"]


def test_a_listening_tick_is_accounted_for_and_fetches_nothing(engine):
    """(d) The wait is booked as ``decode_fetch``, what is heard inside
    it under ``admit`` and ``prefill_dispatch``: the phases still add up
    to the loop, no span has a name outside `TICK_PHASES`, and the
    fetches are one a chunk and one an admission, as before."""
    spans = []
    tracing.flush()
    tracing.set_sink(spans.extend)
    cfg.set("tracing_enabled", True)
    try:
        before = engine.stats()
        fetches = []
        fetch = engine._fetch
        engine._fetch = lambda tree, tag="decode": (fetches.append(tag),
                                                    fetch(tree, tag))[1]
        stream = _decoding(engine)
        listening = _when_listening(engine)
        # The chunk "runs" until the arrival sent into the wait is heard.
        sent = [0]
        _steer(engine, 5.0,
               done=lambda rec: engine.metrics.admissions_heard >= sent[0])
        for i in range(4):
            listening.clear()
            sent[0] += 1
            assert listening.wait(5.0)
            engine.generate([5 + i, 6, 7], max_new_tokens=4)
        assert isinstance(next(stream), int)
        engine.close()              # the counts below stand still
        stats = engine.stats()
    finally:
        cfg.set("tracing_enabled", False)
        tracing.flush()
        tracing.set_sink(None)
    assert stats["admissions_heard"] == 4
    phases = sum(stats[k] - before[k] for k in PHASE_KEYS)
    assert phases == pytest.approx(stats["tick_loop_s"] - before["tick_loop_s"],
                                   rel=0.10)
    assert fetches.count("prefill") == stats["requests"] == 5
    assert fetches.count("decode") == stats["decode_host_syncs"]
    assert len(fetches) == 5 + stats["decode_host_syncs"]
    ticks = [s for s in spans if s["name"].startswith("engine.tick.")]
    assert {s["name"] for s in ticks} <= {f"engine.tick.{p}"
                                          for p in TICK_PHASES}
    waits = [s for s in ticks if s["attrs"].get("listening")
             and s["name"] == "engine.tick.decode_fetch"]
    assert any(s["attrs"]["heard"] for s in waits)
    ticks.sort(key=lambda s: s["start"])
    for a, b in zip(ticks, ticks[1:]):
        assert a["end"] <= b["start"], (a["name"], b["name"])


def test_the_deadline_is_derived_from_what_the_tick_measured():
    """Chunk N's start (the last fetch's return, if it waited) plus the
    shortest of the last chunks, less their scatter and the longest of
    the last carried dispatches; unknown where any of it is."""
    eng = _engine()
    eng.close()                 # the thread is gone: the state is ours
    q = eng._devq
    ahead = q.put("prefill", 98.0, None)
    rec = {"carried": True, "program": q.put("chunk", 99.0, None)}
    assert eng._listen_deadline(rec) is None        # nothing timed yet
    q.chunk_owns.extend([0.090, 0.087, 0.088])
    eng._dispatch_s.extend([0.001, 0.003, 0.002])
    assert eng._listen_deadline(rec) is None        # no fetch has returned
    q.fetched(100.0)
    q.seen(ahead, 100.1)                            # ... and waited
    assert eng._listen_deadline(rec) == pytest.approx(
        100.0 + 0.087 - (0.090 - 0.087) - 0.003)
    assert eng._listen_deadline(dict(rec, carried=False)) is None
    # Only the last few of each count: one slow chunk ages out.
    q.chunk_owns.extend([0.087] * q.chunk_owns.maxlen)
    assert eng._listen_deadline(rec) == pytest.approx(100.0 + 0.087 - 0.003)
    # The fetch found its result ready, or a program no fetch stamps
    # lay ahead: the chunk's start is not known.
    for stamp, fetched in ((None, 1), (103.0, 2)):
        ahead = [q.put("prefill", 101.0, None) for _ in range(2)]
        late = {"carried": True, "program": q.put("chunk", 102.0, None)}
        q.fetched(stamp)
        q.seen(ahead[-1] if fetched == 1 else late["program"], 103.5)
        assert eng._listen_deadline(late) is None
    # An arrival would be admitted at once only ahead of everyone.
    assert eng._admits_at_once()
    eng.scheduler._waiting.append(object())
    assert not eng._admits_at_once()


def test_both_counters_are_flat_keys_a_counter_delta_can_subtract():
    """(e)"""
    snap = EngineMetrics("flat").snapshot()
    for key in ("admissions_heard", "device_dry_dispatches"):
        assert snap[key] == 0 and isinstance(snap[key], int)
    assert snap["device_dry_s"] == 0.0
    m = EngineMetrics("counted")
    m.record_heard()
    m.record_heard()
    m.record_dry_dispatch(0.25)
    snap = m.snapshot()
    assert (snap["admissions_heard"], snap["device_dry_dispatches"],
            snap["device_dry_s"]) == (2, 1, 0.25)
