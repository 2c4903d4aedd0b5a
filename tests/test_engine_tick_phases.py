"""The engine's tick, timed from inside: always-on phase counters, the
TTFT that decomposes, and — while tracing is on — one
``engine.tick.<phase>`` span per phase per tick on the counters' clock.
All on the CPU's tiny engine; times here prove bookkeeping, not speed.
"""

from __future__ import annotations

import ast
import pathlib
import time

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as cfg
from ray_tpu.serve.engine import metrics as engine_metrics
from ray_tpu.serve.engine.metrics import TICK_PHASES, EngineMetrics, TickClock
from ray_tpu.util import tracing

ENGINE_KW = {"max_batch": 2, "max_len": 64, "prompt_buckets": [8, 16],
             "decode_chunk": 2}
TICK_NAMES = {f"engine.tick.{p}" for p in TICK_PHASES}
PHASE_KEYS = [f"tick_{p}_s" for p in TICK_PHASES]
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def engine():
    from ray_tpu.serve.llm import LLMEngine

    old = cfg.get("tracing_enabled")
    cfg.set("tracing_enabled", False)
    eng = LLMEngine(**ENGINE_KW)
    yield eng
    eng.close()
    cfg.set("tracing_enabled", old)
    tracing.set_sink(None)


@pytest.fixture
def sink():
    """Spans of a traced stretch, through the benchmark's own path
    (`set_sink`, switched on AFTER the engine exists)."""
    spans = []
    tracing.flush()
    tracing.set_sink(spans.extend)
    cfg.set("tracing_enabled", True)
    yield spans
    cfg.set("tracing_enabled", False)
    tracing.set_sink(None)


def _collect(spans):
    cfg.set("tracing_enabled", False)
    tracing.flush()
    return [s for s in spans if s["end"] is not None]


def test_phase_counters_are_monotone_and_account_for_the_loop(engine):
    seen = [engine.stats()]
    for n in (3, 5, 7):
        engine.generate(list(range(1, n + 1)), max_new_tokens=6)
        seen.append(engine.stats())
    for a, b in zip(seen, seen[1:]):
        for key in PHASE_KEYS + ["tick_loop_s", "ticks"]:
            assert b[key] >= a[key], key
    last = seen[-1]
    assert last["ticks"] > seen[0]["ticks"]
    for key in ("tick_admit_s", "tick_prefill_dispatch_s",
                "tick_prefill_fetch_s", "tick_prefill_deliver_s",
                "tick_decode_dispatch_s", "tick_decode_fetch_s",
                "tick_decode_deliver_s"):
        assert last[key] > 0.0, key
    assert last["tick_install_s"] == 0.0      # colocated role
    # The phases and the loop's wall seconds agree: what is left is
    # loop overhead (and the iteration under way at the snapshot).
    phases = sum(last[k] for k in PHASE_KEYS)
    assert phases == pytest.approx(last["tick_loop_s"], rel=0.10)


def test_idle_is_a_phase_of_its_own(engine):
    before = engine.stats()
    time.sleep(0.35)                # empty roster: blocked on the mailbox
    after = engine.stats()
    assert after["tick_idle_s"] - before["tick_idle_s"] >= 0.2
    assert after["tick_decode_fetch_s"] == before["tick_decode_fetch_s"]


def test_ttft_is_the_sum_of_its_two_waits(engine):
    given = []
    record = engine.metrics.record_admit

    def spy(queue_s, prefill_s, *rest):
        given.append((queue_s, prefill_s))
        return record(queue_s, prefill_s, *rest)

    engine.metrics.record_admit = spy
    for n in (2, 4, 6, 8):
        engine.generate(list(range(1, n + 1)), max_new_tokens=3)
    s = engine.stats()
    assert s["requests"] == len(given) == 4
    assert all(q >= 0.0 and p > 0.0 for q, p in given)
    assert s["queue_wait_s"] == pytest.approx(sum(q for q, _ in given),
                                              rel=1e-12)
    assert s["prefill_wait_s"] == pytest.approx(sum(p for _, p in given),
                                                rel=1e-12)
    # ... and the TTFT the engine recorded is exactly their sum.
    assert sum(engine.metrics._ttfts) == pytest.approx(
        s["queue_wait_s"] + s["prefill_wait_s"], rel=1e-12)


@pytest.mark.parametrize("prefill_chunk", [0, 4], ids=["whole", "chunked"])
def test_prefill_fetch_bytes_is_a_token_an_admission(prefill_chunk):
    """The tick's prefill hands back its token (int32: 4 bytes; this
    family has no counters) in the ONE counted fetch of an admission; a
    non-final chunk of a chunked prefill fetches nothing."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(prefill_chunk=prefill_chunk, **ENGINE_KW)
    try:
        assert eng.stats()["prefill_fetch_bytes"] == 0
        for i, n in enumerate((3, 11, 14), start=1):    # 1, 3, 4 chunks
            eng.generate(list(range(1, n + 1)), max_new_tokens=4)
            stats = eng.stats()
            assert stats["requests"] == i
            assert stats["prefill_fetch_bytes"] == 4 * i
    finally:
        eng.close()


def test_prompt_tokens_are_counted_a_chunk_at_its_dispatch(sink):
    """A three-chunk prompt raises ``prefill_chunk_tokens`` by each
    chunk's REAL tokens where the chunk is dispatched (bucket padding
    left out) and ``prefill_tokens`` once, by the whole prompt, when
    the last chunk lands; the ``engine.tick.prefill_dispatch`` spans
    carry the same as ``tokens``."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(prefill_chunk=4, **ENGINE_KW)
    seen = []
    record = eng.metrics.record_prefill_chunk

    def recording(tokens):
        record(tokens)
        seen.append((tokens, eng.metrics.prefill_chunk_tokens,
                     eng.metrics.prefill_chunks_dispatched,
                     eng.metrics.prefill_tokens))

    eng.metrics.record_prefill_chunk = recording
    try:
        before = eng.stats()
        assert before["prefill_chunk_tokens"] == 0
        assert before["prefill_chunks_dispatched"] == 0
        # (the scheduler rounds a chunk of 4 up to the smallest bucket)
        eng.generate(list(range(1, 20)), max_new_tokens=4)   # 8 + 8 + 3
        after = eng.stats()
    finally:
        eng.close()
    assert seen == [(8, 8, 1, 0), (8, 16, 2, 0), (3, 19, 3, 0)]
    assert after["prefill_tokens"] == 19 == after["prefill_chunk_tokens"]
    assert after["prefill_chunks_dispatched"] == 3
    dispatched = [s["attrs"]["tokens"] for s in _collect(sink)
                  if s["name"] == "engine.tick.prefill_dispatch"]
    assert dispatched == [8, 8, 3]


def test_first_deliver_moves_once_per_streamed_request(engine):
    engine.generate([1, 2, 3], max_new_tokens=4)       # not streamed
    assert engine.stats()["streams"] == 0
    for i in range(3):
        toks = list(engine.generate_stream([1, 2, 3, 4 + i],
                                           max_new_tokens=5))
        assert len(toks) == 5
        s = engine.stats()
        assert s["streams"] == i + 1
        assert 0.0 < s["first_deliver_s"] < 5.0
    samples = engine_metrics.SERVE_TTFT_BREAKDOWN_MS
    assert {"queue", "prefill", "deliver"} <= {
        dict(k).get("component") for k in samples._counts}


def test_traced_ticks_emit_only_phase_names_disjoint_under_one_root(
        engine, sink):
    # No request here is made under a trace: tick spans do not depend
    # on any request of the roster being traced.
    for n in (3, 9):
        engine.generate(list(range(1, n + 1)), max_new_tokens=6)
    time.sleep(0.25)
    spans = _collect(sink)
    ticks = [s for s in spans if s["name"].startswith("engine.tick.")]
    assert {s["name"] for s in ticks} <= TICK_NAMES
    assert {"engine.tick.admit", "engine.tick.prefill_dispatch",
            "engine.tick.prefill_fetch", "engine.tick.prefill_deliver",
            "engine.tick.decode_dispatch", "engine.tick.decode_fetch",
            "engine.tick.decode_deliver", "engine.tick.idle"} <= {
                s["name"] for s in ticks}
    # Nothing else of the engine's: no request carried a trace context
    # (a process with a compile account adds ITS ``compile.<program>``).
    # The programs' own spans (`DeviceQueue`) are named outside
    # ``engine.``: the benchmark hands every ``engine.*`` span to the
    # idle-gap attribution, and one that covers busy time owns no gap.
    assert {s["name"] for s in spans if not s["name"].startswith("compile.")
            } <= TICK_NAMES | {"serve.engine", "device.chunk",
                               "device.prefill"}
    roots = [s for s in spans if s["name"] == "serve.engine"]
    assert len(roots) == 1 and roots[0]["parent_id"] == ""
    assert roots[0]["attrs"]["engine"] == engine.metrics.name
    for s in ticks:
        assert s["parent_id"] == roots[0]["span_id"]
        assert s["trace_id"] == roots[0]["trace_id"]
    ticks.sort(key=lambda s: s["start"])
    for a, b in zip(ticks, ticks[1:]):
        assert a["end"] <= b["start"], (a["name"], b["name"])
    fetched = [s for s in ticks if s["name"] == "engine.tick.prefill_fetch"]
    assert all(s["attrs"]["bytes"] > 0 and s["attrs"]["bucket"] in (8, 16)
               for s in fetched)


def test_request_spans_share_the_tick_clock(engine, sink):
    """`engine.queued` carries the request's real stamps: it starts at
    arrival, inside the caller's root span, and ends where the first
    `engine.tick.prefill_dispatch` of that request starts."""
    with tracing.trace("client") as root:
        engine.generate([5, 6, 7, 8], max_new_tokens=4)
    spans = _collect(sink)
    mine = [s for s in spans if s["trace_id"] == root.trace_id]
    queued = next(s for s in mine if s["name"] == "engine.queued")
    prefill = next(s for s in mine if s["name"] == "engine.prefill")
    client = next(s for s in mine if s["name"] == "client")
    assert client["start"] - 1e-3 <= queued["start"] <= queued["end"]
    assert queued["end"] == prefill["start"]
    dispatch = [s for s in spans
                if s["name"] == "engine.tick.prefill_dispatch"]
    assert prefill["start"] in {s["start"] for s in dispatch}
    chunks = [s for s in mine if s["name"] == "engine.decode_chunk"]
    fetch_ends = {s["end"] for s in spans
                  if s["name"] == "engine.tick.decode_fetch"}
    assert chunks and all(c["end"] in fetch_ends for c in chunks)


def test_tracing_off_ticks_are_span_free_and_sync_budget_unchanged(engine):
    from ray_tpu.util.tracing import _buffer

    before = len(_buffer)
    out = engine.generate([1, 2, 3, 4], max_new_tokens=6)
    time.sleep(0.15)                           # an idle tick or two
    assert out["num_generated"] == 6
    assert len(_buffer) == before
    assert engine._tick._root is None          # no root until traced
    # 1 prefill sync + ceil(5/2) decode-chunk syncs, as before this PR.
    stats = engine.stats()
    assert stats["decode_host_syncs"] == 3
    # The device's queue counted all the same: three chunks of two.
    assert stats["chunk_steps_retired"] == 6 and stats["chunk_period_s"] > 0
    assert not engine._devq._open


# ------------------------------- the pipelined schedule under the same clock

def _stream_pair(eng, warm: int = 5):
    """Two streams decoding side by side, `warm` tokens in: the tick
    has a chunk in flight and dispatches the next ahead of its fetch."""
    streams = [eng.generate_stream([3 + i, 1, 4, 1, 5], max_new_tokens=50)
               for i in range(2)]
    for stream in streams:
        for _ in range(warm):
            assert isinstance(next(stream), int)
    return streams


def test_phases_account_for_the_loop_with_the_deliveries_under_a_chunk(
        engine):
    """First tokens are fetched AFTER the chunk they join is dispatched
    and a chunk is delivered after the next is: every second is still
    in exactly one phase, and a changed roster is still carried."""
    before = engine.stats()
    streams = _stream_pair(engine)
    engine.generate([9, 9, 9], max_new_tokens=1)    # waits for a slot
    for stream in streams:
        assert len(list(stream)) == 45
    time.sleep(0.15)
    s = engine.stats()
    phases = sum(s[k] - before[k] for k in PHASE_KEYS)
    assert phases == pytest.approx(s["tick_loop_s"] - before["tick_loop_s"],
                                   rel=0.10)
    assert s["decode_chunks_carried"] >= s["decode_chunks_dispatched"] - 2
    # One prefill sync an admission, one sync a fetched chunk.
    assert s["requests"] == 3
    assert s["decode_host_syncs"] <= s["decode_chunks_dispatched"]


@pytest.mark.parametrize("fault", ["fetch_of_the_chunk_in_flight",
                                   "early_dispatch_after_donation"])
def test_a_device_failure_between_dispatch_and_retire_fails_the_roster_once(
        engine, fault):
    """Chunk N+1 is dispatched before chunk N is retired. Whichever of
    the two the device fails in, the roster is failed ONCE, each request
    hears of it once, the cache is rebuilt only if it went, and the
    engine serves the next request as a fresh one would."""
    want = engine.generate([2, 7, 1, 8], max_new_tokens=7)["token_ids"]
    streams = _stream_pair(engine)
    failed = []
    fail_roster = engine._fail_roster

    def spy(e, *rest):
        failed.append(e)
        return fail_roster(e, *rest)

    engine._fail_roster = spy
    if fault == "fetch_of_the_chunk_in_flight":
        inner = engine._fetch

        def fetch(tree, tag="decode"):
            if tag == "decode" and not failed:
                # The next chunk is out already: that is the schedule.
                assert engine._inflight is not None
                raise RuntimeError("injected device failure")
            return inner(tree, tag)

        engine._fetch = fetch
    else:
        inner = engine.loop.decode_chunk

        def dispatch(params, cache, *args):
            if not failed:
                inner(params, cache, *args)      # takes the cache with it
                raise RuntimeError("injected device failure")
            return inner(params, cache, *args)

        engine.loop.decode_chunk = dispatch
    for stream in streams:
        with pytest.raises(RuntimeError, match="injected"):
            list(stream)
    assert len(failed) == 1
    stats = engine.stats()
    assert stats["active"] == 0 and stats["free_slots"] == 2
    assert stats["cache_rebuilds"] == (
        1 if fault == "early_dispatch_after_donation" else 0)
    assert engine.generate([2, 7, 1, 8], max_new_tokens=7)["token_ids"] \
        == want
    assert engine.stats()["cache_rebuilds"] == stats["cache_rebuilds"]


# ---------------------------------------------------- the device's queue

QUEUE_KEYS = {"prefill_behind_s": float, "prefill_own_s": float,
              "prefill_split": int, "prefill_ahead_chunks": int,
              "prefill_ahead_prefills": int, "chunk_period_s": float,
              "chunk_steps_retired": int, "chunk_own_s": float,
              "chunk_steps_timed": int, "device_dry_s": float,
              "device_dry_dispatches": int, "decode_steps_frozen": int}


def _every_fetch_waits(eng):
    """On the CPU's tiny engine a result is often ready before its
    fetch: stamp every fetch's return as one that had to wait, so that
    the queue sees every end (the arithmetic is what is held here)."""
    fetch = eng._fetch

    def waited(tree, tag="decode"):
        out = fetch(tree, tag)
        eng._devq.fetched(time.perf_counter())
        return out

    eng._fetch = waited


def test_every_queue_counter_is_a_flat_key_of_the_snapshot(engine):
    snap = EngineMetrics("flat").snapshot()
    for key, kind in QUEUE_KEYS.items():
        assert snap[key] == 0 and type(snap[key]) is kind, key
    stats = engine.stats()
    assert set(QUEUE_KEYS) <= set(stats)
    assert "listen_deadline_late" not in stats


def test_a_split_prefill_is_its_wait_less_the_fetch_s_last_lines(engine):
    """``behind + own`` of an admission whose prefill's ends were both
    seen is its ``prefill_s`` less the host's time between the fetch's
    return and the phase's closing stamp. That residue lies inside the
    landing that holds it (for the second of a pair, from the FIRST's
    landing on: one program, one end), which the test times around the
    call: a bound a loaded machine keeps, where a number of
    milliseconds is the scheduler's to break.

    Not every admission is split. The second stream arrives while the
    first decodes beside a free slot: where the chunk in flight outlasts
    the host's work (six loaded workers) the tick HEARS it in its
    listening wait, and the dispatch that follows may find that prefill
    gone from the device already and close it with its end unseen
    (`DeviceQueue.put`, ``poll``). Such an admission is counted as
    heard and not as split; every other one is split."""
    _every_fetch_waits(engine)
    waits, splits, landings, entered = [], [], [], {}
    admit, split, land = engine.metrics.record_admit, \
        engine.metrics.record_prefill_split, engine._land_prefill
    engine.metrics.record_admit = lambda q, p, *rest: (
        waits.append(p), admit(q, p, *rest))[1]
    engine.metrics.record_prefill_split = lambda b, o: (
        splits.append((b, o)), split(b, o))[1]

    def timed_landing(job):
        t = entered.setdefault(id(job.programs[-1]), time.perf_counter())
        had = len(splits)
        land(job)
        # (the landing's seconds, its split or None)
        landings.append((time.perf_counter() - t, (splits[had:] or [None])[0]))

    engine._land_prefill = timed_landing
    streams = _stream_pair(engine)          # two behind a chunk or two
    for n in (2, 5, 9):
        engine.generate(list(range(1, n + 1)), max_new_tokens=3)
    for stream in streams:
        stream.close()
    s = engine.stats()
    assert s["requests"] == len(waits) == 5 == len(landings)
    assert s["prefill_split"] == len(splits) <= 5
    assert 5 - len(splits) <= s["admissions_heard"]
    assert s["prefill_behind_s"] == pytest.approx(sum(b for b, _ in splits))
    assert s["prefill_own_s"] == pytest.approx(sum(o for _, o in splits))
    assert [found for _, found in landings if found] == splits
    for wait, (landing, found) in zip(waits, landings):
        if found is not None:
            behind, own = found
            assert behind >= 0.0 and own > 0.0
            assert 0.0 <= wait - (behind + own) <= landing
    # An arrival beside a running stream queued behind its chunk.
    assert s["prefill_ahead_chunks"] >= 1 and s["prefill_ahead_prefills"] >= 0


def test_an_unchunked_prefill_whose_fetch_found_it_ready_is_not_split(engine):
    fetch = engine._fetch
    engine._fetch = lambda tree, tag="decode": (
        fetch(tree, tag), engine._devq.fetched(None))[0]
    engine.generate([1, 2, 3], max_new_tokens=4)
    s = engine.stats()
    assert s["requests"] == 1 and s["prefill_split"] == 0
    assert s["prefill_own_s"] == 0.0 == s["chunk_own_s"]
    assert s["chunk_steps_timed"] == 0 < s["chunk_steps_retired"]


def test_a_chunked_prefill_is_not_split_and_closes_behind_itself():
    """Only the last chunk of an admission is fetched: the others' ends
    are seen by nobody, the admission is not split, and the queue holds
    none of them afterwards."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(prefill_chunk=4, **ENGINE_KW)
    try:
        _every_fetch_waits(eng)
        eng.generate(list(range(1, 20)), max_new_tokens=4)  # three chunks
        eng.generate([1, 2, 3], max_new_tokens=2)           # one
        s = eng.stats()
    finally:
        eng.close()
    assert s["requests"] == 2 and s["prefill_split"] == 1
    assert s["prefill_chunks_dispatched"] == 4
    assert not eng._devq._open


def test_the_retire_cadence_adds_up_to_the_loop_while_a_roster_decodes(
        engine):
    """``chunk_period_s`` is the time from one retire to the next (the
    first's from its dispatch): the periods are disjoint stretches of
    the loop's working seconds, and while a roster decodes nearly all
    of them."""
    _every_fetch_waits(engine)
    for stream in _stream_pair(engine):     # every program compiles here
        list(stream)
    before = engine.stats()
    for stream in _stream_pair(engine):
        assert len(list(stream)) == 45
    time.sleep(0.15)                    # the loop is idle: counts stand
    after = engine.stats()
    d = {k: after[k] - before[k] for k in
         ("chunk_period_s", "chunk_steps_retired", "chunk_own_s",
          "chunk_steps_timed", "tick_loop_s", "tick_idle_s",
          "decode_host_syncs")}
    assert d["chunk_steps_retired"] == 2 * d["decode_host_syncs"] >= 50
    worked = d["tick_loop_s"] - d["tick_idle_s"]
    assert 0.6 * worked <= d["chunk_period_s"] <= worked * 1.02
    # A chunk's own device seconds fit inside the cadence.
    assert 0 < d["chunk_steps_timed"] <= d["chunk_steps_retired"]
    assert 0.0 < d["chunk_own_s"] <= d["chunk_period_s"] * 1.02


@pytest.mark.parametrize("multi_step", [True, False],
                         ids=["pipelined", "serial"])
def test_steps_scanned_past_a_request_s_end_are_counted_frozen(multi_step):
    """A budget of 4: one token from the prefill, three from a chunk of
    eight, whose other five steps run for nobody."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(**{**ENGINE_KW, "decode_chunk": 8,
                       "multi_step": multi_step})
    try:
        assert len(eng.generate([3, 1, 4], max_new_tokens=4)["token_ids"]) == 4
        s = eng.stats()
        assert (s["decode_steps"], s["decode_steps_frozen"]) == (8, 5)
        assert s["chunk_steps_retired"] == 8
        eng.generate([3, 1, 4], max_new_tokens=9)       # a whole chunk
        s = eng.stats()
        assert (s["decode_steps"], s["decode_steps_frozen"]) == (16, 5)
    finally:
        eng.close()


def test_device_spans_are_disjoint_and_in_dispatch_order(engine, sink):
    _every_fetch_waits(engine)
    put, programs = engine._devq.put, []
    engine._devq.put = lambda *a, **kw: (programs.append(put(*a, **kw)),
                                         programs[-1])[1]
    streams = _stream_pair(engine, warm=8)
    engine.generate([7, 7, 7, 7], max_new_tokens=5)
    for stream in streams:
        stream.close()
    time.sleep(0.1)
    spans = _collect(sink)
    device = [s for s in spans if s["name"].startswith("device.")]
    root = next(s for s in spans if s["name"] == "serve.engine")
    assert all(s["parent_id"] == root["span_id"] for s in device)
    assert {s["name"] for s in device} == {"device.chunk", "device.prefill"}
    # As emitted they are in dispatch order, one for each program whose
    # ends were both seen, and no two overlap: one device, one queue.
    split = [p for p in programs if p.split() is not None]
    assert [(s["name"], s["start"], s["end"]) for s in device] == [
        ("device." + p.kind, tracing.wall(p.start), tracing.wall(p.end))
        for p in split]
    # (an arrival HEARD in the listening wait may cost two: its prefill
    # and the chunk ahead, closed unseen by the next dispatch's poll)
    heard = engine.stats()["admissions_heard"]
    assert len(split) >= len(programs) - 2 - 2 * heard
    for a, b in zip(device, device[1:]):
        assert a["end"] <= b["start"]
    for s in device:
        attrs = s["attrs"]
        assert attrs["own_s"] == pytest.approx(s["end"] - s["start"], abs=1e-6)
        assert attrs["behind_s"] >= 0.0
        assert ("slots" in attrs) == (s["name"] == "device.chunk")
        assert ("bucket" in attrs and attrs["tokens"] > 0) == (
            s["name"] == "device.prefill")


def test_request_spans_carry_the_queue_s_split(engine, sink):
    _every_fetch_waits(engine)
    with tracing.trace("client") as root:
        engine.generate([5, 6, 7, 8], max_new_tokens=5)
    spans = [s for s in _collect(sink) if s["trace_id"] == root.trace_id]
    prefill = next(s for s in spans if s["name"] == "engine.prefill")
    attrs = prefill["attrs"]
    assert (attrs["ahead_chunks"], attrs["ahead_prefills"]) == (0, 0)
    assert attrs["behind_s"] == 0.0 < attrs["own_s"]
    assert attrs["own_s"] <= prefill["end"] - prefill["start"]
    chunks = [s for s in spans if s["name"] == "engine.decode_chunk"]
    assert chunks and all(c["attrs"]["period_s"] > 0 for c in chunks)
    assert all(0 < c["attrs"]["own_s"] for c in chunks
               if "own_s" in c["attrs"])


# ------------------------------------------------------ the clock alone

class _Annotation:
    """Stands in for `jax.profiler.TraceAnnotation`."""

    live = []
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        assert not _Annotation.live, "annotations overlap"
        _Annotation.live.append(self.name)

    def __exit__(self, *exc):
        _Annotation.log.append(_Annotation.live.pop())


def test_a_phase_inside_a_phase_suspends_the_outer_one(sink):
    """A preemption lands the chunk in flight during `admit`: the
    fetch's time is not the admission's, and the spans stay disjoint."""
    _Annotation.log.clear()
    m = EngineMetrics("clock-test")
    clock = TickClock(m, _Annotation)
    clock.lap()
    with clock.phase("admit"):
        time.sleep(0.01)
        with clock.phase("decode_fetch", slots=2) as attrs:
            time.sleep(0.03)
            attrs["bytes"] = 24
        time.sleep(0.01)
    clock.lap()
    assert 0.02 <= m.tick_s["admit"] < 0.03 <= m.tick_s["decode_fetch"]
    assert sum(m.tick_s.values()) == pytest.approx(m.tick_loop_s, rel=0.05)
    assert m.ticks == 2
    spans = [s for s in _collect(sink) if s["name"] != "serve.engine"]
    assert [s["name"] for s in spans] == [
        "engine.tick.admit", "engine.tick.decode_fetch", "engine.tick.admit"]
    assert spans[1]["attrs"] == {"slots": 2, "bytes": 24}
    for a, b in zip(spans, spans[1:]):
        assert a["end"] == b["start"]          # one stamp per boundary
    assert _Annotation.log == [s["name"] for s in spans]
    assert not _Annotation.live


def test_a_phase_that_began_traced_is_recorded_after_tracing_goes_off(sink):
    """The harness switches tracing off at the end of its profiled
    stretch, in the middle of some phase: that phase still names its
    gap. One that begins afterwards emits nothing."""
    _Annotation.log.clear()
    clock = TickClock(EngineMetrics("edge"), _Annotation)
    with clock.phase("prefill_fetch", bucket=8):
        cfg.set("tracing_enabled", False)
    with clock.phase("decode_fetch"):
        pass
    tracing.flush()
    assert [s["name"] for s in sink] == ["serve.engine",
                                         "engine.tick.prefill_fetch"]
    assert sink[1]["end"] > sink[1]["start"]
    assert _Annotation.log == ["engine.tick.prefill_fetch"]


def test_snapshot_carries_flat_keys_a_counter_delta_can_subtract():
    snap = EngineMetrics("flat").snapshot()
    for key in PHASE_KEYS + ["tick_loop_s", "queue_wait_s", "prefill_wait_s",
                             "first_deliver_s"]:
        assert snap[key] == 0.0 and isinstance(snap[key], float)
    assert snap["ticks"] == 0 and snap["streams"] == 0


def test_ttft_breakdown_boundaries_resolve_a_loaded_replica():
    bounds = engine_metrics.SERVE_TTFT_BREAKDOWN_MS.boundaries
    inside = [b for b in bounds if 50 <= b <= 1500]
    assert len(inside) >= 10 and list(bounds) == sorted(bounds)


def test_wall_is_one_offset_for_every_stamp():
    a = time.perf_counter()
    # (wall seconds near 2e9 resolve a quarter of a microsecond)
    assert tracing.wall(a + 2.5) - tracing.wall(a) == pytest.approx(
        2.5, abs=1e-6)
    assert abs(tracing.wall(time.perf_counter()) - time.time()) < 0.5


def _imports(path):
    tree = ast.parse((ROOT / path).read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


def test_util_tracing_stays_free_of_jax_and_the_tick_of_the_wall_clock():
    for path in ("ray_tpu/util/tracing.py",
                 "ray_tpu/serve/engine/metrics.py"):
        assert not [m for m in _imports(path)
                    if m == "jax" or m.startswith("jax.")], path
    assert "time.time()" not in (
        ROOT / "ray_tpu/serve/engine/core.py").read_text()
