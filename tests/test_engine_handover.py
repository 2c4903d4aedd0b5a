"""The tick's early hand-over (`core.py` ``_hand_over``): a request that
must end inside the decode chunk in flight, by its budget or its row
cap, lets go of its slot at the top of the tick, the next waiter is
seated in it and joins the NEXT chunk, and the request still gets what
the chunk in flight made for it.

The engines are the tiny ones of the engine tests. A burst is put into
the mailbox whole before the thread may admit (``_burst``), so who
waits and who hands over does not depend on thread timing; budgets of
``1 + whole chunks`` end on a chunk's last step. Nothing here proves
speed.
"""

from __future__ import annotations

import concurrent.futures
import importlib
import random
import threading

import jax
import pytest

from ray_tpu.serve.engine.kv_manager import KVCacheManager
from ray_tpu.serve.engine.scheduler import EngineRequest, Scheduler
from tests.test_engine_compat_matrix import _family_cfg
from tests.test_engine_listen import _engine, _log_the_loop

CHUNK = 4
PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8], [9, 2, 6, 5, 3, 5],
           [1, 6, 1, 8, 0, 3], [1, 4, 1, 4, 2], [5, 7, 7, 2, 1, 5, 6]]
BOOM = RuntimeError("device lost")


def _dense(**kw):
    """Two slots of 64 rows, chunks of four; listens nowhere."""
    eng = _engine(**{"max_len": 64, "decode_chunk": CHUNK,
                     "prefix_block": 4, **kw})
    eng._listen_deadline = lambda rec: None
    return eng


def _family_engine(family, **kw):
    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = _family_cfg(family)
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    return InferenceEngine(
        cfg, params, max_batch=2, max_len=128, prompt_buckets=[8, 16],
        decode_chunk=CHUNK, prefix_block=8, kv_fleet_min_prefix_blocks=-1,
        **kw)


def _burst(eng, requests):
    """Every request in the mailbox before the thread admits one.
    ``requests``: (prompt, budget[, eos_id[, priority]])."""
    gate, admit = threading.Event(), eng._admit
    eng._admit = lambda: (gate.wait(30.0), admit())[1]
    reqs = []
    for prompt, budget, *rest in requests:
        eos, priority = (rest + [None, 0][len(rest):])[:2]
        reqs.append(eng._make_request(prompt, budget, eos,
                                      priority=priority))
    for req in reqs:
        eng._queue.put(req)
    gate.set()
    return reqs


def _tokens(reqs):
    return [r.future.result(timeout=120)["token_ids"] for r in reqs]


def _answers(eng, requests):
    """The burst's token lists and the engine's stats; closes it."""
    try:
        return _tokens(_burst(eng, requests)), eng.stats()
    finally:
        eng.close()


@pytest.fixture(scope="module")
def serial_tokens():
    """What the serial schedule (no chunk in flight at the top of a
    tick: nothing to hand over) answers to PROMPTS at 32 tokens."""
    serial = _dense(multi_step=False)
    try:
        return [serial.generate(p, max_new_tokens=32)["token_ids"]
                for p in PROMPTS]
    finally:
        serial.close()


# ---------------------------------------------------------- the scheduler

@pytest.mark.parametrize("plen,budget,made", [
    (4, 9, 1), (4, 9, 5), (4, 9, 6), (20, 12, 1), (20, 12, 4), (25, 7, 3),
    (2, 30, 1)])
def test_the_rule_is_the_finish_rule_seen_k_tokens_ahead(plen, budget, made):
    """``ends_within`` is ``is_finished``'s budget and row-cap clauses
    after k more tokens, exactly; an EOS is not in it."""
    sched = Scheduler(KVCacheManager(1, 32), max_len=32, prompt_buckets=[8])
    req = EngineRequest(list(range(plen)), budget, eos_id=5,
                        generated=[1] * made, length=plen + made - 1)
    for k in range(1, 9):
        after = EngineRequest(req.prompt_ids, budget,
                              generated=[1] * (made + k),
                              length=req.length + k)
        assert sched.ends_within(req, k) == sched.is_finished(after, 1), k


def test_the_row_cap_alone_ends_a_request_within_the_chunk():
    """(c) A budget the rows cannot hold: the cap is what the rule
    reads."""
    sched = Scheduler(KVCacheManager(1, 32), max_len=32, prompt_buckets=[8])
    req = EngineRequest(list(range(8)), 1000, generated=[1] * 19, length=26)
    assert req.remaining() > CHUNK
    assert not sched.ends_within(req, CHUNK)      # 26 + 4 + 1 < 32
    req.generated.append(1)
    req.length += 1
    assert sched.ends_within(req, CHUNK)          # 27 + 4 + 1 >= 32


def _two_seated():
    kv = KVCacheManager(2, 64, block_size=4)
    sched = Scheduler(kv, max_len=64, prompt_buckets=[8, 16])
    first = EngineRequest([1, 2, 3, 4, 5], 4)
    last = EngineRequest([4, 5, 6], 40)
    for req in (first, last):
        sched.submit(req)
    for adm in sched.admissions():
        adm.request.generated += [7, 8]
        sched.activate(adm.request)
        adm.request.length += 1
    assert (first.slot, last.slot) == (0, 1) and kv.free_slots() == 0
    return kv, sched, first, last


def test_a_handed_over_request_holds_no_slot_and_its_finish_frees_none():
    """``req.slot`` is -1 after the hand-over, and -1 indexes the LAST
    slot: the finish of an ending request must leave that one in use.
    What the slot keeps for reuse is prompt + generated[:-1]."""
    kv, sched, first, last = _two_seated()
    assert sched.ends_within(first, 4) and not sched.ends_within(last, 4)
    sched.hand_over(first)
    assert first.slot == -1 and sched.active == [last]
    assert sched.ending == [first] and kv.free_slots() == 1
    assert kv._slots[0].resident == (1, 2, 3, 4, 5, 7)
    sched.finish(first)                     # at the retire: nothing to free
    assert kv.free_slots() == 1 and kv._slots[1].in_use
    assert sched.ending == [] and sched.active == [last]


def test_the_manager_refuses_to_release_no_slot():
    kv, _sched, first, _last = _two_seated()
    first.slot = -1
    with pytest.raises(ValueError, match="holds no slot"):
        kv.release(first.slot)
    assert kv._slots[1].in_use


def test_a_failed_roster_takes_the_ending_with_it():
    kv, sched, first, last = _two_seated()
    sched.hand_over(first)
    assert sched.fail_active() == [last, first]
    assert sched.active == [] and sched.ending == []
    assert kv.free_slots() == 2 and last.slot == -1


# ------------------------------------------------ (a), (g): the closed loop

# An anchor of 1 + 6 chunks; beside it three requests of 1 + 2 chunks
# turn one slot over: with the hand-over each joins the chunk after its
# predecessor's last, so all six chunks run two live slots.
LOOP = [([1, 2, 3], 25), ([4] * 3, 9), ([5] * 3, 9), ([6] * 3, 9)]


@pytest.mark.parametrize("family", ["llama", "olmo_hybrid",
                                    "granite_hybrid"])
def test_a_closed_loop_gives_the_serial_tokens_with_every_slot_live(family):
    """(a), (g) 2 x slots requests at once, dense and with per-slot
    state (reset for the newcomer behind the old request's last step):
    token for token the serial schedule's, two admissions ahead and no
    slot-step run for nobody."""
    want, _ = _answers(_family_engine(family, multi_step=False), LOOP)
    got, stats = _answers(_family_engine(family), LOOP)
    assert [len(t) for t in got] == [n for _, n in LOOP]
    assert got == want
    assert stats["admissions_ahead"] == 2 and stats["requests"] == 4
    assert stats["decode_host_syncs"] == 6
    assert stats["decode_steps"] == 6 * CHUNK * 2         # occupancy 100 %
    assert stats["free_slots"] == 2 and stats["kv_used_blocks"] == 0


def test_a_seeded_closed_loop_of_clients_gives_the_serial_tokens():
    """(a) Four clients on two slots over a seeded list of requests,
    one prompt in four of several prefill chunks: every answer is the
    serial schedule's whatever met in a batch, slots were handed over,
    and no row delivered to an ending request is booked on the slot's
    new owner."""
    rng = random.Random(48)
    requests = [([rng.randrange(1, 200)
                  for _ in range(rng.choice([3, 5, 7, 20]))],
                 rng.randrange(2, 22)) for _ in range(24)]

    def run(eng):
        try:
            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                return list(pool.map(
                    lambda r: eng.generate(
                        r[0], max_new_tokens=r[1])["token_ids"],
                    requests)), eng.stats()
        finally:
            eng.close()

    want, _ = run(_dense(multi_step=False, prefill_chunk=8))
    eng = _dense(prefill_chunk=8)
    misbooked = []
    retire = eng._retire_chunk

    def checked_retire(rec):
        out = retire(rec)
        misbooked.extend(
            r for r in eng.scheduler.active
            if eng.kv._slots[r.slot].length != r.length)
        return out

    eng._retire_chunk = checked_retire
    got, stats = run(eng)
    assert got == want
    assert stats["admissions_ahead"] >= 8 and stats["requests"] == 24
    assert not misbooked
    assert stats["free_slots"] == 2 and stats["kv_used_blocks"] == 0


def test_a_chunk_with_an_ending_request_is_retired_in_its_tick(
        serial_tokens):
    """One slot, a waiter whose prompt takes three prefill chunks:
    after the hand-over nobody is active and nothing lands, where the
    loop would go round (or drop the chunk unfetched). The chunk in
    flight is retired in the SAME tick: its request is answered before
    the newcomer's prefill is through, and the slot is parked until
    then."""
    eng = _dense(max_batch=1, prefill_chunk=8)
    try:
        eng.generate(PROMPTS[1], max_new_tokens=2)      # compile
        log = _log_the_loop(eng)
        answered, reqs = [], []
        land = eng._land_prefill
        eng._land_prefill = lambda job: (
            answered.append(bool(reqs) and reqs[0].future.done()),
            land(job))[1]
        reqs += _burst(eng, [(PROMPTS[0], 5), (list(range(1, 21)), 3)])
        got = _tokens(reqs)
        stats = eng.stats()
    finally:
        eng.close()
    assert got[0] == serial_tokens[0][:5] and len(got[1]) == 3
    assert stats["admissions_ahead"] == 1
    # The tick of the hand-over: the waiter's first prefill chunk, then
    # the retire, no chunk dispatched (nobody would be live in it); two
    # ticks on, the last prefill chunk, the chunk it joins, its landing.
    ticks = "".join({"admit": "|", "prefill": "p", "chunk": "c",
                     "retire": "r", "land": "l"}.get(x, "") for x in log)
    assert "|pr|p|pcl" in ticks, ticks
    assert answered[-1] is True


# --------------------------------------------------------------- (b): EOS

def _eos_inside_the_second_chunk(tokens):
    """A position of the second chunk (generated 5..7: not its last
    step) whose token the answer has not held before: as ``eos_id`` it
    ends the request THERE."""
    for j in (5, 6, 7):
        if tokens[j] not in tokens[:j]:
            return j
    raise AssertionError(f"no fresh token in {tokens[:8]}")


@pytest.mark.parametrize("beside", [False, True])
def test_an_eos_ahead_of_the_budget_is_seen_at_the_retire(serial_tokens,
                                                          beside):
    """(b) An EOS inside chunk N is nobody's to foresee: the slot is
    freed at the retire and its waiter seated at the next tick, not
    ahead — alone, and while the neighbour's budget ends in the same
    chunk and ITS slot is handed over."""
    j = _eos_inside_the_second_chunk(serial_tokens[3])
    order = [3, 1, 2, 0] if beside else [3, 1, 2]
    budgets = [30, 9 if beside else 30, 3, 3]
    requests = [(PROMPTS[i], n) for i, n in zip(order, budgets)]
    requests[0] += (serial_tokens[3][j],)
    got, stats = _answers(_dense(), requests)
    assert got[0] == serial_tokens[3][:j + 1]
    assert got[1:] == [serial_tokens[i][:n]
                       for i, n in list(zip(order, budgets))[1:]]
    assert stats["admissions_ahead"] == (1 if beside else 0)
    assert stats["requests"] == len(got)


# ----------------------------------------------------------- (c): row cap

def test_the_row_cap_hands_over_as_the_budget_does():
    """(c) Budgets the rows cannot hold (put past the front door's
    check): the cap ends both requests, the first of them inside a
    chunk the host can name ahead, and the waiter is seated ahead."""
    requests = [(PROMPTS[0], 8), (PROMPTS[1], 8), (PROMPTS[2], 3)]

    def capped(**kw):
        eng = _dense(max_len=32, **kw)
        make = eng._make_request

        def unbounded(prompt, budget, *a, **k):
            req = make(prompt, budget, *a, **k)
            if budget == 8:
                req.max_new_tokens = 1000
            return req

        eng._make_request = unbounded
        return eng

    want, _ = _answers(capped(multi_step=False), requests)
    got, stats = _answers(capped(), requests)
    assert [len(t) for t in got] == [32 - 8, 32 - 4, 3]
    assert got == want
    assert stats["admissions_ahead"] == 1


# ------------------------------------------------------- (d): the prefix

def test_the_lent_slot_keeps_confirmed_rows_and_a_waiter_reuses_them(
        serial_tokens):
    """(d) What the slot keeps for reuse at the hand-over is prompt +
    generated[:-1] as the host knows them THEN — the rows the chunk in
    flight is writing are not in it — and a waiter whose prompt goes on
    from there prefills only the rest."""
    turn = PROMPTS[0] + serial_tokens[0][:4] + [7, 7]
    fresh = _dense(multi_step=False)
    try:
        want = fresh.generate(turn, max_new_tokens=6)["token_ids"]
    finally:
        fresh.close()
    eng = _dense()
    kept = []
    hand_over = eng.scheduler.hand_over

    def watched(req):
        slot, known = req.slot, list(req.generated)
        hand_over(req)
        kept.append((req, known, eng.kv._slots[slot].resident))

    eng.scheduler.hand_over = watched
    try:
        reqs = _burst(eng, [(PROMPTS[0], 9), (PROMPTS[1], 30), (turn, 6)])
        outs = [r.future.result(timeout=120) for r in reqs]
    finally:
        eng.close()
    (req, known, resident), = kept
    assert req is reqs[0] and len(known) == 5
    assert resident == tuple(PROMPTS[0] + known[:-1])
    assert outs[0]["token_ids"] == serial_tokens[0][:9]
    assert outs[2]["cached_prefix_len"] == 12
    assert outs[2]["token_ids"] == want


# ------------------------------------------------------- (e): the bypasses

def _disaggregated(requests):
    """The decode role's installs, four handoffs on two slots."""
    pre, dec = _dense(role="prefill"), _dense(role="decode")
    try:
        handoffs = [pre.prefill_remote(p, max_new_tokens=n)
                    for p, n in requests]
        gate, admit = threading.Event(), dec._admit
        dec._admit = lambda: (gate.wait(30.0), admit())[1]
        reqs = [dec.install_async(h) for h in handoffs]
        gate.set()
        return _tokens(reqs), dec.stats()
    finally:
        pre.close()
        dec.close()


BYPASSES = {
    "a_slot_is_free": lambda r: _answers(_dense(max_batch=4), r[:3]),
    "the_line_is_empty": lambda r: _answers(_dense(), r[:2]),
    "the_serial_schedule": lambda r: _answers(_dense(multi_step=False), r),
    "the_speculative_tick": lambda r: _answers(_dense(spec_draft_len=2), r),
    "the_decode_role": _disaggregated,
}


@pytest.mark.parametrize("why", BYPASSES)
def test_no_slot_is_handed_over(serial_tokens, why):
    """(e) With a slot free, with nobody in line, on a schedule with no
    chunk in flight at the top of a tick and on the decode role every
    finish is seen at its retire, as ever."""
    requests = list(zip(PROMPTS[:4], [9, 5, 6, 3]))
    got, stats = BYPASSES[why](requests)
    assert got == [serial_tokens[i][:n] for i, (_, n) in
                   enumerate(requests[:len(got)])]
    assert stats["admissions_ahead"] == 0


def test_no_slot_is_handed_over_while_every_budget_outlives_the_chunk(
        serial_tokens):
    """(e) A waiter in line and no slot free, chunk after chunk: nobody
    lets go until a budget ends inside the chunk in flight, and then
    one does for the one waiter."""
    eng = _dense()
    calls = []
    hand_over = eng._hand_over

    def watched():
        starved = (eng._inflight is not None and not eng.kv.free_slots()
                   and eng.scheduler.queue_depth() > 0)
        left = [r.remaining() for r in eng.scheduler.active]
        lent = hand_over()
        if starved:
            calls.append((len(lent), min(left)))
        return lent

    eng._hand_over = watched
    got, stats = _answers(eng, [(PROMPTS[0], 21), (PROMPTS[1], 25),
                                (PROMPTS[2], 3)])
    assert got == [serial_tokens[i][:n] for i, n in enumerate([21, 25, 3])]
    assert stats["admissions_ahead"] == 1
    assert calls[-1] == (1, CHUNK) and len(calls) == 5
    assert all(lent == 0 and left > CHUNK for lent, left in calls[:-1])


# ------------------------------------------------ (f): a device failure

@pytest.mark.parametrize("lost", ["the_next_dispatch", "the_fetch"])
def test_a_device_failure_after_the_hand_over(serial_tokens, lost):
    """(f) Between the hand-over and the retire the next chunk's
    dispatch raises — the roster, the ending request and the joiner it
    was dispatched with fail — or the chunk's own fetch does: the
    ending request fails with the roster. The engine goes on serving."""
    eng = _dense()
    armed = []
    hand_over, fetch, chunk = (eng.scheduler.hand_over, eng._fetch,
                               eng.loop.decode_chunk)

    def arming(req):
        armed.append(lost)
        hand_over(req)

    def failing_fetch(tree, tag="decode"):
        if tag == "decode" and armed == ["the_fetch"]:
            armed.clear()
            raise BOOM
        return fetch(tree, tag)

    def failing_chunk(*args):
        if armed == ["the_next_dispatch"]:
            armed.clear()
            raise BOOM
        return chunk(*args)

    eng.scheduler.hand_over = arming
    eng._fetch, eng.loop.decode_chunk = failing_fetch, failing_chunk
    try:
        reqs = _burst(eng, [(PROMPTS[0], 30), (PROMPTS[1], 9),
                            (PROMPTS[2], 5)])
        outcomes = []
        for req in reqs:
            try:
                outcomes.append(req.future.result(60)["token_ids"])
            except RuntimeError as e:
                outcomes.append(e)
        joiner = BOOM if lost == "the_next_dispatch" else serial_tokens[2][:5]
        assert outcomes == [BOOM, BOOM, joiner]
        assert eng.scheduler.ending == [] and eng.scheduler.active == []
        assert eng.generate(PROMPTS[3], 4)["token_ids"] \
            == serial_tokens[3][:4]
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["admissions_ahead"] == 1 and stats["cache_rebuilds"] == 0
    assert stats["free_slots"] == 2


# ------------------------------------------------------- (h): priorities

def test_a_higher_class_in_line_takes_the_lent_slot_first(serial_tokens):
    """(h) Two waiters, the later of the higher class: it is seated in
    the first slot handed over, the earlier one in the next."""
    eng = _dense()
    try:
        reqs = _burst(eng, [(PROMPTS[0], 30, None, 2),
                            (PROMPTS[1], 9, None, 2),
                            (PROMPTS[2], 3, None, 0),
                            (PROMPTS[3], 3, None, 1)])
        got = _tokens(reqs)
        stats = eng.stats()
    finally:
        eng.close()
    assert got == [serial_tokens[i][:n]
                   for i, n in enumerate([30, 9, 3, 3])]
    assert reqs[3].first_token_t < reqs[2].first_token_t
    assert stats["admissions_ahead"] == 2 and stats["preempts"] == 0


# ------------------------------------------- the benchmark's one reader

@pytest.mark.parametrize("start,end,want", [
    ({"admissions_ahead": 3, "prefix_hits": 1, "prefix_misses": 9},
     {"admissions_ahead": 22, "prefix_hits": 5, "prefix_misses": 25}, 95.0),
    ({"prefix_hits": 1, "prefix_misses": 9},                # the parent
     {"prefix_hits": 5, "prefix_misses": 25}, None),
    ({"admissions_ahead": 0, "prefix_hits": 1, "prefix_misses": 9},
     {"admissions_ahead": 0, "prefix_hits": 1, "prefix_misses": 9}, None),
])
def test_the_share_of_admissions_made_ahead(start, end, want):
    read = importlib.import_module(
        "benchmark.metrics.admissions_ahead_pct").read
    assert read({"counters": {"start": start, "end": end}}) == want
    assert read({"counters": None}) is None
