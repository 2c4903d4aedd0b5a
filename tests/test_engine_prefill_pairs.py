"""The tick's PAIRED prefill (`core.py` ``_partner``, ``_dispatch_prefill``
with a partner; `decode_loop.py` ``prefill_pair``; `models/llama.py`
``forward_last_rows_with_cache``): two waiting prompts, each at the only
chunk of its plan and short enough that twice the larger of their two
buckets is at most `_PAIR_ROWS`, go out as ONE program over
``[2, bucket]``.

On the CPU, at a tiny float32 size: what is proved here is the
program's rows and tokens against two single programs', the rule, the
bookkeeping and the order of compilations; nothing here proves speed.
"""

from __future__ import annotations

import hashlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core.config import GLOBAL_CONFIG as global_cfg
from ray_tpu.devtools import jax_debug
from ray_tpu.models import llama
from ray_tpu.serve.engine import core
from ray_tpu.serve.engine.core import InferenceEngine, _PrefillJob
from ray_tpu.serve.engine.decode_loop import DecodeLoop
from ray_tpu.serve.engine.scheduler import Admission, EngineRequest
from ray_tpu.util import tracing
from tests.test_engine_handover import _burst as _burst_requests, _tokens
from tests.test_engine_tick_phases import _every_fetch_waits

# 128 and 256 pair under the rule (2 x 256 = `_PAIR_ROWS`), 512 does not.
BUCKETS = [128, 256, 512]
ROWS = 640
CHUNK = 4
CFG = llama.tiny_config(max_seq_len=ROWS)
BOOM = RuntimeError("device lost")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(3))


def _prompt(seed: int, n: int) -> list:
    rng = np.random.default_rng([53, seed])
    return [int(t) for t in rng.integers(1, CFG.vocab_size, n)]


def _engine(params, cfg=CFG, **kw):
    eng = InferenceEngine(cfg, params, **{
        "max_batch": 4, "max_len": ROWS, "prompt_buckets": BUCKETS,
        "decode_chunk": CHUNK, "prefix_block": 16,
        "kv_fleet_min_prefix_blocks": -1, **kw})
    eng._listen_deadline = lambda rec: None     # listens where steered
    return eng


def _burst(eng, prompts, budget: int = 6):
    """Every prompt in the mailbox before the thread admits one."""
    return _burst_requests(eng, [(p, budget) for p in prompts])


PAIR_KEYS = ("prefill_pairs", "prefill_chunks_dispatched",
             "prefill_chunk_tokens", "prefill_split", "requests")


def _delta(eng, before):
    after = eng.stats()
    return {k: after[k] - before[k] for k in PAIR_KEYS}


@pytest.fixture(scope="module")
def alone(params):
    """prompt -> the tokens of that prompt prefilled ALONE, on an engine
    that has met no other (every prompt of this file is drawn once)."""
    eng = _engine(params)
    seen = {}

    def ask(prompt, budget=6):
        key = (tuple(prompt), budget)
        if key not in seen:
            seen[key] = eng.generate(prompt, max_new_tokens=budget)[
                "token_ids"]
        return seen[key]

    yield ask
    assert eng.stats()["prefill_pairs"] == 0
    eng.close()


@pytest.fixture(scope="module")
def eng(params):
    eng = _engine(params)
    _every_fetch_waits(eng)
    yield eng
    eng.close()


# --------------------------------------------------------------- the program

@pytest.fixture(scope="module")
def loop():
    return DecodeLoop(CFG, max_len=ROWS, chunk=CHUNK)


@pytest.mark.parametrize("case", ["two_of_one_bucket",
                                  "a_small_one_in_its_partners_bucket"])
def test_the_paired_program_writes_what_two_single_programs_write(
        loop, params, case):
    """The two tokens and the two slots' rows are those of two single
    programs, with one row starting past a resident prefix
    (``cache_index`` > 0), different ``last``, and in the second case
    the shorter prompt padded to its partner's larger bucket; no other
    slot's row is touched."""
    put = jax.device_put
    (na, own_a, at_a), (nb, own_b, at_b) = {
        "two_of_one_bucket": ((100, 128, 32), (77, 128, 0)),
        "a_small_one_in_its_partners_bucket": ((60, 128, 16), (200, 256, 0)),
    }[case]
    bucket = max(own_a, own_b)
    slots = (2, 0)
    rng = np.random.default_rng(7)
    shape = llama.init_kv_cache(CFG, 4, ROWS)["k"].shape

    def dirty():    # every slot holds somebody's old rows
        r = np.random.default_rng(11)
        return {k: put(r.standard_normal(shape).astype(np.float32))
                for k in ("k", "v")}

    prompts = [rng.integers(1, CFG.vocab_size, n).astype(np.int32)
               for n in (na, nb)]

    def padded(rows, width):
        out = np.zeros((len(rows), width), np.int32)
        for i, p in enumerate(rows):
            out[i, :len(p)] = p
        return put(out)

    cache, single = dirty(), []
    for p, own, at, slot in zip(prompts, (own_a, own_b), (at_a, at_b), slots):
        token, cache = loop.prefill_inplace(
            params, cache, padded([p], own), put(np.int32(slot)),
            put(np.int32(at)), put(np.int32(len(p) - 1)))
        single.append(int(token[0]))
    (ta, tb), paired = loop.prefill_pair(
        params, dirty(), padded(prompts, bucket),
        put(np.array(slots, np.int32)), put(np.array([at_a, at_b], np.int32)),
        put(np.array([na - 1, nb - 1], np.int32)))
    assert ta.shape == tb.shape == (1,) and ta.dtype == jnp.int32
    assert [int(ta[0]), int(tb[0])] == single
    before = dirty()
    for key in ("k", "v"):
        got, want, was = (np.asarray(c[key]) for c in (paired, cache, before))
        for slot, n, at in zip(slots, (na, nb), (at_a, at_b)):
            # The prompt's own rows; and the rows under them, resident
            # before either program ran, as they were.
            np.testing.assert_allclose(got[:, slot, :, at:at + n],
                                       want[:, slot, :, at:at + n],
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(got[:, slot, :, :at],
                                          was[:, slot, :, :at])
            # Padding is written up to the PAIR's bucket and no further.
            np.testing.assert_array_equal(got[:, slot, :, at + bucket:],
                                          was[:, slot, :, at + bucket:])
        for other in set(range(4)) - set(slots):
            np.testing.assert_array_equal(got[:, other], was[:, other])


def test_the_rows_forward_reads_each_row_under_its_own_length(params):
    """`forward_last_rows_with_cache` row by row against
    `forward_last_with_cache`: a row's keys end at ITS index + T,
    whatever lies in the rows its partner has and it has not."""
    rng = np.random.default_rng(5)
    rows = {k: jnp.asarray(rng.standard_normal(
        (CFG.n_layers, 2, CFG.n_kv_heads, 96, CFG.head_dim)), jnp.float32)
        for k in ("k", "v")}
    tokens = jnp.asarray(rng.integers(1, CFG.vocab_size, (2, 16)), jnp.int32)
    index, last = jnp.array([40, 8], jnp.int32), jnp.array([15, 3], jnp.int32)
    logits, new = llama.forward_last_rows_with_cache(
        params, tokens, rows, index, last, CFG)
    for b in range(2):
        one = {k: v[:, b:b + 1] for k, v in rows.items()}
        want, cache = llama.forward_last_with_cache(
            params, tokens[b:b + 1], one, index[b], last[b], CFG)
        np.testing.assert_allclose(logits[b], want[0], rtol=1e-5, atol=1e-5)
        for k in ("k", "v"):
            np.testing.assert_allclose(new[k][:, b], cache[k][:, 0],
                                       rtol=1e-5, atol=1e-5)


# What `jax.jit(f).lower(shapes).as_text()` read at the parent commit
# (10fae2f), at `llama.tiny_config()`: the training step's programs
# (`parallel/spmd.py`, `parallel/pipeline.py`) trace `_block` without a
# cache, and the tick's single prefill with one index for all rows.
LOWERED_AT_THE_PARENT = {
    "forward": "5107fec9c47d583f3c3ac89b0b847610f1dcc27f2d70d9bd03dbf549839b4f74",
    "block_without_a_cache":
        "8b09a569221112635b2635f7f80c0fa92e812eb162ad30ed2b04e99853f7d262",
    "forward_last_with_cache":
        "5f420600ab184da1c419c048b9a870b74dea6f5dfa100b2d891239792cd595ce",
}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the texts were taken under jax 0.9.0")
@pytest.mark.parametrize("program", sorted(LOWERED_AT_THE_PARENT))
def test_the_paths_with_one_index_lower_to_the_text_they_had(program):
    """A per-row ``cache_index`` is a branch of `_block`'s cache path
    alone: the cache-less block, the whole forward and the single
    prefill lower to the programs they lowered to."""
    cfg = llama.tiny_config()
    sds = jax.ShapeDtypeStruct
    weights = jax.eval_shape(functools.partial(llama.init_params, cfg),
                             jax.random.PRNGKey(0))
    scalar = sds((), jnp.int32)
    # (The module's name is in the text: the functions' names are the
    # ones the texts were taken under.)
    def whole(p, t):
        return llama.forward(p, t, cfg)

    def block(x, layer, pos):
        return llama._block(x, layer, pos, cfg, None)[0]

    def last(p, t, c, i, l):
        return llama.forward_last_with_cache(p, t, c, i, l, cfg)

    if program == "forward":
        fn, args = whole, (weights, sds((2, 32), jnp.int32))
    elif program == "block_without_a_cache":
        layer = jax.tree.map(lambda a: sds(a.shape[1:], a.dtype),
                             weights["blocks"])
        fn, args = block, (sds((2, 32, cfg.d_model), cfg.dtype), layer,
                           sds((2, 32), jnp.int32))
    else:
        cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 1, 64))
        fn, args = last, (weights, sds((1, 32), jnp.int32), cache, scalar,
                          scalar)
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LOWERED_AT_THE_PARENT[program]


# ------------------------------------------------------------------ the rule

def _job(n: int, bucket: int, pos: int = 0, chunks=None) -> _PrefillJob:
    req = EngineRequest(list(range(1, pos + n + 1)), 4)
    return _PrefillJob(Admission(req, 0, pos, bucket,
                                 chunks=chunks or [(n, bucket)]), pos)


@pytest.fixture(scope="module")
def rule(params):
    """`_partner` of an engine whose thread has ended."""
    eng = _engine(params)
    eng.close()

    def partner(job, behind):
        eng._prefilling = [job, *behind]
        return eng._partner(job, behind)

    return partner


@pytest.mark.parametrize("own,others,want", [
    (128, [128], 0), (128, [256], 0), (256, [128], 0), (256, [256], 0),
    (128, [512], None), (256, [512], None), (512, [128, 512], None),
    # The same bucket before the neighbouring one, else the next behind.
    (128, [256, 128], 1), (256, [512, 128, 256], 2), (128, [512, 256], 1),
    (128, [], None),
])
def test_two_jobs_pair_when_twice_the_larger_bucket_is_within_the_rule(
        rule, own, others, want):
    assert core._PAIR_ROWS == 512
    behind = [_job(b - 3, b) for b in others]
    got = rule(_job(own - 5, own), behind)
    assert got is (None if want is None else behind[want])


def test_a_prompt_padded_to_its_partners_bucket_must_end_within_its_rows(
        rule):
    """The scheduler's plan keeps ``pos + bucket`` inside the slot for
    the job's OWN bucket; a pair's larger bucket is held to the same
    bound, for both: an overrun is clamped backwards over resident
    rows."""
    hit = _job(100, 128, pos=400)               # 400 + 128 <= 640
    assert rule(hit, [_job(200, 256)]) is None  # 400 + 256 > 640
    assert rule(_job(200, 256), [hit]) is None
    fits = _job(100, 128)
    assert rule(hit, [_job(200, 256), fits]) is fits    # 400 + 128
    snug = _job(100, 128, pos=ROWS - 256)
    mate = _job(200, 256)
    assert rule(snug, [mate]) is mate


def test_only_a_job_at_the_one_chunk_of_its_plan_pairs(rule):
    chunked = _job(128, 128, chunks=[(128, 128), (60, 128)])
    short = _job(50, 128)
    assert rule(chunked, [short]) is None
    assert rule(short, [chunked]) is None
    gone = _job(50, 128)
    gone.idx = 1                    # went out as an earlier job's partner
    assert rule(short, [gone]) is None


# ------------------------------------------------------------------ the tick

@pytest.mark.parametrize("na,nb", [(100, 90), (60, 200), (250, 130)])
def test_two_prompts_that_wait_together_are_one_program(eng, alone, na, nb):
    pa, pb = _prompt(na, na), _prompt(nb, nb)
    before = eng.stats()
    got = _tokens(_burst(eng, [pa, pb]))
    assert got == [alone(pa), alone(pb)]
    assert _delta(eng, before) == {
        "prefill_pairs": 1, "prefill_chunks_dispatched": 1,
        "prefill_chunk_tokens": na + nb, "prefill_split": 2, "requests": 2}


def test_three_that_wait_are_a_pair_and_a_single(eng, alone):
    """The first pairs with the next behind it OF ITS BUCKET; the one
    between them goes out alone, behind the pair."""
    prompts = [_prompt(1, 70), _prompt(2, 180), _prompt(3, 95)]
    before = eng.stats()
    got = _tokens(_burst(eng, prompts))
    assert got == [alone(p) for p in prompts]
    assert _delta(eng, before) == {
        "prefill_pairs": 1, "prefill_chunks_dispatched": 2,
        "prefill_chunk_tokens": 70 + 180 + 95, "prefill_split": 3,
        "requests": 3}
    # The single one queued behind the pair's ONE program; the pair's
    # two behind none of their own.
    s = eng.stats()
    assert s["prefill_ahead_prefills"] - before["prefill_ahead_prefills"] == 1


def test_a_prefix_hit_pairs_from_where_its_rows_end(eng, alone):
    """``cache_index`` > 0 through the engine: the prefix cache is
    always on, and a hit's suffix starts a row past 0."""
    first = _prompt(4, 330)
    eng.generate(first, max_new_tokens=2)
    again = first[:320] + _prompt(5, 70)        # 320 rows resident
    other = _prompt(6, 110)
    before = eng.stats()
    reqs = _burst(eng, [again, other])
    got = _tokens(reqs)
    assert reqs[0].cached_len == 320
    assert got == [alone(again), alone(other)]
    d = _delta(eng, before)
    assert d["prefill_pairs"] == 1 and d["prefill_chunk_tokens"] == 70 + 110


@pytest.mark.parametrize("na,nb", [(300, 310), (200, 400)])
def test_buckets_past_the_rule_go_out_one_by_one(eng, alone, na, nb):
    pa, pb = _prompt(na, na), _prompt(nb, nb)
    before = eng.stats()
    assert _tokens(_burst(eng, [pa, pb])) == [alone(pa), alone(pb)]
    assert _delta(eng, before) == {
        "prefill_pairs": 0, "prefill_chunks_dispatched": 2,
        "prefill_chunk_tokens": na + nb, "prefill_split": 2, "requests": 2}


def test_one_waiting_job_goes_out_alone(eng, alone):
    p = _prompt(7, 88)
    before = eng.stats()
    assert eng.generate(p, max_new_tokens=6)["token_ids"] == alone(p)
    d = _delta(eng, before)
    assert d["prefill_pairs"] == 0 and d["prefill_chunks_dispatched"] == 1


def test_a_plan_of_several_chunks_does_not_pair(params, alone):
    chunked = _engine(params, prefill_chunk=128)
    try:
        long, short = _prompt(8, 200), _prompt(9, 50)   # 128 + 72 | 50
        got = _tokens(_burst(chunked, [long, short]))
        s = chunked.stats()
    finally:
        chunked.close()
    assert got == [alone(long), alone(short)]
    assert s["prefill_pairs"] == 0 and s["prefill_chunks_dispatched"] == 3


def test_arrivals_heard_in_the_listening_wait_go_out_one_by_one(params,
                                                                 alone):
    """`_hear` admits ONE arrival and dispatches it at once: nobody
    waits beside it."""
    eng = _engine(params)
    try:
        stream = eng.generate_stream(_prompt(10, 20), max_new_tokens=400)
        for _ in range(5):
            next(stream)
        listening = threading.Event()
        get = eng._queue.get

        def heard_get(*args, **kwargs):
            if kwargs.get("timeout"):
                listening.set()
            return get(*args, **kwargs)

        eng._queue.get = heard_get
        eng._chunk_done = lambda rec: eng.metrics.admissions_heard >= 2
        eng._listen_deadline = lambda rec: time.perf_counter() + 5.0
        assert listening.wait(10.0)
        before = eng.stats()
        prompts = [_prompt(11, 40), _prompt(12, 45)]
        reqs = [eng._make_request(p, 6, None) for p in prompts]
        for req in reqs:
            eng._queue.put(req)
        got = _tokens(reqs)
        s = eng.stats()
    finally:
        eng.close()
    assert got == [alone(p) for p in prompts]
    assert s["admissions_heard"] - before["admissions_heard"] == 2
    assert s["prefill_pairs"] == 0
    assert (s["prefill_chunks_dispatched"]
            - before["prefill_chunks_dispatched"]) == 2


class _Compiles:
    """Backend compiles of the tick's prefill programs, as JAX reports
    them (what `util/compile_cache.py`'s account counts)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, _secs, fun_name=None, **_kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and fun_name == "jit(prefill)"):
            self.n += 1


@pytest.fixture
def compiles():
    seen = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(seen)
    yield seen
    jax.monitoring.unregister_event_duration_listener(seen)


def test_a_family_without_the_rows_forward_runs_the_programs_it_ran(compiles):
    """An engine of a module that lacks `forward_last_rows_with_cache`
    has no paired program, pairs nobody and compiles one prefill a
    bucket, where it first dispatches it: what it did."""
    from tests.test_engine_compat_matrix import _family_cfg

    cfg = _family_cfg("olmo_hybrid")
    assert not hasattr(cfg.model, "forward_last_rows_with_cache")
    weights = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8]]

    def answers(together):
        eng = InferenceEngine(
            cfg, weights, max_batch=2, max_len=128, prompt_buckets=[8, 16],
            decode_chunk=CHUNK, prefix_block=8, kv_fleet_min_prefix_blocks=-1)
        eng._listen_deadline = lambda rec: None
        try:
            assert eng.loop.prefill_pair is None
            if together:
                return _tokens(_burst(eng, prompts)), eng.stats()
            return [eng.generate(p, max_new_tokens=6)["token_ids"]
                    for p in prompts], eng.stats()
        finally:
            eng.close()

    apart, _ = answers(False)
    at = compiles.n
    together, s = answers(True)
    assert together == apart
    assert compiles.n - at == 1             # one bucket met, one program
    assert s["prefill_pairs"] == 0 and s["prefill_chunks_dispatched"] == 2


def test_a_buckets_two_programs_are_compiled_where_it_is_first_met(
        params, compiles, alone):
    """Whichever way a bucket's first prompt goes out, that dispatch
    compiles the bucket's single AND paired program, from shapes: a
    later pair, or a later single, finds its program compiled (nothing
    is compiled inside a timed window)."""
    one, later = _prompt(13, 66), _prompt(18, 199)
    pair = [_prompt(14, 101), _prompt(15, 99)]
    mixed = [_prompt(16, 30), _prompt(17, 222)]
    # (`alone`'s engine compiles its own programs: asked before the count.)
    want = {tuple(p): alone(p) for p in [one, later, *pair, *mixed]}
    eng = _engine(params)
    try:
        at = compiles.n
        assert eng.generate(one, max_new_tokens=6)["token_ids"] \
            == want[tuple(one)]
        assert compiles.n - at == 2         # [1, 128] and [2, 128]
        assert _tokens(_burst(eng, pair)) == [want[tuple(p)] for p in pair]
        assert eng.stats()["prefill_pairs"] == 1 and compiles.n - at == 2
        # A small prompt whose first dispatch is in its partner's
        # bucket: the 256 bucket's two are compiled there.
        assert _tokens(_burst(eng, mixed)) == [want[tuple(p)] for p in mixed]
        assert eng.stats()["prefill_pairs"] == 2 and compiles.n - at == 4
        assert eng.generate(later, max_new_tokens=6)["token_ids"] \
            == want[tuple(later)]
        assert compiles.n - at == 4
        # A bucket past the rule has one program, compiled at its
        # first dispatch as ever.
        eng.generate(_prompt(19, 300), max_new_tokens=2)
        assert compiles.n - at == 5
    finally:
        eng.close()


def test_a_pair_that_raises_fails_both_and_nobody_else(params, alone):
    eng = _engine(params)
    try:
        bystander = eng.generate_stream(_prompt(20, 25), max_new_tokens=60)
        first = [next(bystander) for _ in range(5)]
        pair, calls = eng.loop.prefill_pair, []

        def raising(*args):
            calls.append(1)
            raise BOOM

        eng.loop.prefill_pair = raising
        reqs = _burst(eng, [_prompt(21, 40), _prompt(22, 50)])
        for req in reqs:
            with pytest.raises(RuntimeError, match="device lost"):
                req.future.result(timeout=60)
        assert calls == [1]
        eng.loop.prefill_pair = pair
        rest = first + list(bystander)
        assert rest == alone(_prompt(20, 25), 60)
        s = eng.stats()
        assert s["cache_rebuilds"] == 0 and s["free_slots"] == 4
        nxt = [_prompt(23, 44), _prompt(24, 55)]
        assert _tokens(_burst(eng, nxt)) == [alone(p) for p in nxt]
        assert eng.stats()["prefill_pairs"] == 1
    finally:
        eng.close()


def test_the_witness_counts_one_paired_program_a_bucket(params, monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_JAX", "1")
    jax_debug.reset()
    eng = _engine(params)
    try:
        for seed, (na, nb) in enumerate([(90, 80), (70, 100), (200, 90),
                                         (33, 140)]):
            _tokens(_burst(eng, [_prompt(30 + seed, na),
                                 _prompt(40 + seed, nb)]))
        s = eng.stats()
    finally:
        eng.close()
        reports = jax_debug.over_budget_reports()
        jax_debug.reset()
    assert s["prefill_pairs"] == 4
    # Two buckets' paired programs, no single one ever dispatched, and
    # no signature beyond the budget of one a bucket.
    assert s["compiled_programs"]["prefill_pair"] == 2
    assert s["compiled_programs"]["prefill_inplace"] == 0
    assert reports == []


def test_a_pairs_spans_and_phase_say_two_rows(params):
    got = []
    tracing.flush()
    tracing.set_sink(got.extend)
    old = global_cfg.get("tracing_enabled")
    global_cfg.set("tracing_enabled", True)
    eng = _engine(params)
    _every_fetch_waits(eng)
    try:
        with tracing.trace("test.pair"):
            reqs = _burst(eng, [_prompt(50, 61), _prompt(51, 160)])
        _tokens(reqs)
        with tracing.trace("test.single"):
            eng.generate(_prompt(52, 45), max_new_tokens=2)
    finally:
        eng.close()
        tracing.flush()
        global_cfg.set("tracing_enabled", old)
        tracing.set_sink(None)

    def named(name):
        return [s["attrs"] for s in got if s["name"] == name]

    prefills = named("engine.prefill")
    assert [a["rows"] for a in prefills] == [2, 2, 1]
    assert sorted(a["prefill_tokens"] for a in prefills[:2]) == [61, 160]
    assert {a["bucket"] for a in prefills[:2]} == {256}
    # Neither of a pair counts its partner ahead of it.
    assert [a["ahead_prefills"] for a in prefills[:2]] == [0, 0]
    dispatches = named("engine.tick.prefill_dispatch")
    assert [(a["rows"], a["tokens"], a["bucket"]) for a in dispatches] \
        == [(2, 61 + 160, 256), (1, 45, 128)]
    device = named("device.prefill")
    assert [a["rows"] for a in device] == [2, 1]
    # One entry of the device's queue that both admissions hold: the
    # same seconds behind and on the device for both.
    assert prefills[0]["own_s"] == prefills[1]["own_s"] == device[0]["own_s"]
