"""Engine feature compatibility matrix + chunked-prefill/multi-step
behavior.

The engine's compounding performance knobs — speculative decoding
(PR 3), weight-only int8 (PR 6), chunked prefill, and multi-step
double-buffered ticks — all share ONE correctness contract: greedy
output is token-identical to the plain engine (int8 compares within the
same quantized weights, since quantization itself legitimately changes
logits). All 8 combinations of the first three run in tier 1.
"""

import concurrent.futures as cf
import threading
import time

import pytest

jax = pytest.importorskip("jax")

PROMPTS = [
    [7] * 12,                 # repetitive: prompt lookup drafts
    list(range(2, 32)),       # 30 tokens: chunks under prefill_chunk=8
    [9, 8, 7] * 6,            # mid-length repetitive
    [1, 2, 3],                # short
    list(range(2, 32)),       # repeat: exercises prefix reuse mid-run
]
N_NEW = 16  # long enough for prompt lookup to latch onto repetition


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models import llama

    cfg = llama.tiny_config(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _run(tiny_model, **kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", [8, 16])
    kw.setdefault("prefix_block", 8)
    eng = LLMEngine(cfg, params, **kw)
    try:
        outs = [eng.generate(p, max_new_tokens=N_NEW)["token_ids"]
                for p in PROMPTS]
        stats = eng.stats()
    finally:
        eng.close()
    return outs, stats


@pytest.fixture(scope="module")
def baselines(tiny_model):
    """Plain-engine greedy outputs per quantization level (multi-step
    off: the pre-PR schedule is the ground truth the new knobs must
    reproduce)."""
    return {
        None: _run(tiny_model, multi_step=False)[0],
        "int8": _run(tiny_model, multi_step=False, quantize="int8")[0],
    }


def _combo_kw(spec, quant, chunked):
    kw = {}
    if spec:
        kw.update(spec_draft_len=spec, spec_chunk=2)
    if quant:
        kw.update(quantize=quant)
    if chunked:
        kw.update(prefill_chunk=chunked)
    return kw


FULL_COMBOS = [(s, q, c)
               for s in (0, 2) for q in (None, "int8") for c in (0, 8)]


@pytest.mark.parametrize("spec,quant,chunked", FULL_COMBOS)
def test_feature_combo_token_identity_full(tiny_model, baselines, spec,
                                           quant, chunked):
    outs, stats = _run(tiny_model, **_combo_kw(spec, quant, chunked))
    assert outs == baselines[quant], (spec, quant, chunked)
    if spec:
        assert stats["spec_chunks"] > 0   # the verify path really ran
    if chunked:
        # 30-token prompt, chunk 8: intermediate chunks dispatched
        # without a fetch — prefill syncs stay one per admission, so
        # prefill token counts are the only chunking trace here.
        assert stats["prefill_tokens"] > 0


# --------------------------------------------------------- multi-step


def test_multi_step_token_identity_and_sync_parity(tiny_model,
                                                   baselines):
    """The double-buffered schedule delivers identical tokens with the
    identical host-sync count (the witness invariant: one sync per
    FETCHED chunk — pipelining moves the sync, never adds one)."""
    outs_on, stats_on = _run(tiny_model, multi_step=True)
    _, stats_off = _run(tiny_model, multi_step=False)
    assert outs_on == baselines[None]
    assert (stats_on["decode_host_syncs"]
            == stats_off["decode_host_syncs"])


def test_multi_step_pipelines_dispatch_ahead_of_fetch(tiny_model):
    """Steady-state decode must dispatch chunk N+1 BEFORE fetching
    chunk N (the observable double-buffer), with the SAME dispatch and
    fetch counts as the serial schedule: a budget-bound burst wastes
    nothing, because the engine skips the speculative dispatch once no
    request's remaining budget can outlive the in-flight chunk."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    events = {}
    for multi_step in (True, False):
        eng = LLMEngine(cfg, params, max_batch=1, max_len=64,
                        prompt_buckets=[8], decode_chunk=4,
                        multi_step=multi_step)
        log = events.setdefault(multi_step, [])
        inner_dispatch = eng.loop.decode_chunk
        inner_fetch = eng._fetch

        def dispatch(*a, _i=inner_dispatch, _log=log, **kw):
            _log.append("d")
            return _i(*a, **kw)

        def fetch(tree, tag="decode", _i=inner_fetch, _log=log):
            if tag == "decode":
                _log.append("f")
            return _i(tree, tag)

        eng.loop.decode_chunk = dispatch
        eng._fetch = fetch
        try:
            out = eng.generate([1, 2, 3], max_new_tokens=13)
        finally:
            eng.close()
        assert out["num_generated"] == 13
    # Identical work: 3 dispatches, 3 fetches (ceil(12/4)) both ways …
    assert sorted(events[True]) == sorted(events[False]) == \
        ["d", "d", "d", "f", "f", "f"]
    # … but multi-step enqueues the second chunk BEFORE fetching the
    # first, while the serial schedule strictly alternates.
    assert events[True] == ["d", "d", "f", "d", "f", "f"]
    assert events[False] == ["d", "f", "d", "f", "d", "f"]


def test_multi_step_roster_churn_under_concurrency(tiny_model):
    """Requests joining and finishing mid-burst (slot recycling, prefix
    reuse, staggered lengths) must not lose or duplicate tokens when
    chunks are retired one behind dispatch."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    want = {}
    for ms in (False, True):
        eng = LLMEngine(cfg, params, max_batch=2, max_len=64,
                        prompt_buckets=[8, 16], decode_chunk=4,
                        multi_step=ms)
        lens = [5, 9, 13, 7, 11, 6]
        prompts = [[i + 1] * 3 for i in range(6)]
        try:
            with cf.ThreadPoolExecutor(6) as pool:
                futs = [pool.submit(eng.generate, p, n)
                        for p, n in zip(prompts, lens)]
                outs = [f.result(timeout=300)["token_ids"]
                        for f in futs]
        finally:
            eng.close()
        want[ms] = outs
        for n, o in zip(lens, outs):
            assert len(o) == n
    assert want[True] == want[False]


# ---------------------------- the pipelined schedule across a changed roster

def _tiny_llama_128():
    from ray_tpu.models import llama

    return llama.tiny_config(max_seq_len=128)


def _family_cfg(family):
    import dataclasses

    if family == "llama":
        return _tiny_llama_128()
    if family == "ouro":
        # Rows and no slot state, like llama: not a row of FAMILIES.
        from ray_tpu.models import ouro

        return ouro.tiny_config(vocab_size=64, d_model=24, n_heads=2,
                                n_kv_heads=2, head_dim=12, d_ff=32,
                                n_layers=2, n_loops=3)
    return dataclasses.replace(FAMILIES[family](), max_seq_len=128)


class _Churn:
    """One engine under a roster that changes all the time, every
    schedule decision made on the engine thread so that two runs see
    the same arrivals: requests are put in order while the first
    admission waits behind a gate, and a second wave is put from inside
    the third decode fetch. ``log`` holds ``d`` per decode-chunk
    dispatch and ``f`` per decode fetch; ``done_in`` the ``done`` mask
    each dispatch was handed."""

    def __init__(self, cfg, params=None, *, multi_step, max_batch=2,
                 second_wave=(), **kw):
        from ray_tpu.serve.engine.core import InferenceEngine

        kw.setdefault("prefill_chunk", 8)
        self.eng = eng = InferenceEngine(
            cfg, params, max_batch=max_batch, max_len=128,
            prompt_buckets=[8, 16], decode_chunk=4, prefix_block=8,
            multi_step=multi_step, kv_fleet_min_prefix_blocks=-1, **kw)
        self.log, self.done_in, self.reqs = [], [], []
        self.gate = threading.Event()
        self.on_fetch = {3: lambda: self.put(second_wave)}
        fetches = [0]
        inner_dispatch, inner_fetch, admit = (
            eng.loop.decode_chunk, eng._fetch, eng._admit)

        def dispatch(params, cache, tokens, lengths, remaining, eos, done):
            self.log.append("d")
            self.done_in.append([bool(x) for x in jax.device_get(done)])
            return inner_dispatch(params, cache, tokens, lengths,
                                  remaining, eos, done)

        def fetch(tree, tag="decode"):
            if tag == "decode":
                self.log.append("f")
                fetches[0] += 1
                self.on_fetch.pop(fetches[0], lambda: None)()
            return inner_fetch(tree, tag)

        def gated_admit():
            self.gate.wait(60)
            admit()

        eng.loop.decode_chunk, eng._fetch, eng._admit = (
            dispatch, fetch, gated_admit)

    def put(self, requests):
        for prompt, n, *rest in requests:
            req = self.eng._make_request(prompt, n, *(rest or [None]))
            self.reqs.append(req)
            self.eng._queue.put(req)

    def run(self, requests):
        try:
            self.put(requests)
            self.gate.set()
            outs = [r.future.result(timeout=300)["token_ids"]
                    for r in list(self.reqs)]
            # The second wave was put while the first ran.
            outs += [r.future.result(timeout=300)["token_ids"]
                     for r in self.reqs[len(outs):]]
            return outs, self.eng.stats()
        finally:
            self.eng.close()


# The anchor outlives everyone, so some request can always outlive the
# chunk in flight and both schedules run the same number of chunks;
# the others turn their slots over beside it, two of them on one prompt
# (prefix reuse where the family and the slot allow it) that prefills
# in three chunks.
ANCHOR = ([1, 2, 3], 120)
LONG = list(range(2, 22))
FIRST_WAVE = [ANCHOR, ([4] * 3, 5), ([5] * 3, 13), ([6] * 3, 7), (LONG, 9)]
SECOND_WAVE = [(LONG, 11), ([7] * 3, 6), ([8] * 5, 2)]
CHURN_CASES = [("llama", 2), ("llama", 3), ("llama", 4),
               ("olmo_hybrid", 2), ("minicpm_sala", 3), ("zaya", 3),
               ("granite_hybrid", 2), ("kimi_linear", 2), ("ouro", 2)]


@pytest.mark.parametrize("family,max_batch", CHURN_CASES)
def test_pipelined_dispatch_leads_every_fetch_under_churn(family,
                                                          max_batch):
    """Admissions and finishes change the roster on nearly every tick;
    chunk N+1 is still dispatched before chunk N is fetched on EVERY
    tick that has a chunk in flight, the tokens are the serial
    schedule's, and no sync is added. A family with slot state gives
    the same tokens too: a slot frozen in the carried mask is not
    stepped."""
    cfg = _family_cfg(family)
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    runs = {}
    for ms in (False, True):
        churn = _Churn(cfg, params, multi_step=ms, max_batch=max_batch,
                       second_wave=SECOND_WAVE)
        runs[ms] = (*churn.run(FIRST_WAVE), churn.log)
    (want, serial, serial_log), (got, piped, log) = runs[False], runs[True]
    assert [len(o) for o in got] == [n for _, n in FIRST_WAVE + SECOND_WAVE]
    assert got == want
    n = piped["decode_chunks_dispatched"]
    assert serial_log == ["d", "f"] * serial["decode_chunks_dispatched"]
    assert log == ["d"] + ["d", "f"] * (n - 1) + ["f"]
    assert piped["decode_host_syncs"] <= serial["decode_host_syncs"]
    assert serial["decode_chunks_carried"] == 0
    assert piped["decode_chunks_carried"] == n - 1 > 0.9 * n
    assert piped["requests"] == serial["requests"] == 8
    # The roster did change: a request ended or joined at nearly every
    # chunk boundary of the churn, far more often than it stood still.
    assert piped["prefix_tokens_reused"] == serial["prefix_tokens_reused"]
    if (family, max_batch) == ("llama", 2):
        # One slot beside the anchor's: the repeat follows its twin.
        assert piped["prefix_tokens_reused"] == 16


def _first_token(cfg, params, prompt):
    from ray_tpu.serve.engine.core import InferenceEngine

    eng = InferenceEngine(cfg, params, max_batch=1, max_len=128,
                          prompt_buckets=[8, 16],
                          kv_fleet_min_prefix_blocks=-1)
    try:
        return eng.generate(prompt, max_new_tokens=1)["token_ids"][0]
    finally:
        eng.close()


@pytest.mark.parametrize("case", ["first_token_is_eos", "budget_of_one"])
def test_a_request_that_ends_at_its_first_token_joins_frozen(case):
    """Its first token is on the DEVICE when the chunk it joins is
    dispatched: the device applies the finish rules to it, so the slot
    is done in that chunk's input, emits nothing, and the request ends
    with its one token while the neighbour decodes on."""
    cfg = _tiny_llama_128()
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    prompt = [9, 8, 7, 6]
    first = _first_token(cfg, params, prompt)
    short = ((prompt, 12, first) if case == "first_token_is_eos"
             else (prompt, 1))
    runs = {}
    for ms in (False, True):
        churn = _Churn(cfg, params, multi_step=ms, max_batch=2)
        runs[ms] = (*churn.run([([1, 2, 3], 30), short, ([5] * 3, 6)]),
                    churn.done_in)
    (want, _, _), (got, stats, done_in) = runs[False], runs[True]
    assert got == want and got[1] == [first]
    # Slot 1 joined the first chunk done, with slot 0 live beside it
    # (the host had seen neither token), and was handed to the third
    # request only after that.
    assert done_in[0] == [False, True]
    assert len(got[2]) == 6
    assert stats["decode_chunks_carried"] >= stats[
        "decode_chunks_dispatched"] - 1


def test_a_preempted_slot_is_not_carried_into_the_next_chunk():
    """A request parked while a chunk is in flight: the chunk is landed
    first (its tokens are the victim's), the slot goes to the preemptor
    with the HOST's values, never the victim's carry, and both end with
    the tokens an undisturbed engine gives them."""
    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = _tiny_llama_128()
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    low, high = ([3, 1, 4, 1, 5], 40), ([2, 7, 1, 8], 12)
    alone = InferenceEngine(cfg, params, max_batch=1, max_len=128,
                            prompt_buckets=[8, 16], decode_chunk=4,
                            kv_fleet_min_prefix_blocks=-1)
    try:
        want = [alone.generate(*r)["token_ids"] for r in (low, high)]
    finally:
        alone.close()
    eng = InferenceEngine(cfg, params, max_batch=1, max_len=128,
                          prompt_buckets=[8, 16], decode_chunk=4,
                          kv_fleet_min_prefix_blocks=-1)
    try:
        stream = eng.generate_stream(*low, priority=0)
        got_low = [next(stream) for _ in range(6)]   # chunks in flight
        got_high = eng.generate(*high, priority=5)["token_ids"]
        got_low += list(stream)
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["preempts"] == 1 and stats["resumes"] == 1
    assert [got_low, got_high] == want


def test_live_steps_are_counted_from_what_the_device_reports():
    """`decode_steps` counts, at the retire, chunk x (slots the chunk
    found live): on the serial schedule that is the roster at dispatch
    x chunk, which is what was counted before; on the pipelined one a
    slot carried in already frozen no longer counts."""
    cfg = _tiny_llama_128()
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    seen = {}
    for ms in (False, True):
        churn = _Churn(cfg, params, multi_step=ms, max_batch=3,
                       second_wave=SECOND_WAVE)
        at_retire = []
        record = churn.eng.metrics.record_chunk

        def spy(tokens, live_steps, elapsed, _r=record, _o=at_retire):
            _o.append(live_steps)
            return _r(tokens, live_steps, elapsed)

        churn.eng.metrics.record_chunk = spy
        _, stats = churn.run(FIRST_WAVE)
        at_dispatch = [4 * d.count(False) for d in churn.done_in]
        seen[ms] = (at_dispatch, at_retire, stats)
    at_dispatch, at_retire, stats = seen[False]
    assert at_retire == at_dispatch and sum(at_retire) == stats[
        "decode_steps"]
    # Pipelined: the mask handed to the device says who is live, and
    # the retire counts exactly those (the host's roster at dispatch
    # would have counted slots the device had already frozen).
    at_dispatch, at_retire, stats = seen[True]
    assert at_retire == at_dispatch and sum(at_retire) == stats[
        "decode_steps"]
    assert stats["tokens_generated"] == seen[False][2]["tokens_generated"]
    assert 0.0 < stats["decode_utilization"] <= 1.0


# ----------------------------------------------------- chunked prefill


def test_prefill_plan_shapes():
    from ray_tpu.serve.engine.kv_manager import KVCacheManager
    from ray_tpu.serve.engine.scheduler import Scheduler

    kv = KVCacheManager(num_slots=2, max_len=64, block_size=8)
    s = Scheduler(kv, max_len=64, prompt_buckets=[8, 16, 32],
                  prefill_chunk=8)
    assert s.prefill_plan(5) == [(5, 8)]          # within one chunk
    assert s.prefill_plan(8) == [(8, 8)]
    assert s.prefill_plan(20) == [(8, 8), (8, 8), (4, 8)]
    assert s.prefill_plan(16) == [(8, 8), (8, 8)]  # exact multiple
    # Padded rows: full chunks are unpadded, only the tail buckets.
    assert s._prefill_rows(20) == 8 + 8 + 8
    # Chunking off: one bucket-padded piece.
    s0 = Scheduler(kv, max_len=64, prompt_buckets=[8, 16, 32])
    assert s0.prefill_plan(20) == [(20, 32)]
    assert s0._prefill_rows(20) == 32
    # prefill_chunk snaps DOWN to a configured bucket (static shapes;
    # snapping up would balloon the chunk between sparse buckets and
    # reintroduce the one-shot stall) — up only when nothing smaller.
    s7 = Scheduler(kv, max_len=64, prompt_buckets=[8, 16, 32],
                   prefill_chunk=7)
    assert s7.prefill_chunk == 8
    s20 = Scheduler(kv, max_len=64, prompt_buckets=[8, 16, 32],
                    prefill_chunk=20)
    assert s20.prefill_chunk == 16
    s_sparse = Scheduler(kv, max_len=256, prompt_buckets=[32, 224],
                         prefill_chunk=64)
    assert s_sparse.prefill_chunk == 32  # NOT 224


def test_chunked_fit_admits_deeper_prefix_reuse():
    """The chunked row bound (full chunks unpadded, only the tail
    bucketed) is tighter than the one-shot bucket, so reuse depths the
    unchunked fit must veto survive: a 16-token resident hit on a
    39-token prompt at max_len 40 keeps all 16 rows chunked
    (16 + 8+8+8 = 40) but shrinks to 8 unchunked (16 + 32 = 48)."""
    from ray_tpu.serve.engine.kv_manager import KVCacheManager
    from ray_tpu.serve.engine.scheduler import (EngineRequest,
                                                Scheduler)

    prompt = list(range(2, 41))  # 39 tokens
    for chunk, want_cached in ((0, 8), (8, 16)):
        kv = KVCacheManager(num_slots=1, max_len=40, block_size=8)
        s = Scheduler(kv, max_len=40, prompt_buckets=[8, 32],
                      prefill_chunk=chunk)
        slot, _ = kv.acquire(prompt)
        kv.release(slot, resident_tokens=prompt[:16])  # 2-block hit
        req = EngineRequest(prompt_ids=list(prompt), max_new_tokens=1)
        s.submit(req)
        (adm,) = list(s.admissions())
        assert adm.cached_len == want_cached, (chunk, adm.cached_len)


def test_kv_commit_prefill_tracks_materialized_prefix():
    """Occupancy is committed in FULL at acquire (the chunk plan is
    spoken for — the router's KV-pressure term must not under-count a
    long in-flight prefill), while resident/chain track the
    MATERIALIZED prefix chunk by chunk, hashed incrementally (the new
    blocks chain onto the old hashes — same chain as a one-shot
    hash)."""
    from ray_tpu.serve.engine.kv_manager import (KVCacheManager,
                                                 chain_hashes)

    kv = KVCacheManager(num_slots=1, max_len=32, block_size=4)
    prompt = list(range(40, 60))  # 20 tokens
    slot, cached = kv.acquire(prompt)
    assert cached == 0 and kv.used_blocks() == 5  # whole plan, up-front
    kv.commit_prefill(slot, prompt[:8])
    assert kv._slots[slot].resident == tuple(prompt[:8])
    assert len(kv._slots[slot].chain) == 2
    kv.commit_prefill(slot, prompt[:14])  # mid-block tail: 3 complete
    assert len(kv._slots[slot].chain) == 3
    kv.commit_prefill(slot, prompt[:20])
    assert (list(kv._slots[slot].chain)
            == chain_hashes(prompt, 4))   # incremental == one-shot
    assert kv.used_blocks() == 5          # unchanged by materialization
    kv.release(slot, resident_tokens=prompt)
    assert kv.used_blocks() == 0


def test_abort_seeds_only_preacquire_prefix():
    """A failed admission releases the slot seeding the PRE-ACQUIRE
    reused prefix (rows a confirmed earlier generation wrote), never
    the aborted request's own unconfirmed rows."""
    from ray_tpu.serve.engine.kv_manager import KVCacheManager
    from ray_tpu.serve.engine.scheduler import (EngineRequest,
                                                Scheduler)

    kv = KVCacheManager(num_slots=1, max_len=32, block_size=4)
    s = Scheduler(kv, max_len=32, prompt_buckets=[8, 16],
                  prefill_chunk=4)
    seed = list(range(70, 78))
    slot, _ = kv.acquire(seed)
    kv.release(slot, resident_tokens=seed)
    prompt = seed + list(range(80, 88))
    req = EngineRequest(prompt_ids=prompt, max_new_tokens=4)
    s.submit(req)
    (adm,) = list(s.admissions())
    assert adm.cached_len == 8
    kv.commit_prefill(adm.slot, prompt[:12])  # one chunk landed …
    s.abort_admission(req, resident=prompt[:adm.cached_len])  # … fails
    # The old 8-token prefix still serves hits; the aborted rows don't.
    s2, cached = kv.acquire(seed + [99])
    assert cached == 8
    kv.release(s2, resident_tokens=())
    s3, cached = kv.acquire(prompt)
    assert cached == 0  # the 12-token commit never reached the index


def test_chunked_prefill_engine_prefix_reuse_and_streaming(tiny_model):
    """Chunked engine end-to-end: warm repeat reuses the prefix cache
    and streams identical tokens; a long prompt co-batched with an
    active decode stream doesn't change either output."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_batch=2, max_len=64,
                    prompt_buckets=[8, 16], prefix_block=8,
                    prefill_chunk=8, decode_chunk=4)
    long_prompt = list(range(2, 32))
    try:
        cold = eng.generate(long_prompt, max_new_tokens=8)
        assert cold["cached_prefix_len"] == 0
        warm = eng.generate(long_prompt, max_new_tokens=8)
        assert warm["cached_prefix_len"] == 24  # 3 of 30//8 blocks
        assert warm["token_ids"] == cold["token_ids"]
        got = {}

        def consume(name, prompt, n):
            got[name] = list(eng.generate_stream(prompt,
                                                 max_new_tokens=n))

        t1 = threading.Thread(target=consume, args=("decode",
                                                    [5, 4, 3], 20))
        t1.start()
        deadline = time.monotonic() + 120
        while eng.metrics.requests < 3 and time.monotonic() < deadline:
            time.sleep(0.001)  # decode stream admitted (monotonic
            #                    signal — roster emptiness races)
        assert eng.metrics.requests >= 3, "stream never admitted"
        consume("long", list(range(32, 60)), 6)
        t1.join(timeout=300)
    finally:
        eng.close()
    assert len(got["decode"]) == 20
    assert len(got["long"]) == 6


def test_chunked_prefill_emits_per_chunk_spans(tiny_model):
    """TTFT decomposition under chunked prefill: one engine.prefill
    span PER CHUNK with chunk/chunks attrs (a whole 30-token prompt
    attributed to one span would hide where the prefill time went)."""
    from ray_tpu.core.config import GLOBAL_CONFIG as gcfg
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util import tracing

    cfg, params = tiny_model
    spans = []
    old = gcfg.get("tracing_enabled")
    gcfg.set("tracing_enabled", True)
    tracing.set_sink(spans.extend)
    eng = LLMEngine(cfg, params, max_batch=1, max_len=64,
                    prompt_buckets=[8, 16], prefill_chunk=8,
                    decode_chunk=4)
    try:
        with tracing.trace("matrix-root"):
            out = eng.generate(list(range(2, 32)), max_new_tokens=4)
        tracing.flush()
    finally:
        eng.close()
        tracing.set_sink(None)
        gcfg.set("tracing_enabled", old)
    assert out["num_generated"] == 4
    pf = sorted((s for s in spans if s["name"] == "engine.prefill"),
                key=lambda s: s["attrs"]["chunk"])
    # 30-token suffix, chunk 8 -> (8, 8, 8, 6): four chunk spans.
    assert [s["attrs"]["chunk"] for s in pf] == [0, 1, 2, 3]
    assert all(s["attrs"]["chunks"] == 4 for s in pf)
    assert [s["attrs"]["prefill_tokens"] for s in pf] == [8, 8, 8, 6]
    assert pf[-1]["attrs"]["bucket"] == 8
    queued = [s for s in spans if s["name"] == "engine.queued"]
    assert len(queued) == 1


# ---------------------------------------------- what a family's cache refuses

def _olmo_hybrid():
    from ray_tpu.models import olmo_hybrid

    import jax.numpy as jnp

    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=64, d_model=24, n_layers=4, n_heads=2, linear_heads=2,
        linear_key_dim=6, linear_value_dim=10, d_ff=32, max_seq_len=64,
        dtype=jnp.float32)


def _minicpm_sala():
    from ray_tpu.models import minicpm_sala
    from ray_tpu.ops.sparse_attention import Selection

    import jax.numpy as jnp

    return minicpm_sala.MiniCPMSalaConfig(
        vocab_size=64, d_model=24, mixer_types=("minicpm4", "lightning-attn"),
        n_heads=2, n_kv_heads=1, head_dim=8, lightning_heads=2,
        lightning_head_dim=8, d_ff=32, max_seq_len=64,
        selection=Selection(kernel=4, stride=2, block=4, window=4, topk=4,
                            dense_len=16), dtype=jnp.float32)


def _zaya():
    from ray_tpu.models import zaya

    import jax.numpy as jnp

    return zaya.ZayaConfig(
        vocab_size=64, d_model=24, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, rotary_dim=4, n_experts=4, moe_d_ff=16, router_d=8,
        max_seq_len=64, dtype=jnp.float32)


def _dots3_note():
    from ray_tpu.models import dots3_note

    import jax.numpy as jnp

    geometry = dots3_note.LatentGeometry
    return dots3_note.Dots3NoteConfig(
        vocab_size=64, d_model=24,
        layer_types=(dots3_note.FULL, dots3_note.FULL, dots3_note.SLIDING),
        full=geometry(2, 12, 8, 4, 4, 4, 1e4),
        sliding=geometry(2, 12, 12, 4, 4, 4, 1e4), index_heads=2,
        index_head_dim=8, index_topk=4, window=3, d_ff=32,
        moe_d_ff=16, n_experts=8, held_experts=(2, 2), n_experts_per_tok=2,
        max_seq_len=64, dtype=jnp.float32)


def _granite_hybrid():
    from ray_tpu.models import granite_hybrid

    import jax.numpy as jnp

    return granite_hybrid.GraniteHybridConfig(
        vocab_size=64, d_model=24,
        layer_kinds=("mamba", "attention", "mamba", "mamba"), n_heads=2,
        n_kv_heads=1, head_dim=12, mamba_heads=4, mamba_head_dim=12,
        mamba_state=8, mamba_chunk=8, d_ff=32, max_seq_len=64,
        dtype=jnp.float32)


def _kimi_linear():
    from ray_tpu.models import kimi_linear

    import jax.numpy as jnp

    return kimi_linear.KimiLinearConfig(
        vocab_size=64, d_model=24, kinds=("kda", "kda", "mla", "kda"),
        kda_heads=2, kda_head_dim=8, kda_rank=4, n_heads=2, kv_lora_rank=8,
        qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4, d_ff=32,
        moe_d_ff=16, n_experts=8, held_experts=(2, 4), n_experts_per_tok=2,
        max_seq_len=64, dtype=jnp.float32)


# family -> its tiny configuration; a further family is a further row.
FAMILIES = {"olmo_hybrid": _olmo_hybrid, "minicpm_sala": _minicpm_sala,
            "zaya": _zaya, "dots3_note": _dots3_note,
            "granite_hybrid": _granite_hybrid, "kimi_linear": _kimi_linear}
# A family whose per-slot entry is valid by the query's position alone (a
# ring of last rows): nothing is reset when a slot changes owner, so it
# neither counts `state_resets` nor stamps them on a span. Every other
# family here MUST do both.
NOT_RESET_WITH_THE_SLOT = {"dots3_note"}
OPTIONS = {"quantize": dict(quantize="int8"),
           "spec_draft_len": dict(spec_draft_len=2),
           "role": dict(role="prefill"),
           "kv_fleet": dict(kv_fleet_min_prefix_blocks=0)}


@pytest.mark.parametrize("option", OPTIONS)
@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_refuses_what_its_cache_cannot_serve(family, option):
    """A family that does not offer an option (none but llama offers
    any: the seam's default) is refused it at construction, by name and
    with the MECHANISM's one sentence on what it needs of a family,
    before any weight is made; without it the engine comes up."""
    from ray_tpu.serve.engine.core import InferenceEngine
    from ray_tpu.serve.engine.decode_loop import ENGINE_OPTIONS

    cfg = FAMILIES[family]()
    assert option not in getattr(cfg.model, "ENGINE_OFFERS", ())
    kwargs = dict(max_batch=2, max_len=64, prompt_buckets=[8, 16],
                  kv_fleet_min_prefix_blocks=-1)
    with pytest.raises(ValueError,
                       match=f"cannot serve with {option} yet") as refused:
        InferenceEngine(cfg, **{**kwargs, **OPTIONS[option]})
    assert cfg.model.__name__ in str(refused.value)
    assert ENGINE_OPTIONS[option] in str(refused.value)


def test_an_offered_option_the_engine_does_not_know_is_named(monkeypatch):
    """A keyword that leaves the engine must take its name out of every
    family's `ENGINE_OFFERS` with it: a stale name is an error that
    names the module and the name, not silence."""
    from ray_tpu.models import llama
    from ray_tpu.serve.engine.core import InferenceEngine

    monkeypatch.setattr(llama, "ENGINE_OFFERS",
                        (*llama.ENGINE_OFFERS, "an_option_that_left"))
    with pytest.raises(ValueError, match=r"llama\.ENGINE_OFFERS "
                                         r"names 'an_option_that_left'"):
        InferenceEngine(llama.tiny_config(max_seq_len=64), max_batch=2,
                        max_len=64, prompt_buckets=[8, 16],
                        kv_fleet_min_prefix_blocks=-1)


@pytest.mark.parametrize("family", ["llama", *FAMILIES])
def test_the_engine_serves_the_tree_its_family_lays_out(family):
    """The model seam's optional `serving_params(params, cfg)`: a
    family WITHOUT it serves the very tree it was handed
    (``engine.params is params``: no copy, no program); a family with
    it is handed the published tree and serves what the function makes
    of it, every leaf the function leaves alone the same buffer."""
    import jax

    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = _family_cfg(family)
    params = cfg.model.init_params(cfg, jax.random.PRNGKey(7))
    engine = InferenceEngine(cfg, params, max_batch=2, max_len=64,
                             prompt_buckets=[8, 16],
                             kv_fleet_min_prefix_blocks=-1)
    try:
        if not hasattr(cfg.model, "serving_params"):
            assert engine.params is params
            return
        given, held = (dict(jax.tree_util.tree_leaves_with_path(t))
                       for t in (params, engine.params))
        assert set(given) != set(held)              # something was laid out
        untouched = set(given) & set(held)
        assert untouched and all(held[k] is given[k] for k in untouched)
        assert len(engine.generate([3, 1, 4, 1, 5],
                                   max_new_tokens=3)["token_ids"]) == 3
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_with_slot_state_reuses_no_prefix(family):
    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = FAMILIES[family]()
    engine = InferenceEngine(cfg, max_batch=2, max_len=64,
                             prompt_buckets=[8, 16],
                             kv_fleet_min_prefix_blocks=-1)
    try:
        assert cfg.model.SLOT_STATE_KEYS and not engine.kv.reuse_prefix
        assert "prefix_reuse_vetoed" in engine.stats()
    finally:
        engine.close()


def _prefill_counters(cfg) -> dict:
    """The scalars a family's tick prefill counts, by name."""
    import jax

    from ray_tpu.serve.engine.decode_loop import serving_params

    cache = cfg.model.init_kv_cache(cfg, 1, 16)
    out = jax.eval_shape(
        lambda p, c: cfg.model.forward_last_with_cache(
            p, jax.numpy.zeros((1, 8), "int32"), c, 0, 7, cfg)[2],
        serving_params(cfg, cfg.model.init_params(cfg,
                                                  jax.random.PRNGKey(0))),
        cache)
    return dict.fromkeys(out, 0)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_chunked_prefill_fetches_every_chunks_counters(family):
    """A family with slot state resets its slot in the FIRST chunk of a
    prefill; the tick fetches once, after the last: the counters of
    every chunk ride that fetch, and the request's span sums them."""
    from ray_tpu.serve.engine.core import InferenceEngine

    cfg = FAMILIES[family]()
    engine = InferenceEngine(cfg, max_batch=2, max_len=64,
                             prompt_buckets=[8, 16], prefill_chunk=8,
                             kv_fleet_min_prefix_blocks=-1)
    try:
        for _ in range(2):
            out = engine.generate(list(range(1, 21)), max_new_tokens=2)
            assert len(out["token_ids"]) == 2
        stats = engine.stats()
    finally:
        engine.close()
    if family in NOT_RESET_WITH_THE_SLOT:
        assert "state_resets" not in _prefill_counters(cfg)
        assert "state_resets" not in cfg.model.SPAN_ATTRS
    else:
        assert stats["state_resets"] == 2       # one an admission
        assert engine._span_attrs([{"state_resets": 1},
                                   {"state_resets": 0}]) == {"state_reset": 1}
    # ... in one fetch each: the token and three chunks' scalars.
    assert stats["prefill_fetch_bytes"] == 2 * 4 * (
        1 + 3 * len(_prefill_counters(cfg)))
    if "prefill_chunks" in cfg.model.SPAN_ATTRS:
        assert stats["prefill_chunks"] == 2 * 3     # 8 + 8 + 4 tokens

