"""Speculative-decoding tests: prompt-lookup drafting, multi-token
verify, KV speculation accounting, and the greedy-equivalence invariant.

The hard contract under test: greedy speculative decode must be
TOKEN-IDENTICAL to greedy non-speculative decode for the same engine
config, prompts, and seeds — speculation may only change how many
forward passes each token costs, never which token comes out. The
drafter and adaptive controller are host-side and jax-free, so their
tests run without a model.
"""

import threading

import pytest

jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models import llama

    cfg = llama.tiny_config(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def make_engine(tiny_model, **kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", [8, 16])
    return LLMEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def eng_plain(tiny_model):
    eng = make_engine(tiny_model, decode_chunk=4)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def eng_spec(tiny_model):
    eng = make_engine(tiny_model, decode_chunk=4, spec_draft_len=4,
                      spec_chunk=2, spec_ngram_max=4)
    yield eng
    eng.close()


# ------------------------------------------------------------------ drafter


def test_prompt_lookup_drafter():
    from ray_tpu.serve.engine.drafter import PromptLookupDrafter

    d = PromptLookupDrafter(ngram_max=3)
    # Longest suffix n-gram wins: [5, 6] recurs, continuation follows it.
    assert d.draft([1, 5, 6, 9, 2, 5, 6], 2) == [9, 2]
    # Most RECENT earlier occurrence is preferred.
    assert d.draft([5, 6, 1, 5, 6, 2, 5, 6], 1) == [2]
    # Self-extension: a match ending at the suffix unrolls the loop to
    # the full need (a period-2 cycle drafts period-2 forever).
    assert d.draft([7, 8, 7, 8], 6) == [7, 8, 7, 8, 7, 8]
    assert d.draft([3, 3, 3, 3], 5) == [3, 3, 3, 3, 3]
    # No earlier occurrence of any suffix n-gram -> no draft.
    assert d.draft([1, 2, 3, 4, 5], 4) == []
    assert d.draft([1], 4) == []
    assert d.draft([1, 2, 1], 0) == []
    with pytest.raises(ValueError):
        PromptLookupDrafter(ngram_max=0)


def test_spec_control_adaptive():
    from ray_tpu.serve.engine.drafter import SpecControl

    c = SpecControl(allowance=4, max_allowance=16, bad_limit=2,
                    probe_interval=4)
    assert c.budget() == 4
    c.observe(4, 4)                      # perfect tick: double
    assert c.allowance == 8
    c.observe(8, 8)
    assert c.allowance == 16             # capped
    c.observe(16, 5)                     # middling (0.31): hold
    assert c.allowance == 16
    c.observe(16, 0)                     # bad tick 1: halve
    assert c.allowance == 8
    c.observe(8, 0)                      # bad tick 2: hits bad_limit -> 0
    assert c.allowance == 0
    # Backed off: only a periodic 1-token probe remains.
    probes = [c.budget() for _ in range(8)]
    assert probes.count(1) == 2 and probes.count(0) == 6
    # A probe that verifies re-opens the allowance.
    c.observe(1, 1)
    assert c.allowance == 2
    # Consecutive-bad accounting resets on any good tick.
    c.observe(2, 0)
    c.observe(2, 2)
    c.observe(4, 0)
    assert c.allowance >= 1              # single bad tick never zeroes


# -------------------------------------------------------------- equivalence


def reference_greedy(tiny_model, prompt, n):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg, params = tiny_model
    ids = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, jnp.asarray([ids]), cfg)
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt):]


def test_spec_greedy_equivalence(eng_plain, eng_spec):
    """Acceptance: speculative greedy == plain greedy, token for token,
    including repetitive prompts where drafts actually get accepted."""
    for prompt in ([1, 2, 3, 4, 5], [9, 8, 7], [5] * 8, [16] * 10):
        for n in (1, 6, 20):
            a = eng_plain.generate(prompt, max_new_tokens=n)
            b = eng_spec.generate(prompt, max_new_tokens=n)
            assert a["token_ids"] == b["token_ids"], (prompt, n)
            assert b["num_generated"] == len(b["token_ids"])
    # The repetitive prompts must have exercised the verify path (drafts
    # proposed and accepted), or this test proves nothing.
    assert eng_spec.metrics.spec_chunks > 0
    assert eng_spec.metrics.spec_accepted > 0


def test_spec_eos_mid_window(eng_plain, eng_spec):
    """EOS landing inside a verify window stops exactly AT the EOS —
    accepted-but-beyond-EOS draft tokens must never be delivered."""
    prompt = [3, 1, 4, 1, 5]
    free = eng_plain.generate(prompt, max_new_tokens=24)["token_ids"]
    for k in (2, 5, 9):
        eos = free[k]
        if free.index(eos) != k:
            continue  # eos occurs earlier; expected cut differs
        a = eng_plain.generate(prompt, max_new_tokens=24, eos_id=eos)
        b = eng_spec.generate(prompt, max_new_tokens=24, eos_id=eos)
        assert a["token_ids"] == b["token_ids"] == free[:k + 1]
        assert b["token_ids"][-1] == eos
        streamed = list(eng_spec.generate_stream(prompt,
                                                 max_new_tokens=24,
                                                 eos_id=eos))
        assert streamed == b["token_ids"]


def test_spec_budget_not_window_multiple(eng_plain, eng_spec):
    """Budgets that end mid-window stop exactly on budget (the per-
    position remaining mask, not the window width, decides)."""
    for n in (3, 7, 11):
        a = eng_plain.generate([2, 4, 6], max_new_tokens=n)
        b = eng_spec.generate([2, 4, 6], max_new_tokens=n)
        assert a["token_ids"] == b["token_ids"]
        assert b["num_generated"] == n


def test_spec_row_cap_equivalence(eng_plain, eng_spec):
    """Generations running into the max_len row cap freeze at the same
    token with and without speculation (window overruns land in the
    scratch strip, never shifting valid rows)."""
    prompt = list(range(2, 40))  # 38 tokens, max_len 64
    a = eng_plain.generate(prompt, max_new_tokens=26)
    b = eng_spec.generate(prompt, max_new_tokens=26)
    assert a["token_ids"] == b["token_ids"]


def test_spec_off_path_identical(tiny_model, eng_plain):
    """spec_draft_len=0 must behave exactly like the pre-speculation
    engine: no drafter, no verify program, no cache padding, same
    tokens, same host-sync cadence."""
    eng = make_engine(tiny_model, decode_chunk=4, spec_draft_len=0)
    try:
        assert eng.speculation is None
        assert eng.loop.scratch_rows == 0
        assert not hasattr(eng.loop, "verify_chunk")
        assert eng.cache["k"].shape == eng_plain.cache["k"].shape
        before = eng.metrics.host_syncs
        out = eng.generate([16] * 10, max_new_tokens=9)
        assert (out["token_ids"]
                == eng_plain.generate([16] * 10,
                                      max_new_tokens=9)["token_ids"])
        # token 0 from prefill, 8 more in ceil(8/4) = 2 chunk fetches
        assert eng.metrics.host_syncs - before == 2
        assert eng.metrics.spec_chunks == 0
    finally:
        eng.close()


# ------------------------------------------------------- KV spec accounting


def test_kv_speculation_accounting_no_leaks():
    from ray_tpu.serve.engine.kv_manager import KVCacheManager

    kv = KVCacheManager(num_slots=2, max_len=32, block_size=4)
    prompt = list(range(10, 19))           # 9 tokens
    slot, _ = kv.acquire(prompt)
    assert kv.used_blocks() == 3           # ceil(9/4)
    # A dispatched verify chunk reserves rows for its draft windows …
    kv.begin_speculation(slot, 10)
    assert kv.used_blocks() == 5           # ceil(19/4): in-flight drafts
    with pytest.raises(ValueError):
        kv.begin_speculation(slot, 2)      # one in-flight max
    # … and the fetch commits only the accepted prefix; the rejected
    # rows are rolled back with no block leak.
    kv.commit_speculation(slot, 3)
    assert kv.used_blocks() == 3           # ceil(12/4)
    with pytest.raises(ValueError):
        kv.commit_speculation(slot, 99)    # beyond reservation
    # Release with a pending reservation (device-failure path) clears it.
    s2, _ = kv.acquire([1, 2, 3])
    kv.begin_speculation(s2, 8)
    kv.release(s2, resident_tokens=())
    assert kv.used_blocks() == 3           # only the first slot remains
    kv.release(slot, resident_tokens=prompt + [7, 7, 7])
    assert kv.used_blocks() == 0
    assert kv.free_slots() == 2


def test_kv_rejected_drafts_never_poison_prefix_index():
    """Only VERIFIED tokens are released as resident: a later prompt
    that extends the true generation hits the cache, one that extends a
    rejected draft path does not reuse unverified rows."""
    from ray_tpu.serve.engine.kv_manager import KVCacheManager

    kv = KVCacheManager(num_slots=1, max_len=32, block_size=4)
    prompt = [1, 2, 3, 4]
    verified = [5, 6, 7]                   # accepted draft tokens
    slot, _ = kv.acquire(prompt)
    kv.begin_speculation(slot, 8)
    kv.commit_speculation(slot, len(verified))
    # The engine releases prompt + verified tokens only — rejected draft
    # rows are rolled back and never become resident.
    kv.release(slot, resident_tokens=prompt + verified)
    s, cached = kv.acquire(prompt + verified + [9])
    assert s == slot and cached == 4       # one complete verified block
    kv.release(s, resident_tokens=())
    # A prompt following the REJECTED continuation [8, 8, ...] finds no
    # resident prefix beyond what was verified.
    s, cached = kv.acquire([1, 2, 3, 8, 8, 8, 8, 8])
    assert cached == 0


def test_engine_spec_blocks_settle_after_requests(tiny_model):
    """End-to-end: after speculative generations finish, no reservation
    or block accounting is left behind."""
    eng = make_engine(tiny_model, decode_chunk=4, spec_draft_len=4,
                      spec_chunk=2, prefix_block=4)
    try:
        eng.generate([16] * 10, max_new_tokens=12)
        eng.generate([1, 2, 3], max_new_tokens=6)
        assert eng.kv.used_blocks() == 0
        assert eng.kv.free_slots() == eng.max_batch
        assert all(s.spec_rows == 0 for s in eng.kv._slots)
        # Prefix chains stay valid: the repeated prompt hits the cache
        # and reproduces the cold generation exactly.
        cold = eng.generate([16] * 10, max_new_tokens=12)
        assert cold["cached_prefix_len"] > 0
    finally:
        eng.close()


# ----------------------------------------------------------------- adaptive


def test_adaptive_shrinks_to_zero_under_adversarial_drafts(tiny_model):
    """Drafts that always verify wrong drive the allowance to a hard 0
    within bad_limit ticks; after that, decode ticks dispatch the PLAIN
    program (no verify-window compute), so an adversarial workload pays
    nothing over speculation-off outside a rare 1-token probe."""
    eng = make_engine(tiny_model, decode_chunk=4, spec_draft_len=4,
                      spec_chunk=1)
    prompt = [3, 1, 4, 1, 5]
    try:
        free = eng.generate(prompt, max_new_tokens=30)["token_ids"]
        # A token the generation never emits: drafting it always rejects.
        bogus = next(t for t in range(eng.cfg.vocab_size)
                     if t not in free and t not in prompt)

        class BogusDrafter:
            def draft(self, context, need):
                return [bogus] * need

        eng.speculation.drafter = BogusDrafter()
        base_spec = eng.metrics.spec_chunks
        base_syncs = eng.metrics.host_syncs
        out = eng.generate(prompt, max_new_tokens=30)
        assert out["token_ids"] == free     # rejection never corrupts
        spec_chunks = eng.metrics.spec_chunks - base_spec
        syncs = eng.metrics.host_syncs - base_syncs
        # Allowance 4 halves under 100% rejection: 4->2->1->1 then the
        # bad-streak limit zeroes it; at most bad_limit verify chunks
        # plus the occasional probe — the rest dispatch plain.
        assert spec_chunks <= 4 + syncs // 8 + 1
        assert syncs - spec_chunks >= 5     # plain path took over
    finally:
        eng.close()


def test_oracle_drafts_sustain_full_windows(tiny_model):
    """Draft-buffer alignment across windows: with an ORACLE drafter
    (drafts the true continuation), every window must fully accept —
    across ALL spec_chunk windows of a dispatch, not just the first.
    Each full window advances draft_len+1 positions (drafts + bonus),
    so the buffer rows are packed at stride draft_len+1; a stride-K
    packing desynchronizes row 1+ by one token per window and caps
    delivery near half (this is a regression test for exactly that)."""
    K, C = 3, 2
    eng = make_engine(tiny_model, max_batch=1, decode_chunk=4,
                      spec_draft_len=K, spec_chunk=C)
    prompt = [3, 1, 4, 1, 5]
    n = 33  # 1 prefill + 32 decode
    try:
        free = eng.generate(prompt, max_new_tokens=n)["token_ids"]

        class OracleDrafter:
            def draft(self, context, need):
                g = len(context) - len(prompt)
                return free[g:g + need]

        eng.speculation.drafter = OracleDrafter()
        base_syncs = eng.metrics.host_syncs
        base_drafted = eng.metrics.spec_drafted
        base_accepted = eng.metrics.spec_accepted
        out = eng.generate(prompt, max_new_tokens=n)
        syncs = eng.metrics.host_syncs - base_syncs
        drafted = eng.metrics.spec_drafted - base_drafted
        accepted = eng.metrics.spec_accepted - base_accepted
    finally:
        eng.close()
    assert out["token_ids"] == free
    # An oracle's drafts must ALL verify — in EVERY window, not just
    # row 0. Stride-K packing desynchronizes row 1+ by one position per
    # full window and rejects them whenever the continuation isn't
    # locally constant (this generation alternates).
    assert drafted > 0 and accepted == drafted
    # And multi-window acceptance must beat the plain sync cadence
    # (ceil(32/4) = 8 chunks) by a wide margin.
    assert syncs <= 6


def test_lookup_miss_backoff_stops_scanning(tiny_model):
    """Chronic lookup misses count toward the adaptive bad streak: the
    allowance zeroes and the (host-side) lookup itself stops running on
    every tick — only the periodic probe remains."""
    from ray_tpu.serve.engine.drafter import SpecControl

    c = SpecControl(allowance=4, max_allowance=16, bad_limit=3,
                    probe_interval=8)
    for _ in range(3):
        assert c.budget() > 0
        c.miss()
    assert c.allowance == 0
    calls = sum(1 for _ in range(16) if c.budget() > 0)
    assert calls == 2  # two probes in 16 ticks, not 16 scans
    # Engine level: a drafter that never matches must leave the request
    # on the plain program after bad_limit ticks.
    eng = make_engine(tiny_model, decode_chunk=4, spec_draft_len=4)
    try:
        calls = [0]
        real = eng.speculation.drafter

        class CountingMissDrafter:
            def draft(self, context, need):
                calls[0] += 1
                return []

        eng.speculation.drafter = CountingMissDrafter()
        base = eng.metrics.host_syncs
        eng.generate([1, 2, 3], max_new_tokens=30)
        ticks = eng.metrics.host_syncs - base
        assert eng.metrics.spec_chunks == 0   # nothing ever drafted
        # Lookup ran only until the streak zeroed the allowance, plus
        # sparse probes — not every tick.
        assert calls[0] < ticks
        eng.speculation.drafter = real
    finally:
        eng.close()


def test_prometheus_labels_roundtrip_hostile_names():
    """Engine names are arbitrary user strings: a name with commas and
    quotes must round-trip render -> parse without mis-attribution."""
    from ray_tpu.util.dashboard import _parse_prometheus
    from ray_tpu.util.metrics import Gauge

    g = Gauge("rtpu_test_hostile_labels", "test")
    name = 'prod,eu "canary"'
    g.set(7.0, labels={"engine": name})
    text = "\n".join(g.render())
    parsed = [(n, lbl, v) for n, lbl, v in _parse_prometheus(text)
              if n == "rtpu_test_hostile_labels"]
    assert parsed == [("rtpu_test_hostile_labels", {"engine": name}, 7.0)]


# ------------------------------------------------------------------ metrics


def test_decode_utilization_reflects_frozen_steps(tiny_model):
    """The utilization denominator counts live slot-steps scanned, not
    tokens delivered: a request freezing mid-chunk shows < 1.0 (the old
    accounting passed delivered for both and always read 1.0)."""
    eng = make_engine(tiny_model, decode_chunk=8)
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)
        m = eng.metrics
        # Token 0 from prefill; 3 decode tokens from ONE 8-step chunk.
        assert m.host_syncs == 1
        assert m.decode_steps == 8
        assert m.tokens_generated == 4
        assert eng.stats()["decode_utilization"] == pytest.approx(3 / 8)
    finally:
        eng.close()


def test_spec_stats_surface(eng_spec):
    s = eng_spec.stats()
    for key in ("spec_chunks", "spec_drafted", "spec_accepted",
                "spec_accept_rate", "decode_utilization"):
        assert key in s, key
    assert 0.0 <= s["spec_accept_rate"] <= 1.0
    assert s["spec_drafted"] >= s["spec_accepted"]


def test_concurrent_spec_streams(eng_spec):
    """Two concurrent requests through the verify path: per-consumer
    ordering and content match the plain reference."""
    prompts = [[16] * 9, [4, 5, 6]]
    tiny = (eng_spec.cfg, eng_spec.params)
    got = {}

    def consume(i):
        got[i] = list(eng_spec.generate_stream(prompts[i],
                                               max_new_tokens=7))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    for i, p in enumerate(prompts):
        assert got[i] == reference_greedy(tiny, p, 7), p


# ------------------------------------------------------------------- sweep


def test_spec_equivalence_sweep(tiny_model):
    """Exhaustive greedy-equivalence sweep across spec configs x prompts
    x budgets (the quick tests above cover one config; this covers the
    knob matrix, including adaptive-off and single-token drafts)."""
    plain = make_engine(tiny_model, decode_chunk=4)
    prompts = ([1, 2, 3, 4, 5], [9, 8, 7], [5] * 8, [16] * 10,
               [3, 1, 4, 1, 5, 9, 2, 6])
    try:
        for spec_kw in ({"spec_draft_len": 4},
                        {"spec_draft_len": 4, "spec_chunk": 2},
                        {"spec_draft_len": 2, "spec_chunk": 3},
                        {"spec_draft_len": 8, "spec_adaptive": False},
                        {"spec_draft_len": 1}):
            spec = make_engine(tiny_model, decode_chunk=4, **spec_kw)
            try:
                for p in prompts:
                    for n in (1, 5, 20, 40):
                        if len(p) + n > 64:
                            continue
                        a = plain.generate(p, max_new_tokens=n)
                        b = spec.generate(p, max_new_tokens=n)
                        assert (a["token_ids"] == b["token_ids"]), \
                            (spec_kw, p, n)
            finally:
                spec.close()
    finally:
        plain.close()
