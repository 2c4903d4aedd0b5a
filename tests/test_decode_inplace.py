"""The decode step that is batched by construction and touches the
cache in place (``llama.decode_step_with_cache``, PR 26), held to the
``vmap``ped step it replaced.

That step forwarded each slot's one token through the functional
``llama.forward_with_cache`` at the slot's own index; it is kept here
as plain loops, the reference the new ``decode_chunk`` must match:
tokens, ``n_valid``, the carried state and every row of the cache, for
mixed lengths, a slot that finishes mid-chunk, a slot at the row cap
and an empty slot. Then the engine's side of donation: a donated
program that raises costs the cache, and the engine rebuilds it.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.quant import quantize_params
from ray_tpu.serve.engine.decode_loop import DecodeLoop

SLOTS, MAX_LEN, CHUNK = 4, 32, 4
CACHE = 6       # where a chunk's results hold the cache (counters follow)

HEADS = {"gqa": dict(n_heads=4, n_kv_heads=2),
         "mha": dict(n_heads=4, n_kv_heads=4)}
# How the step reaches attention: the dispatcher's jnp twin (what it
# picks off the TPU), the Pallas kernel under the interpreter at
# tiny_config's head size 16 (the layer sliced and padded to lanes a
# call), the same at head size 128 with rows the block divides (the
# kernel reads the layer's blocks where they lie in the whole cache: the
# form the Mistral, Olmo and ZAYA cells run), int8 weights.
ROUTES = {
    "contiguous": {},
    "interpret": dict(interpret_kernels=True),
    "interpret_inplace": dict(interpret_kernels=True, d_model=512),
    "int8": {},
}


def _model(heads: str, route: str):
    cfg = llama.tiny_config(max_seq_len=MAX_LEN, **HEADS[heads],
                            **ROUTES[route])
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    if route == "int8":
        params = quantize_params(params, dtype="int8")
    return cfg, params


def _roster():
    """Slot 0 decodes on; slot 1's budget ends mid-chunk; slot 2 hits
    the row cap after one token; slot 3 is empty (parked on the last
    row, as ``LLMEngine._roster_arrays`` parks it)."""
    lengths = np.array([5, 9, MAX_LEN - 2, MAX_LEN - 1], np.int32)
    remaining = np.array([10, 2, 10, 0], np.int32)
    eos = np.full((SLOTS,), -1, np.int32)
    done = np.array([False, False, False, True])
    return lengths, remaining, eos, done


def _prefilled(loop, cfg, params, lengths, done):
    """A cache whose live slots hold real prompts' rows (the functional
    prefill, one slot at a time) and each slot's last prompt token."""
    rng = np.random.default_rng(11)
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_LEN)
    tokens = np.zeros((SLOTS, 1), np.int32)
    prompts = {}
    for b in range(SLOTS):
        if done[b]:
            continue
        prompt = rng.integers(1, cfg.vocab_size, int(lengths[b]) + 1)
        prompts[b] = prompt
        _, cache = loop.prefill(params, cache,
                                jnp.asarray(prompt[None, :-1], jnp.int32),
                                jnp.int32(b), jnp.int32(0))
        tokens[b, 0] = prompt[-1]
    return cache, tokens, prompts


def _vmapped_chunk(cfg, params, cache, tokens, lengths, remaining, eos,
                   done):
    """The deleted step and its chunk, slot by slot and step by step."""
    fwd = jax.jit(functools.partial(llama.forward_with_cache, cfg=cfg))
    tok, ln, rem, dn = (np.array(tokens[:, 0]), np.array(lengths),
                        np.array(remaining), np.array(done))
    toks, was_done = [], []
    for _ in range(CHUNK):
        nxt = np.zeros((SLOTS,), np.int32)
        for b in range(SLOTS):
            row = {k: v[:, b:b + 1] for k, v in cache.items()}
            logits, new = fwd(params, jnp.asarray(tok[b:b + 1, None]), row,
                              jnp.int32(ln[b]))
            cache = {k: cache[k].at[:, b:b + 1].set(new[k]) for k in cache}
            nxt[b] = int(jnp.argmax(logits[0, -1]))
        emit = np.where(dn, tok, nxt).astype(np.int32)
        was_done.append(dn.copy())
        ln = np.where(dn, ln, ln + 1)
        rem = np.where(dn, rem, rem - 1)
        dn = dn | (emit == eos) | (rem <= 0) | (ln + 1 >= MAX_LEN)
        toks.append(emit)
        tok = emit
    n_valid = CHUNK - np.sum(np.array(was_done), axis=0)
    return np.array(toks).T, n_valid, tok[:, None], ln, rem, dn, cache


def _run_both(heads, route, eos_of=None):
    cfg, params = _model(heads, route)
    loop = DecodeLoop(cfg, max_len=MAX_LEN, chunk=CHUNK)
    lengths, remaining, eos, done = _roster()
    cache, tokens, prompts = _prefilled(loop, cfg, params, lengths, done)
    if eos_of is not None:
        eos = eos_of(_vmapped_chunk(cfg, params, cache, tokens, lengths,
                                    remaining, eos, done)[0])
    want = _vmapped_chunk(cfg, params, cache, tokens, lengths, remaining,
                          eos, done)
    # The chunk takes its cache donated: hand it a copy, keep ours.
    got = loop.decode_chunk(params, jax.tree.map(jnp.copy, cache),
                            jnp.asarray(tokens), jnp.asarray(lengths),
                            jnp.asarray(remaining), jnp.asarray(eos),
                            jnp.asarray(done))
    return cfg, params, loop, prompts, want, got


def _assert_same(want, got):
    names = ("tokens", "n_valid", "next_tokens", "lengths", "remaining",
             "done")
    for name, w, g in zip(names, want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    # Every row but the one each slot is parked on at the chunk's end:
    # a done or empty slot wrote there and nowhere else, but WHAT it
    # wrote is nobody's since PR 34 (it attends to no row, so its
    # hidden state past the first layer is not the old step's).
    parked = np.asarray(got[3])
    for key in ("k", "v"):
        g, w = np.array(got[CACHE][key]), np.array(want[CACHE][key])
        for b, row in enumerate(parked):
            g[:, b, :, row] = w[:, b, :, row] = 0
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=key)


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("heads", HEADS)
def test_decode_chunk_matches_the_vmapped_step(heads, route):
    _, _, _, _, want, got = _run_both(heads, route)
    _assert_same(want, got)
    n_valid = np.asarray(got[1])
    # The roster did what it was built to do.
    assert n_valid.tolist() == [CHUNK, 2, 1, 0]
    assert np.asarray(got[5]).tolist() == [False, True, True, True]


@pytest.mark.parametrize("heads", HEADS)
def test_decode_chunk_stops_at_a_slots_eos(heads):
    """Slot 0's own eos is the second token it emits: it freezes there
    and the rest of its chunk repeats that token."""
    def eos_of(tokens):
        eos = np.full((SLOTS,), -1, np.int32)
        eos[0] = tokens[0, 1]
        return eos

    _, _, _, _, want, got = _run_both(heads, "contiguous", eos_of)
    _assert_same(want, got)
    assert int(np.asarray(got[1])[0]) <= 2


@pytest.mark.parametrize("route", ["contiguous", "interpret",
                                   "interpret_inplace"])
@pytest.mark.parametrize("heads", HEADS)
def test_decoded_rows_equal_the_functional_prefills(heads, route):
    """What the in-place step leaves in a live slot's rows is what the
    functional prefill of prompt + emitted tokens writes there."""
    cfg, params, loop, prompts, _, got = _run_both(heads, route)
    toks, n_valid, cache = (np.asarray(got[0]), np.asarray(got[1]),
                            got[CACHE])
    for b, prompt in prompts.items():
        n = int(n_valid[b])
        seq = np.concatenate([prompt, toks[b, :n]])[:-1]
        _, ref = loop.prefill(
            params, llama.init_kv_cache(cfg, SLOTS, MAX_LEN),
            jnp.asarray(seq[None], jnp.int32), jnp.int32(b), jnp.int32(0))
        for key in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache[key][:, b, :, :len(seq)]),
                np.asarray(ref[key][:, b, :, :len(seq)]),
                rtol=1e-4, atol=1e-5, err_msg=f"slot {b} {key}")


def test_decode_step_is_the_chunks_step():
    """``decode_step`` stays exported: the standalone (functional) jit
    of the step the chunk scans over."""
    cfg, params = _model("gqa", "contiguous")
    loop = DecodeLoop(cfg, max_len=MAX_LEN, chunk=1)
    lengths, remaining, eos, done = _roster()
    cache, tokens, _ = _prefilled(loop, cfg, params, lengths, done)
    nxt, stepped, _ = loop.decode_step(params, cache, jnp.asarray(tokens),
                                       jnp.asarray(lengths),
                                       jnp.asarray(~done))
    assert not cache["k"].is_deleted()      # functional: ours lives on
    got = loop.decode_chunk(params, jax.tree.map(jnp.copy, cache),
                            jnp.asarray(tokens), jnp.asarray(lengths),
                            jnp.asarray(remaining), jnp.asarray(eos),
                            jnp.asarray(done))
    live = ~done
    np.testing.assert_array_equal(np.asarray(got[0])[live, 0],
                                  np.asarray(nxt)[live])
    np.testing.assert_allclose(np.asarray(got[CACHE]["k"]),
                               np.asarray(stepped["k"]), rtol=1e-6)


def test_tick_programs_donate_and_the_checks_prefill_does_not():
    cfg, params = _model("gqa", "contiguous")
    loop = DecodeLoop(cfg, max_len=MAX_LEN, chunk=2, spec_window=3,
                      kv_page=16)
    lengths, remaining, eos, done = (jnp.asarray(a) for a in _roster())
    tokens = jnp.ones((SLOTS, 1), jnp.int32)
    prompt = jnp.ones((1, 8), jnp.int32)
    zero = jnp.int32(0)
    rows = MAX_LEN + loop.scratch_rows

    def fresh():
        return llama.init_kv_cache(cfg, SLOTS, rows)

    cache = fresh()
    _, kept = loop.prefill(params, cache, prompt, zero, zero)
    assert not cache["k"].is_deleted() and not cache["v"].is_deleted()
    page = loop.export_page(kept, zero, zero)
    assert not kept["k"].is_deleted()
    calls = {
        "prefill_inplace": lambda c: loop.prefill_inplace(
            params, c, prompt, zero, zero, jnp.int32(7))[1],
        "decode_chunk": lambda c: loop.decode_chunk(
            params, c, tokens, lengths, remaining, eos, done)[CACHE],
        "verify_chunk": lambda c: loop.verify_chunk(
            params, c, tokens,
            jnp.zeros((SLOTS, loop.spec_chunk, 2), jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), lengths, remaining, eos,
            done)[-1],
        "install_page": lambda c: loop.install_page(c, *page, zero, zero),
    }
    for name, call in calls.items():
        cache = fresh()
        out = call(cache)
        assert cache["k"].is_deleted() and cache["v"].is_deleted(), name
        assert out["k"].shape == (cfg.n_layers, SLOTS, cfg.n_kv_heads,
                                  rows, cfg.head_dim), name


# ------------------------------------------------ the engine's recovery

PROMPT = [5, 9, 2, 7, 1, 3]


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg = llama.tiny_config(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return LLMEngine(cfg, params, max_len=64, prompt_buckets=[8, 16],
                     decode_chunk=2, **kw)


@pytest.fixture(scope="module")
def fresh_tokens():
    eng = _engine(max_batch=1)
    try:
        return eng.generate(PROMPT, max_new_tokens=9)["token_ids"]
    finally:
        eng.close()


def _raise_after_donation(inner, times: int = 1):
    """A donated program that raises: the real one runs (and so has the
    cache), then the call fails."""
    left = [times]

    def call(params, cache, *args):
        if left[0]:
            left[0] -= 1
            inner(params, cache, *args)
            raise RuntimeError("injected device failure")
        return inner(params, cache, *args)

    return call


def test_failed_decode_dispatch_rebuilds_the_cache(fresh_tokens):
    """The roster's request fails, the waiting one does not, and both
    it and a later request come out as a fresh engine's would."""
    eng = _engine(max_batch=1)
    try:
        assert eng.stats()["cache_rebuilds"] == 0
        # A finished request leaves a resident prefix behind: the
        # rebuild must drop it, or the next admission reuses rows of
        # zeros.
        assert eng.generate(PROMPT, max_new_tokens=9)["token_ids"] \
            == fresh_tokens
        eng.loop.decode_chunk = _raise_after_donation(eng.loop.decode_chunk)
        results = {}

        def ask(name):
            try:
                results[name] = eng.generate(PROMPT, max_new_tokens=9,
                                             timeout=120)["token_ids"]
            except RuntimeError as e:
                results[name] = e

        first = threading.Thread(target=ask, args=("roster",))
        first.start()
        # One slot: the second request waits behind the first.
        while eng.stats()["active"] + eng.stats()["prefilling"] == 0 \
                and first.is_alive():
            pass
        second = threading.Thread(target=ask, args=("waiting",))
        second.start()
        first.join(120)
        second.join(120)
        assert not first.is_alive() and not second.is_alive()
        failed = [v for v in results.values()
                  if isinstance(v, RuntimeError)]
        assert len(failed) == 1 and "injected" in str(failed[0])
        assert [v for v in results.values()
                if not isinstance(v, RuntimeError)] == [fresh_tokens]
        stats = eng.stats()
        assert stats["cache_rebuilds"] == 1
        assert stats["active"] == 0 and stats["free_slots"] == 1
        assert not eng.cache["k"].is_deleted()
        out = eng.generate(PROMPT, max_new_tokens=9)
        assert out["token_ids"] == fresh_tokens
        assert eng.stats()["cache_rebuilds"] == 1
    finally:
        eng.close()


def test_failed_tick_prefill_rebuilds_the_cache(fresh_tokens):
    """The tick's prefill is donated too: the request whose prefill
    raised fails together with the roster it took the cache from."""
    eng = _engine(max_batch=2)
    try:
        stream = eng.generate_stream([4, 4, 8, 1], max_new_tokens=40)
        assert isinstance(next(stream), int)     # the roster holds a slot
        eng.loop.prefill_inplace = _raise_after_donation(
            eng.loop.prefill_inplace)
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate(PROMPT, max_new_tokens=9, timeout=120)
        with pytest.raises(RuntimeError, match="injected"):
            list(stream)
        stats = eng.stats()
        assert stats["cache_rebuilds"] == 1
        assert stats["active"] == 0 and stats["free_slots"] == 2
        assert eng.generate(PROMPT, max_new_tokens=9)["token_ids"] \
            == fresh_tokens
    finally:
        eng.close()


def test_a_failure_that_spares_the_cache_rebuilds_nothing(fresh_tokens):
    """A program that raises before it runs (here: before the call)
    leaves the cache alive; the roster still fails, nothing is
    rebuilt and resident prefixes stay."""
    eng = _engine(max_batch=1)
    try:
        inner = eng.loop.decode_chunk
        left = [1]

        def call(*args):
            if left[0]:
                left[0] -= 1
                raise RuntimeError("injected before dispatch")
            return inner(*args)

        eng.loop.decode_chunk = call
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate(PROMPT, max_new_tokens=9, timeout=120)
        assert eng.stats()["cache_rebuilds"] == 0
        assert eng.generate(PROMPT, max_new_tokens=9)["token_ids"] \
            == fresh_tokens
    finally:
        eng.close()
