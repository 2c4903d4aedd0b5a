"""The decode step is told which slots are live (PR 34): a slot that is
idle or frozen is stepped all the same (static shapes) and its row is
still written where the engine parked it, but its attention reads no
row, and nothing a live slot gets changes by it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.engine.decode_loop import DecodeLoop

SLOTS, MAX_LEN, CHUNK = 4, 32, 4
# The dispatcher's jnp twin; the kernel interpreted over a layer sliced
# and padded (head size 16); the kernel interpreted over the whole cache
# where it lies (head size 128, rows the block divides).
ROUTES = {"reference": {}, "kernel": dict(interpret_kernels=True),
          "kernel_inplace": dict(interpret_kernels=True, d_model=512)}


def _roster():
    """Slot 0 decodes on, slot 1's budget ends after two tokens (frozen
    for the rest of the chunk), slot 2 decodes on, slot 3 is idle,
    parked as ``LLMEngine._roster_arrays`` parks it."""
    lengths = np.array([5, 9, 3, MAX_LEN - 1], np.int32)
    remaining = np.array([10, 2, 10, 0], np.int32)
    done = np.array([False, False, False, True])
    return lengths, remaining, np.full((SLOTS,), -1, np.int32), done


@pytest.fixture(scope="module", params=list(ROUTES))
def chunk(request):
    cfg = dataclasses.replace(
        llama.tiny_config(max_seq_len=MAX_LEN, n_heads=4, n_kv_heads=2),
        **ROUTES[request.param])
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    loop = DecodeLoop(cfg, max_len=MAX_LEN, chunk=CHUNK)
    lengths, remaining, eos, done = _roster()
    rng = np.random.default_rng(5)
    cache = llama.init_kv_cache(cfg, SLOTS, MAX_LEN)
    for b in range(3):
        prompt = rng.integers(1, cfg.vocab_size, (1, int(lengths[b])))
        _, cache = loop.prefill(params, cache, jnp.asarray(prompt, jnp.int32),
                                jnp.int32(b), jnp.int32(0))
    tokens = rng.integers(1, cfg.vocab_size, (SLOTS, 1)).astype(np.int32)
    got = loop.decode_chunk(params, jax.tree.map(jnp.copy, cache),
                            *map(jnp.asarray,
                                 (tokens, lengths, remaining, eos, done)))
    return loop, params, cache, tokens, got


def test_live_slots_tokens_are_the_unmasked_steps_bit_for_bit(chunk):
    """The chunk (which masks) against its own step called with
    ``live=None`` (every slot reads ``lengths + 1`` rows, as before PR
    34), frozen and advanced on the host."""
    loop, params, cache, tokens, got = chunk
    lengths, remaining, _, done = _roster()
    tok, ln, rem, dn = tokens.copy(), lengths.copy(), remaining.copy(), done
    for step in range(CHUNK):
        nxt, cache, _ = loop.decode_step(params, cache, jnp.asarray(tok),
                                         jnp.asarray(ln))
        live = ~dn
        np.testing.assert_array_equal(np.asarray(got[0])[live, step],
                                      np.asarray(nxt)[live])
        tok = np.where(dn, tok[:, 0], np.asarray(nxt))[:, None].astype(
            np.int32)
        ln, rem = np.where(dn, ln, ln + 1), np.where(dn, rem, rem - 1)
        dn = dn | (rem <= 0) | (ln + 1 >= MAX_LEN)
    assert np.asarray(got[1]).tolist() == [CHUNK, 2, CHUNK, 0]


def test_masked_logits_of_live_slots_are_the_unmasked_ones(chunk):
    loop, params, cache, tokens, _ = chunk
    lengths, _, _, done = _roster()
    step = jax.jit(lambda live: llama.decode_step_with_cache(
        params, jnp.asarray(tokens), cache, jnp.asarray(lengths), loop.cfg,
        live))
    whole, _, counted = step(jnp.ones((SLOTS,), bool))
    masked, stepped, counters = step(jnp.asarray(~done))
    np.testing.assert_array_equal(np.asarray(masked)[~done],
                                  np.asarray(whole)[~done])
    assert int(counted["decode_attn_rows"]) == (lengths + 1).sum()
    assert int(counters["decode_attn_rows"]) == (lengths + 1)[~done].sum()
    # One block a slot at this size: the idle slot's is not fetched.
    assert int(counters["decode_attn_rows_streamed"]) == 3 * MAX_LEN
    # The idle slot's row is still written where it was parked.
    parked = np.asarray(stepped["k"])[:, 3, :, MAX_LEN - 1]
    assert parked.any()
    assert not np.asarray(cache["k"])[:, 3].any()


def test_engine_stats_carry_the_rows_asked_for_and_streamed():
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(max_batch=4, max_len=64, prompt_buckets=[8, 16],
                    decode_chunk=2)
    try:
        eng.generate([5, 9, 2, 7, 1, 3], max_new_tokens=6)
        stats = eng.stats()
    finally:
        eng.close()
    # One live slot of four: 5 decode steps over 7 to 11 rows, by layer.
    rows, streamed = (stats["decode_attn_rows"],
                      stats["decode_attn_rows_streamed"])
    assert rows == sum(range(7, 12))
    assert rows <= streamed <= 6 * 64
