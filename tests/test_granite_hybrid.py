"""The dense ``granitemoehybrid`` family (``models/granite_hybrid.py``,
``ops/mamba2.py``) at tiny widths on the CPU, float32, seeded: the
chunked scan against the token-by-token recurrence, the decode kernel
against its twin, the cache path against the plain reference
(``benchmark/reference/mamba2_gqa_decoder.py``, which
``tests/test_granite_hybrid_published.py`` holds to the publisher's
code), and through `LLMEngine` what a per-slot state asks of the
engine: a reset on admission, no prefix reuse, and slots that are not
live left alone. The attention layers' head size is 64 over 2 KV heads,
so that the cache packs two heads a row as the published sizes do."""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import granite_hybrid as builder
from ray_tpu.models import granite_hybrid as granite
from ray_tpu.ops import mamba2

KINDS = ["mamba", "mamba", "attention", "mamba", "mamba", "mamba",
         "attention", "mamba"]
CONFIG = dict(
    vocab_size=256, hidden_size=128, intermediate_size=96,
    shared_intermediate_size=96, num_hidden_layers=8, layer_types=KINDS,
    num_attention_heads=2, num_key_value_heads=2, mamba_n_heads=8,
    mamba_d_head=32, mamba_d_state=16, mamba_expand=2, mamba_n_groups=1,
    mamba_d_conv=4, mamba_chunk_size=16, mamba_conv_bias=True,
    mamba_proj_bias=False, num_local_experts=0, num_experts_per_tok=0,
    position_embedding_type="nope", attention_bias=False,
    tie_word_embeddings=True, embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8,
    max_position_embeddings=128, rms_norm_eps=1e-5, torch_dtype="float32")
ENGINE = dict(max_batch=2, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4, kv_fleet_min_prefix_blocks=-1)
N_MAMBA = KINDS.count("mamba")


@pytest.fixture(scope="module")
def tiny():
    cfg = builder.config(CONFIG)
    assert cfg.kv_pack == 2 and cfg.state_group == 4
    assert cfg.segments == [("mamba", 0, 2), ("attention", 0, 1),
                            ("mamba", 2, 3), ("attention", 1, 1),
                            ("mamba", 5, 1)]
    return cfg, builder.init_params(cfg, 3)


def _scan_inputs(t, seed=0, b=2, h=4, p=10, n=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    bm = jax.random.normal(ks[3], (b, t, n))
    cm = jax.random.normal(ks[4], (b, t, n))
    state = jax.random.normal(ks[5], (b, h, p, n))
    return (x, dt, a, bm, cm), state


# ------------------------------------------------------------------ the ops

@pytest.mark.parametrize("t, chunk, from_zero", [
    (150, 64, True), (64, 64, False), (200, 64, False), (5, 64, False),
    (37, 16, False), (300, 256, True)])
def test_chunk_scan_equals_the_recurrence(t, chunk, from_zero):
    """Whole and partial chunks, lengths across a chunk's boundary, from
    zero and continued from a state."""
    xs, state = _scan_inputs(t, seed=t)
    state = jnp.zeros_like(state) if from_zero else state
    y, s = mamba2.chunk_scan(*xs, state, chunk=chunk)
    y_want, s_want = mamba2.recurrence(*xs, state)
    # A decay inside a chunk is the exp of a DIFFERENCE of running sums:
    # float32 keeps it to 6e-8 of the sums, which grow with the chunk.
    tol = dict(rtol=2e-5 * chunk / 16, atol=5e-5 * chunk / 64)
    np.testing.assert_allclose(y, y_want, **tol)
    np.testing.assert_allclose(s, s_want, **tol)


def test_a_padded_bucket_leaves_the_state_at_the_last_real_token():
    """Padding (dt = 0) after 37 real tokens of a bucket of 100: the
    state is the recurrence's after 37, from a non-zero one; softplus
    alone would have stepped it."""
    (x, dt, a, bm, cm), state = _scan_inputs(100, seed=7)
    real = jnp.arange(100) < 37
    y, s = mamba2.chunk_scan(x, jnp.where(real[None, :, None], dt, 0.0), a,
                             bm, cm, state, chunk=32)
    y_want, s_want = mamba2.recurrence(x[:, :37], dt[:, :37], a, bm[:, :37],
                                       cm[:, :37], state)
    np.testing.assert_allclose(s, s_want, atol=2e-5)
    np.testing.assert_allclose(y[:, :37], y_want, atol=2e-5)
    _, stepped = mamba2.chunk_scan(x, dt, a, bm, cm, state, chunk=32)
    assert float(jnp.abs(stepped - s_want).max()) > 1e-2


@pytest.mark.parametrize("h, p, n, group", [(4, 10, 6, 1), (4, 64, 16, 2),
                                            (8, 32, 128, 4)])
def test_mamba2_decode_kernel_equals_its_twin_exactly(h, p, n, group):
    """Interpreted, on layer 1 of a 3-layer state array: the kernel's
    outputs and tiles are the twin's bit for bit (they run one body),
    the other layers are untouched, and both are the recurrence's one
    step."""
    assert mamba2.state_group(h, p) == group
    (x, dt, a, bm, cm), state = _scan_inputs(1, seed=p, b=3, h=h, p=p, n=n)
    step = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    packed = mamba2.pack_state(state, group)
    np.testing.assert_array_equal(mamba2.unpack_state(packed, group), state)
    stack = jnp.stack([packed + 1.0, packed, packed - 1.0])
    y_twin, s_twin = mamba2.mamba2_decode(stack, jnp.int32(1), *step)
    y_kern, s_kern = mamba2.mamba2_decode(stack, jnp.int32(1), *step,
                                          interpret=True)
    np.testing.assert_allclose(y_kern, y_twin, rtol=0, atol=2e-7)
    np.testing.assert_allclose(s_kern, s_twin, rtol=0, atol=2e-7)
    np.testing.assert_array_equal(s_kern[0], stack[0])
    np.testing.assert_array_equal(s_kern[2], stack[2])
    y_want, s_want = mamba2.recurrence(x, dt, a, bm, cm, state)
    np.testing.assert_allclose(y_twin, y_want[:, 0], atol=1e-5)
    np.testing.assert_allclose(
        mamba2.unpack_state(s_twin[1], group), s_want, atol=1e-5)


def test_the_kernel_steps_a_slot_in_several_blocks():
    """A block smaller than a slot's state: the grid walks the slot's
    tiles in pieces and the result is the one-block kernel's."""
    (x, dt, a, bm, cm), state = _scan_inputs(1, b=2, h=8, p=32, n=16)
    stack = mamba2.pack_state(state, 4)[None]
    step = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    one = mamba2.mamba2_decode(stack, jnp.int32(0), *step, interpret=True)
    two = mamba2.mamba2_decode(stack, jnp.int32(0), *step, interpret=True,
                               block_bytes=16 * 128 * 4)
    for got, want in zip(two, one):
        np.testing.assert_array_equal(got, want)


def test_a_slot_that_is_not_live_keeps_its_state():
    """dt = 0: the tile comes back as it went in."""
    (x, dt, a, bm, cm), state = _scan_inputs(1, b=2)
    stack = mamba2.pack_state(state, 1)[None]
    live = jnp.array([True, False])[:, None]
    _, out = mamba2.mamba2_decode(
        stack, jnp.int32(0), x[:, 0], jnp.where(live, dt[:, 0], 0.0), a,
        bm[:, 0], cm[:, 0], interpret=True)
    np.testing.assert_array_equal(out[0, 1], stack[0, 1])
    assert (out[0, 0] != stack[0, 0]).any()


def test_columns_are_exact():
    """A row spread into columns through the MXU's bf16 products: the
    float32 values come back bit for bit."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (2, 16)) * 37.0
    for row, col in zip(rows, mamba2._columns(rows, 128)):
        np.testing.assert_array_equal(col, jnp.broadcast_to(row[:, None],
                                                            (16, 128)))


# ------------------------------------------------- the model, the reference

def test_forward_equals_the_plain_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(1, 256, (2, 90))
    rows = [(0, i) for i in range(0, 90, 7)] + [(1, 89), (1, 40)]
    want = builder.reference.logits_at(params, tokens, rows, CONFIG)
    logits = granite.forward(params, jnp.asarray(tokens), cfg)
    got = jnp.stack([logits[s, p] for s, p in rows])
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-4


def test_prefill_then_decode_through_the_cache_equals_the_reference(tiny):
    """The tick's prefill of 50 tokens in a bucket of 64 into slots that
    hold another request's leavings, then 40 steps through the cache
    (the kernels interpreted), a third slot parked on its last row:
    every row of logits is the reference's full forward pass's, and the
    first layer's state the reference's recurrence's."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, interpret_kernels=True)
    tokens = np.random.default_rng(1).integers(1, 256, (2, 90))
    rows = [(s, p) for s in range(2) for p in range(49, 90)]
    want = np.asarray(builder.reference.logits_at(
        params, tokens, rows, CONFIG)).reshape(2, 41, -1)
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    cache = jax.tree.map(lambda a: a + 1,
                         granite.init_kv_cache(cfg, 3, 128))
    got = [[], []]
    for s in range(2):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :50] = tokens[s, :50]
        logits, cache, counters = loop.prefill_last(
            params, cache, jnp.asarray(padded), jnp.int32(s), jnp.int32(0),
            jnp.int32(49))
        got[s].append(logits[0])
        assert counters == {"mamba2_prefill_tokens": 50, "state_resets": 1}
    for j in range(50, 90):
        step_tokens = np.zeros((3, 1), np.int32)
        step_tokens[:2, 0] = tokens[:, j]
        logits, cache, counters = loop.decode_step_whole(
            params, cache, jnp.asarray(step_tokens),
            jnp.asarray([j, j, 127], jnp.int32))
        # All three slots live (no mask given): the parked one reads its
        # whole 128 rows, each slot one block here.
        assert counters == {"mamba2_slot_steps": 3 * N_MAMBA,
                            "decode_attn_rows": 2 * (j + 1) + 128,
                            "decode_attn_rows_streamed": 3 * 128}
        for s in range(2):
            got[s].append(logits[s])
    got = np.asarray(got)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-3, err.max()
    for s in range(2):
        state = builder.first_state(cfg, cache, s)
        ref = builder.reference.first_state(params, tokens[s], CONFIG)
        assert float(jnp.linalg.norm(state - ref)
                     / jnp.linalg.norm(ref)) < 1e-5


@pytest.mark.parametrize("rounded", [False, True])
def test_the_first_layers_state_tells_a_bf16_state_from_a_float32_one(rounded):
    """Served in bf16 (the blocks are pre-norm: the first layer's input
    product takes a bf16 operand, which the reference's `first_state`
    rounds as the served precision states), a prompt and 16 steps: the
    float32 state is off the reference's recurrence by what float32
    sums differ by, a state handed on in bf16 by 2^-9 a step, and
    ``serve_hybrid``'s limit lies between them."""
    from benchmark.drivers import serve_hybrid

    config = dict(CONFIG, torch_dtype="bfloat16", mamba_d_state=64)
    cfg = builder.config(config)
    params = builder.init_params(cfg, 5)
    tokens = np.random.default_rng(5).integers(1, 256, (1, 56))

    def hand_on(cache):
        ssm = cache["ssm"]
        if rounded:
            ssm = ssm.astype(jnp.bfloat16).astype(jnp.float32)
        return dict(cache, ssm=ssm)

    padded = np.zeros((1, 64), np.int32)
    padded[0, :40] = tokens[0, :40]
    _, cache, _ = granite.forward_last_with_cache(
        params, jnp.asarray(padded), granite.init_kv_cache(cfg, 1, 128), 0,
        39, cfg)
    cache = hand_on(cache)
    step = jax.jit(lambda tok, cache, n: granite.decode_step_with_cache(
        params, tok, cache, n, cfg)[1])
    for j in range(40, 56):
        cache = hand_on(step(jnp.asarray(tokens[:, j:j + 1]), cache,
                             jnp.asarray([j], jnp.int32)))
    state = builder.first_state(cfg, cache, 0)
    want = builder.reference.first_state(params, tokens[0], config)
    off = float(jnp.linalg.norm(state - want) / jnp.linalg.norm(want))
    limit = serve_hybrid.TOL_STATE_REL_L2
    assert (off > 3 * limit) if rounded else (off < limit / 5), off


def test_a_frozen_slot_is_not_stepped(tiny):
    """The step's ``live`` mask: a slot outside it keeps its state and
    its conv tail bit for bit, and counts no state step."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params = tiny
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    cache = jax.tree.map(lambda a: a + 0.5,
                         granite.init_kv_cache(cfg, 2, 128))
    _, after, counters = loop.decode_step_whole(
        params, cache, jnp.asarray([[5], [7]], jnp.int32),
        jnp.asarray([3, 3], jnp.int32), jnp.asarray([True, False]))
    assert counters["mamba2_slot_steps"] == N_MAMBA
    for name in granite.SLOT_STATE_KEYS:
        np.testing.assert_array_equal(after[name][:, 1], cache[name][:, 1])
        assert (np.asarray(after[name][:, 0])
                != np.asarray(cache[name][:, 0])).any()


def test_chunked_prefill_equals_whole_prefill(tiny):
    """64 tokens in one piece, and as 32 + 32 (the second continued
    from the slot's state, conv tail and rows at ``cache_index`` 32,
    its attention read through the packed cache): the same logits and
    the same cache."""
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(2).integers(1, 256, (1, 64)))
    fresh = granite.init_kv_cache(cfg, 1, 128)
    whole, cache_whole, _ = granite.forward_with_cache(params, tokens, fresh,
                                                       0, cfg)
    first, cache, _ = granite.forward_with_cache(params, tokens[:, :32],
                                                 fresh, 0, cfg)
    second, cache, counters = granite.forward_with_cache(
        params, tokens[:, 32:], cache, 32, cfg)
    assert int(counters["state_resets"]) == 0
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=2e-4)
    for name in cache:
        np.testing.assert_allclose(cache[name][..., :64, :]
                                   if name in "kv" else cache[name],
                                   cache_whole[name][..., :64, :]
                                   if name in "kv" else cache_whole[name],
                                   atol=2e-4, err_msg=name)


def test_two_kv_heads_share_a_cached_row(tiny):
    """The packed row: head 2j in lanes [0, 64), head 2j + 1 in [64,
    128), as written by the prefill and by the step alike."""
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(3).integers(1, 256, (1, 9)))
    cache = granite.init_kv_cache(cfg, 1, 128)
    assert cache["k"].shape == (2, 1, 1, 128, 128)
    _, cache, _ = granite.forward_with_cache(params, tokens[:, :8], cache, 0,
                                             cfg)
    _, stepped, _ = granite.decode_step_with_cache(
        params, tokens[:, 8:], cache, jnp.asarray([8], jnp.int32), cfg)
    _, whole, _ = granite.forward_with_cache(
        params, tokens, granite.init_kv_cache(cfg, 1, 128), 0, cfg)
    for name in "kv":
        np.testing.assert_allclose(stepped[name][..., :9, :],
                                   whole[name][..., :9, :], atol=1e-5)
    assert float(jnp.abs(whole["k"][0, 0, 0, :9, :64]).min()) > 0
    assert float(jnp.abs(whole["k"][0, 0, 0, :9, 64:]).min()) > 0


# -------------------------------------------------------------- the engine

def _serve(tiny, **kwargs):
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_deployment

    cfg, params = tiny
    handle = serve.run(build_llm_deployment(engine_kwargs=dict(
        cfg=cfg, params=params, **{**ENGINE, **kwargs})),
        _local_testing_mode=True)
    return handle, handle._instance.engine


def _ask(handle, prompt, n=10):
    return handle.remote({"prompt_ids": prompt,
                          "max_new_tokens": n}).result()["token_ids"]


def _greedy(tiny, prompt, got):
    """Teacher-forced: each token the argmax after what precedes it."""
    cfg, params = tiny
    logits = granite.forward(params, jnp.asarray([prompt + got]), cfg)[0]
    return np.asarray(jnp.argmax(logits[len(prompt) - 1:-1], -1)).tolist()


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 256, n)] for n in lengths]


def test_engine_resets_a_slots_state_and_reuses_no_prefix(tiny):
    """Through `serve.run(build_llm_deployment(..))`, one slot: request
    B after A gets the tokens a fresh engine gives it (the state was
    reset in the tick's prefill), and A again finds its rows resident,
    reuses none of them (`prefix_reuse_vetoed` counts it) and gets the
    same tokens."""
    a, b = _prompts(0, 40, 20)
    handle, engine = _serve(tiny, max_batch=1)
    try:
        assert set(engine.cache) == {"k", "v", "ssm", "conv"}
        got_a = _ask(handle, a)
        again = _ask(handle, a)
        got_b = _ask(handle, b)
        stats = handle.stats.remote().result()
    finally:
        engine.close()
    fresh, fresh_engine = _serve(tiny, max_batch=1)
    try:
        assert _ask(fresh, b) == got_b
    finally:
        fresh_engine.close()
    assert got_a == _greedy(tiny, a, got_a) and again == got_a
    assert got_b == _greedy(tiny, b, got_b)
    assert stats["prefix_reuse_vetoed"] == 1
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_reused"] == 0
    assert stats["state_resets"] == 3
    assert stats["mamba2_prefill_tokens"] == 40 + 40 + 20
    # 9 decoded tokens a request, a state a Mamba layer each.
    assert stats["mamba2_slot_steps"] == 3 * 9 * N_MAMBA
    # Two attention layers' K and V rows of 2 heads x 64, float32.
    assert stats["kv_bytes_per_token"] == 2 * 2 * 128 * 4
    # The state (8 x 32 x 16) and the last 3 of 288 conv inputs, float32.
    assert stats["state_bytes_per_slot"] == N_MAMBA * (8 * 32 * 16 * 4
                                                       + 3 * 288 * 4)
    # The counters the request's span carries, under the family's names.
    assert engine._span_attrs([{"state_resets": np.int32(1),
                                "mamba2_prefill_tokens": np.int32(40)}]) == {
        "state_reset": 1, "mamba2_prefill_tokens": 40}


def test_slots_that_are_not_live_leave_the_others_alone(tiny):
    """Two slots: a request of 6 tokens freezes in the middle of the
    other's chunks and its slot then idles; the other's 30 tokens are
    the model's own greedy ones, and the idle slot's state is finite
    and as the request left it."""
    long, short = _prompts(1, 40, 20)
    handle, engine = _serve(tiny)
    got = {}
    try:
        threads = [threading.Thread(
            target=lambda k, p, n: got.__setitem__(k, _ask(handle, p, n)),
            args=args) for args in (("long", long, 30), ("short", short, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        state = np.asarray(engine.cache["ssm"])
    finally:
        engine.close()
    assert got["long"] == _greedy(tiny, long, got["long"])
    assert got["short"] == _greedy(tiny, short, got["short"])
    assert np.isfinite(state).all() and state.any(axis=(0, 2, 3, 4)).all()


def test_chunked_prefill_between_decode_chunks(tiny):
    """``prefill_chunk``: a prompt of 100 is prefilled as 32-token
    pieces while the other slot decodes; the decode chunks in between
    must not step the half-built state."""
    first, long = _prompts(2, 40, 100)
    handle, engine = _serve(tiny, prefill_chunk=32)
    got = {}
    try:
        one = threading.Thread(target=lambda: got.__setitem__(
            "first", _ask(handle, first, 40)))
        one.start()
        time.sleep(0.5)
        got["long"] = _ask(handle, long, 10)
        one.join(300)
    finally:
        engine.close()
    assert got["first"] == _greedy(tiny, first, got["first"])
    assert got["long"] == _greedy(tiny, long, got["long"])


def test_the_engine_refuses_what_the_state_cannot_serve(tiny):
    cfg, params = tiny
    from ray_tpu.serve.engine.core import InferenceEngine

    with pytest.raises(ValueError, match="granite_hybrid cannot serve "
                                         "with spec_draft_len yet"):
        InferenceEngine(cfg=cfg, params=params, spec_draft_len=2, **ENGINE)
    assert not hasattr(granite, "ENGINE_OFFERS")
