"""The Olmo-Hybrid family (``models/olmo_hybrid.py``,
``ops/gated_delta.py``) at tiny widths on the CPU, float32, seeded:
the chunked scan against the token-by-token recurrence, the decode
kernel against its twin, the cache path against the plain reference
(``benchmark/reference/gated_delta_decoder.py``), and through
`LLMEngine` what a per-slot state asks of the engine: a reset on
admission, no prefix reuse, and slots that are not live left alone.
The widths are no multiples of 8 (dk 6, dv 10), so that a padding bug
shows."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import olmo_hybrid as builder
from ray_tpu.models import olmo_hybrid as hybrid
from ray_tpu.ops import gated_delta

CONFIG = dict(
    vocab_size=256, hidden_size=60, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=6, linear_value_head_dim=10,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    max_position_embeddings=128, rms_norm_eps=1e-6, torch_dtype="float32",
    rope_parameters={"rope_theta": None}, tie_word_embeddings=False,
    attention_bias=False)
ENGINE = dict(max_batch=2, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4, kv_fleet_min_prefix_blocks=-1)


@pytest.fixture(scope="module")
def tiny():
    cfg = builder.config(CONFIG)
    return cfg, builder.init_params(cfg, 3)


def _scan_inputs(t, seed=0, b=2, h=4, dk=6, dv=10):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gated_delta.l2_normalize(jax.random.normal(ks[0], (b, t, h, dk)))
    k = gated_delta.l2_normalize(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -2.0 * jax.random.uniform(ks[3], (b, t, h))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    state = jax.random.normal(ks[5], (b, h, dv, dk))
    return (q * dk ** -0.5, k, v, g, beta), state


# ------------------------------------------------------------------ the ops

@pytest.mark.parametrize("t, from_zero", [(150, True), (64, False),
                                          (200, False), (5, False)])
def test_chunk_scan_equals_the_recurrence(t, from_zero):
    """Whole and partial chunks, from zero and continued from a state."""
    xs, state = _scan_inputs(t, seed=t)
    state = jnp.zeros_like(state) if from_zero else state
    o, s = gated_delta.chunk_scan(*xs, state)
    o_want, s_want = gated_delta.recurrence(*xs, state)
    np.testing.assert_allclose(o, o_want, atol=2e-5)
    np.testing.assert_allclose(s, s_want, atol=2e-5)


def test_a_padded_bucket_leaves_the_state_at_the_last_real_token():
    """Padding (alpha = 1, beta = 0) after 37 real tokens of a bucket of
    100: the state is the recurrence's after 37, from a non-zero one."""
    (q, k, v, g, beta), state = _scan_inputs(100, seed=7)
    real = jnp.arange(100) < 37
    g_pad = jnp.where(real[None, :, None], g, 0.0)
    beta_pad = jnp.where(real[None, :, None], beta, 0.0)
    o, s = gated_delta.chunk_scan(q, k, v, g_pad, beta_pad, state)
    o_want, s_want = gated_delta.recurrence(
        q[:, :37], k[:, :37], v[:, :37], g[:, :37], beta[:, :37], state)
    np.testing.assert_allclose(s, s_want, atol=2e-5)
    np.testing.assert_allclose(o[:, :37], o_want, atol=2e-5)


def test_causal_conv_tail_is_taken_at_the_real_length():
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    zero = jnp.zeros((1, 15))
    y, tail = gated_delta.causal_conv(u, zero, w, n_real=7)
    np.testing.assert_array_equal(tail.reshape(3, 5), u[0, 4:7])
    # A prompt shorter than the tail keeps what was there before it.
    _, short = gated_delta.causal_conv(u, tail, w, n_real=1)
    np.testing.assert_array_equal(short.reshape(3, 5),
                                  jnp.concatenate([u[0, 5:7], u[0, :1]]))
    # Token by token from that tail: the same outputs as in one piece.
    y_all, _ = gated_delta.causal_conv(u, zero, w)
    for t in range(7, 12):
        y_t, tail = gated_delta.causal_conv_step(u[:, t], tail, w)
        np.testing.assert_allclose(y_t, y_all[:, t], atol=1e-6)
    np.testing.assert_allclose(y[:, :7], y_all[:, :7], atol=1e-6)


@pytest.mark.parametrize("h, dk, dv, group", [(4, 6, 10, 1), (4, 8, 64, 2),
                                              (6, 16, 64, 2)])
def test_gdn_decode_kernel_equals_its_twin_exactly(h, dk, dv, group):
    """Interpreted, on layer 1 of a 3-layer state array: the kernel's
    outputs and tiles are the twin's bit for bit where the tile is
    whole sublanes and lanes, as the chip's are (at 6 x 10 the CPU
    compiler orders the two programs' sums differently: one unit in the
    last place), the other layers are untouched, and both are the
    recurrence's one step."""
    assert gated_delta.state_group(h, dv) == group
    (q, k, v, g, beta), state = _scan_inputs(1, seed=dv, b=3, h=h, dk=dk,
                                             dv=dv)
    step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    packed = gated_delta.pack_state(state, group)
    np.testing.assert_array_equal(gated_delta.unpack_state(packed, group),
                                  state)
    stack = jnp.stack([packed + 1.0, packed, packed - 1.0])
    o_twin, s_twin = gated_delta.gdn_decode(stack, jnp.int32(1), *step)
    o_kern, s_kern = gated_delta.gdn_decode(stack, jnp.int32(1), *step,
                                            interpret=True)
    exactly = dict(rtol=0, atol=0 if dk % 8 == 0 else 2e-7)
    np.testing.assert_allclose(o_kern, o_twin, **exactly)
    np.testing.assert_allclose(s_kern, s_twin, **exactly)
    np.testing.assert_array_equal(s_kern[0], stack[0])
    np.testing.assert_array_equal(s_kern[2], stack[2])
    o_want, s_want = gated_delta.recurrence(q, k, v, g, beta, state)
    np.testing.assert_allclose(o_twin, o_want[:, 0], atol=1e-5)
    np.testing.assert_allclose(
        gated_delta.unpack_state(s_twin[1], group), s_want, atol=1e-5)


def test_a_slot_that_is_not_live_keeps_its_state():
    """alpha = 1, beta = 0: the tile comes back as it went in."""
    (q, k, v, g, beta), state = _scan_inputs(1, b=2)
    stack = gated_delta.pack_state(state, 1)[None]
    live = jnp.array([True, False])[:, None]
    _, out = gated_delta.gdn_decode(
        stack, jnp.int32(0), q[:, 0], k[:, 0], v[:, 0],
        jnp.where(live, g[:, 0], 0.0), jnp.where(live, beta[:, 0], 0.0),
        interpret=True)
    np.testing.assert_array_equal(out[0, 1], stack[0, 1])
    assert (out[0, 0] != stack[0, 0]).any()


# ------------------------------------------------- the model, the reference

def test_forward_equals_the_plain_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(0).integers(1, 256, (2, 90))
    rows = [(0, i) for i in range(0, 90, 7)] + [(1, 89), (1, 40)]
    want = builder.reference.logits_at(params, tokens, rows, CONFIG)
    logits = hybrid.forward(params, jnp.asarray(tokens), cfg)
    got = jnp.stack([logits[s, p] for s, p in rows])
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-4


def test_prefill_then_decode_through_the_cache_equals_the_reference(tiny):
    """The tick's prefill of 50 tokens in a bucket of 64 into slots that
    hold another request's leavings, then 40 steps through the cache
    (the kernels interpreted), a third slot parked on its last row:
    every row of logits is the reference's full forward pass's."""
    import dataclasses

    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, interpret_kernels=True)
    tokens = np.random.default_rng(1).integers(1, 256, (2, 90))
    rows = [(s, p) for s in range(2) for p in range(49, 90)]
    want = np.asarray(builder.reference.logits_at(
        params, tokens, rows, CONFIG)).reshape(2, 41, -1)
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    cache = jax.tree.map(lambda a: a + 1,
                         hybrid.init_kv_cache(cfg, 3, 128))
    got = [[], []]
    for s in range(2):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :50] = tokens[s, :50]
        logits, cache, counters = loop.prefill_last(
            params, cache, jnp.asarray(padded), jnp.int32(s), jnp.int32(0),
            jnp.int32(49))
        got[s].append(logits[0])
        assert counters == {"gdn_prefill_tokens": 50, "state_resets": 1}
    for j in range(50, 90):
        step_tokens = np.zeros((3, 1), np.int32)
        step_tokens[:2, 0] = tokens[:, j]
        logits, cache, counters = loop.decode_step_whole(
            params, cache, jnp.asarray(step_tokens),
            jnp.asarray([j, j, 127], jnp.int32))
        assert counters == {"gdn_slot_steps": 3 * 6}
        for s in range(2):
            got[s].append(logits[s])
    got = np.asarray(got)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-3, err.max()
    assert np.isfinite(np.asarray(cache["state"])).all()


def test_chunked_prefill_equals_whole_prefill(tiny):
    """64 tokens in one piece, and as 32 + 32 (the second continued
    from the slot's state, conv tail and rows at ``cache_index`` 32):
    the same logits and the same cache (to what float32 keeps through
    8 layers: the two read the full layers' rows in another order)."""
    cfg, params = tiny
    tokens = jnp.asarray(np.random.default_rng(2).integers(1, 256, (1, 64)))
    fresh = hybrid.init_kv_cache(cfg, 1, 128)
    whole, cache_whole, _ = hybrid.forward_with_cache(params, tokens, fresh,
                                                      0, cfg)
    first, cache, _ = hybrid.forward_with_cache(params, tokens[:, :32],
                                                fresh, 0, cfg)
    second, cache, counters = hybrid.forward_with_cache(
        params, tokens[:, 32:], cache, 32, cfg)
    assert int(counters["state_resets"]) == 0
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=5e-3)
    for name in cache:
        np.testing.assert_allclose(cache[name][..., :64, :]
                                   if name in "kv" else cache[name],
                                   cache_whole[name][..., :64, :]
                                   if name in "kv" else cache_whole[name],
                                   atol=5e-3, err_msg=name)


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
    logits = hybrid.forward(params, jnp.asarray([prompt + got]), cfg)[0]
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
        assert set(engine.cache) == {"k", "v", "state", "conv"}
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
    assert stats["gdn_prefill_tokens"] == 40 + 40 + 20
    # 9 decoded tokens a request, a state a linear layer each.
    assert stats["gdn_slot_steps"] == 3 * 9 * 6
    # Two full layers' K and V rows of 4 heads x 15, float32.
    assert stats["kv_bytes_per_token"] == 2 * 2 * 60 * 4
    assert stats["state_bytes_per_slot"] == 6 * (4 * 6 * 10 * 4
                                                 + 3 * 4 * 22 * 4)
    # The counter the request's span carries, under the family's name.
    assert engine._span_attrs([{"state_resets": np.int32(1)}]) == {
        "state_reset": 1}


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
        state = np.asarray(engine.cache["state"])
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
