"""The GLM-4.7-Flash family (``models/glm_moe_lite.py``) against its plain
reference on seeded float32 weights at a tiny size, its latent cache and
its dropless experts; and the engine's model seam: the new family end to
end, what its cache refuses, and llama's programs built as before."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import glm_moe_lite as builder
from benchmark.harness import manifest
from benchmark.reference import mla_moe_decoder as reference
from ray_tpu.models import glm_moe_lite as glm
from ray_tpu.models import llama
from ray_tpu.ops import mla_decode

# Float32 on both sides, the same numbers in another order of
# operations (the absorbed step, a sorted grouped product against a
# loop over experts): 1e-6 to 5e-6 at logits of size 4 here; 2e-4 is
# the dense families' tolerance (tests/benchmark/test_reference.py).
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def tiny():
    """(config dict, program configuration, params): the configuration
    file's own rehearsal sizes, norm gains and the router's bias moved
    off their starting values so that both conventions are compared."""
    with open(manifest.BENCH_DIR / "configs" / "glm-4.7-flash-l7.json") as f:
        c = json.load(f)
    c = {**c, **c["rehearse"]}
    cfg = builder.config(c)
    params = builder.init_params(cfg, 5)
    bump = lambda k, a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(k),
                                                    a.shape, a.dtype)
    for stack in ("dense", "moe"):
        for i, name in enumerate(("ln_attn", "ln_q", "ln_kv", "ln_mlp")):
            params[stack][name] = bump(i, params[stack][name])
    params["ln_out"] = bump(9, params["ln_out"])
    return c, cfg, params


def _tokens(seed, shape, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1, vocab)


def test_full_forward_agrees_with_the_reference(tiny):
    c, cfg, params = tiny
    tokens = _tokens(1, (2, 37))
    rows = [(b, t) for b in range(2) for t in (0, 9, 36)]
    want = reference.logits_at(params, tokens, rows, c)
    got = glm.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.stack([got[b, t] for b, t in rows]), want,
                               **TOL)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_the_latent_cache(tiny, interpret):
    """Prefill 24 tokens into slot 1 of a two-slot cache, then 16 steps
    of the ABSORBED decode over the latent rows: every step's logits
    against the reference's full (expanded) forward pass."""
    c, cfg, params = tiny
    cfg = dataclasses.replace(cfg, interpret_kernels=interpret)
    tokens = _tokens(2, (1, 40))
    want = np.asarray(reference.logits_at(
        params, tokens, [(0, t) for t in range(23, 40)], c))
    cache = glm.init_kv_cache(cfg, 2, 64)
    assert set(cache) == {"kv"} and cache["kv"].shape == (3, 2, 64, 128)
    row = {k: v[:, 1:2] for k, v in cache.items()}
    logits, row, _, seen = glm.forward_with_cache(params, tokens[:, :24], row,
                                                  0, cfg)
    assert seen["experts"].shape == (2, 1, 24, 2)
    np.testing.assert_allclose(logits[0, 23], want[0], **TOL)
    cache = {k: cache[k].at[:, 1:2].set(row[k]) for k in cache}
    step = jax.jit(lambda cache, tok, lens: glm.decode_step_with_cache(
        params, tok, cache, lens, cfg))
    for t in range(24, 40):
        # Slot 0 is idle: parked on its last row, as the engine does.
        tok = jnp.stack([jnp.zeros((1,), jnp.int32), tokens[0, t:t + 1]])
        logits, cache, counters, seen = step(cache, tok,
                                             jnp.array([63, t], jnp.int32))
        np.testing.assert_allclose(logits[1], want[t - 23], **TOL)
    assert int(counters["moe_layer_steps"]) == 2
    assert 2 <= int(counters["moe_expert_hits"]) <= 8
    assert int(counters["mla_decode_rows"]) == 64 + 40
    assert seen["experts"].shape == (2, 2, 1, 2)


def test_absorbed_decode_equals_expanded_attention(tiny):
    """The same position two ways: one decode step (the up-projections
    absorbed, attention over the latent) and a one-token prefill that
    reads back through the cache (the latent rows expanded to per-head
    keys and values)."""
    _, cfg, params = tiny
    tokens = _tokens(3, (1, 20))
    cache = glm.init_kv_cache(cfg, 1, 32)
    _, cache, *_ = glm.forward_with_cache(params, tokens[:, :19], cache, 0,
                                          cfg)
    expanded, *_ = glm.forward_with_cache(params, tokens[:, 19:], cache, 19,
                                          cfg)
    absorbed, *_ = glm.decode_step_with_cache(
        params, tokens[:, 19:], cache, jnp.array([19], jnp.int32), cfg)
    np.testing.assert_allclose(absorbed[0], expanded[0, 0], **TOL)


def test_the_step_takes_the_live_mask_and_does_not_read_it(tiny):
    """The seam hands every family ``live`` (PR 34). This one must go on
    asking for an idle slot's rows: the benchmark's
    ``mla_decode_attn_roofline`` takes them off ``mla_decode_rows``."""
    _, cfg, params = tiny
    cache = glm.init_kv_cache(cfg, 2, 32)
    tok, lens = jnp.ones((2, 1), jnp.int32), jnp.array([31, 7], jnp.int32)
    plain = glm.decode_step_with_cache(params, tok, cache, lens, cfg)
    masked = glm.decode_step_with_cache(params, tok, cache, lens, cfg,
                                        jnp.array([False, True]))
    jax.tree.map(np.testing.assert_array_equal, plain, masked)
    assert int(masked[2]["mla_decode_rows"]) == 32 + 8


def test_tick_prefill_returns_the_last_real_row_and_routes_no_padding(tiny):
    _, cfg, params = tiny
    tokens = _tokens(4, (1, 24))
    want = glm.forward(params, tokens, cfg)[0, 23]
    padded = jnp.pad(tokens, ((0, 0), (0, 8)))
    logits, cache, counters, seen = glm.forward_last_with_cache(
        params, padded, glm.init_kv_cache(cfg, 1, 32), 0, 23, cfg)
    assert seen["experts"].shape == (2, 1, 32, 2)
    assert logits.shape == (1, 256)
    np.testing.assert_allclose(logits[0], want, **TOL)
    assert int(counters["moe_prefill_tokens"]) == 24
    # 24 real tokens x 2 experts over 8 experts, in 2 expert layers.
    assert float(counters["moe_prefill_load_mean"]) == 2 * 24 * 2 / 8
    assert 2 * 6 <= int(counters["moe_prefill_load_max"]) <= 2 * 24
    assert bool(jnp.all(jnp.isfinite(cache["kv"])))


def _one_layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["moe"])


def _loop_over_experts(x, layer, cfg, experts, gates):
    """What the expert layer has to give, pair by pair."""
    y = glm._swiglu(x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            y = y.at[t].add(g * glm._swiglu(
                x[t:t + 1], layer["w_gate"][e], layer["w_up"][e],
                layer["w_down"][e])[0])
    return y


def test_no_token_is_dropped_under_skew(tiny):
    """Every token chooses the SAME two experts and the others get
    none: a layer with a capacity would drop most pairs; this one gives
    the pair-by-pair result, and its load says 40, 40 and zeros."""
    _, cfg, params = tiny
    layer = _one_layer(params)
    # Scores nearly equal for all; the bias alone picks experts 6 and 2.
    layer["router"] = layer["router"] * 1e-3
    layer["router_bias"] = jnp.zeros(8).at[jnp.array([6, 2])].set(0.3)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, cfg.d_model))
    y, experts, load = glm.moe_ffn(
        x, layer, {k: layer[k] for k in glm.EXPERT_STACKS}, 0, cfg)
    assert np.asarray(load).tolist() == [0, 0, 40, 0, 0, 0, 40, 0]
    assert set(np.asarray(experts).ravel()) == {2, 6}
    _, gates = glm.route(x, layer["router"], layer["router_bias"], cfg)
    np.testing.assert_allclose(
        y, _loop_over_experts(x, layer, cfg, experts, gates), **TOL)


def test_experts_are_chosen_on_score_plus_bias_and_weighed_by_score(tiny):
    _, cfg, params = tiny
    layer = _one_layer(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    s = jax.nn.sigmoid(x @ layer["router"])
    experts, gates = glm.route(x, layer["router"], layer["router_bias"], cfg)
    by_biased = np.argsort(-np.asarray(s + layer["router_bias"]), -1)[:, :2]
    by_score = np.argsort(-np.asarray(s), -1)[:, :2]
    assert (np.sort(experts, -1) == np.sort(by_biased, -1)).all()
    # The bias matters: on its own the score would choose otherwise.
    assert (np.sort(by_biased, -1) != np.sort(by_score, -1)).any()
    chosen = np.take_along_axis(np.asarray(s), np.asarray(experts), -1)
    np.testing.assert_allclose(
        gates, 1.8 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


# ------------------------------------------------------------ the kernel

@pytest.mark.parametrize("lengths", [[64, 1, 17, 0], [33, 64, 32, 5]])
def test_mla_decode_kernel_matches_its_reference(lengths):
    """Interpreted: blocks past a slot's length parked and skipped, a
    partial block masked, an empty slot zero; layer 1 of a 2-layer
    cache picked by the index map."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (4, 5, 128))
    cache = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 64, 128))
    lens = jnp.array(lengths, jnp.int32)
    want = mla_decode.mla_decode_attention_reference(
        q, cache[1], lens, v_dim=96, scale=0.25)
    got = mla_decode.mla_decode_attention(
        q, cache, lens, layer=jnp.int32(1), v_dim=96, scale=0.25,
        block_s=16, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[np.asarray(lengths) == 0].any()


# -------------------------------------------------------- the engine seam

ENGINE = dict(max_batch=4, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4)


def test_engine_serves_the_family_end_to_end(tiny):
    """Through `serve.run(build_llm_deployment(..))`: the tokens are
    the model's own greedy ones, the counters came with the fetches
    the tick makes anyway, and the cache is the latent one."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_deployment

    _, cfg, params = tiny
    handle = serve.run(build_llm_deployment(engine_kwargs=dict(
        cfg=cfg, params=params, **ENGINE)), _local_testing_mode=True)
    engine = handle._instance.engine
    try:
        assert set(engine.cache) == {"kv"}
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, n)]
                   for n in (20, 45, 33)]
        for prompt in prompts:
            got = handle.remote({"prompt_ids": prompt,
                                 "max_new_tokens": 10}).result()["token_ids"]
            # Teacher-forced: each token the argmax after what precedes it.
            logits = glm.forward(params, jnp.asarray([prompt + got]), cfg)[0]
            assert got == np.asarray(
                jnp.argmax(logits[len(prompt) - 1:-1], -1)).tolist()
        stats = handle.stats.remote().result()
        # The spans' attributes are the family's to name, not the engine's.
        assert engine._span_attrs([{"moe_expert_hits": np.int32(5),
                                    "moe_layer_steps": np.int32(2)}]) == {
            "experts_touched": 5}
    finally:
        engine.close()
    # One fetch a prefill, one a chunk of 4: 9 decoded tokens = 3 chunks.
    assert stats["decode_host_syncs"] == 3 * 3
    # A prefill's fetch: its token and its three counters, 4 bytes each.
    assert stats["prefill_fetch_bytes"] == 3 * (4 + 3 * 4)
    assert stats["moe_prefill_tokens"] == 20 + 45 + 33
    assert stats["moe_layer_steps"] == 9 * 4 * 2
    assert 0 < stats["moe_expert_hits"] <= stats["moe_layer_steps"] * 8
    assert stats["moe_prefill_load_max"] >= stats["moe_prefill_load_mean"]
    # Every step counts the rows its attention is asked to read: the
    # one live slot's (9 steps, then 3 frozen at its last length to the
    # chunk's end), and the three idle ones parked on their last row.
    assert stats["mla_decode_rows"] == sum(
        sum(len(p) + 1 + j for j in range(9)) + 3 * (len(p) + 10)
        + 12 * 3 * 128 for p in prompts)
    assert stats["kv_bytes_per_token"] == 3 * 128 * 4    # layers x W x f32


@pytest.mark.parametrize("option", [
    dict(quantize="int8"), dict(spec_draft_len=2),
    dict(role="prefill"), dict(kv_fleet_min_prefix_blocks=0)],
    ids=["quantize", "spec_draft_len", "role", "kv_fleet"])
def test_engine_refuses_what_the_latent_cache_cannot_do(tiny, option):
    from ray_tpu.serve.engine.core import InferenceEngine

    _, cfg, params = tiny
    name = next(iter(option))
    name = {"kv_fleet_min_prefix_blocks": "kv_fleet"}.get(name, name)
    with pytest.raises(ValueError, match=f"cannot serve with {name} yet"):
        InferenceEngine(cfg, params, **ENGINE, **option)


def test_llama_builds_its_programs_from_its_own_functions(monkeypatch):
    """The seam's three functions are the ones `DecodeLoop` traces for
    the llama family: the check's prefill returns the bucket's logits,
    the tick's a token (no counters), the chunk its seven results and
    the step's counters."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg = llama.tiny_config(max_seq_len=64)
    assert cfg.model is llama
    called = []
    for name in ("forward_with_cache", "forward_last_with_cache",
                 "decode_step_with_cache"):
        fn = getattr(llama, name)
        monkeypatch.setattr(
            llama, name,
            lambda *a, _fn=fn, _name=name: called.append(_name) or _fn(*a))
    loop = DecodeLoop(cfg, max_len=64, chunk=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = llama.init_kv_cache(cfg, 2, 64)
    prompt, zero = jnp.zeros((1, 16), jnp.int32), jnp.int32(0)
    out = loop.prefill(params, cache, prompt, zero, zero)
    assert len(out) == 2 and out[0].shape == (1, 16, cfg.vocab_size)
    out = loop.prefill_inplace(params, out[1], prompt, zero, zero,
                               jnp.int32(15))
    assert len(out) == 2 and out[0].shape == (1,)
    assert out[0].dtype == jnp.int32
    vec = jnp.zeros((2,), jnp.int32)
    out = loop.decode_chunk(params, out[1], jnp.zeros((2, 1), jnp.int32), vec,
                            vec + 4, vec - 1, jnp.zeros((2,), bool))
    assert len(out) == 8 and set(out[7]) == {
        "decode_attn_rows", "decode_attn_rows_streamed"}
    assert called == ["forward_with_cache", "forward_last_with_cache",
                      "decode_step_with_cache"]


# Both families through the one tick prefill: (model module,
# configuration, params) by name.
FAMILIES = ["llama", "llama-tied", "glm"]
BUCKET, MAX_LEN = 16, 64


@pytest.fixture(scope="module")
def families(tiny):
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    out = {"glm": (glm,) + tiny[1:]}
    for name, tied in (("llama", False), ("llama-tied", True)):
        cfg = llama.tiny_config(max_seq_len=MAX_LEN, tie_embeddings=tied)
        out[name] = (llama, cfg, llama.init_params(cfg, jax.random.PRNGKey(7)))
    return {name: (model, cfg, params,
                   DecodeLoop(cfg, max_len=MAX_LEN, chunk=4))
            for name, (model, cfg, params) in out.items()}


@pytest.mark.parametrize("cache_index", [0, 24], ids=["fresh", "prefix"])
@pytest.mark.parametrize("last", [0, 7, BUCKET - 1])
@pytest.mark.parametrize("family", FAMILIES)
def test_tick_prefill_is_the_checks_prefill_with_one_row_of_head(
        families, family, last, cache_index):
    """`prefill_last`'s row is `prefill`'s ``logits[0, last]`` (one body
    up to the final norm), after a resident prefix too; the tick's
    program gives that row's argmax and `prefill_last`'s cache."""
    model, cfg, params, loop = families[family]
    slot, put = jnp.int32(1), jnp.int32
    tokens = _tokens(11, (1, cache_index + BUCKET))
    cache = model.init_kv_cache(cfg, 2, MAX_LEN)
    if cache_index:
        cache = loop.prefill(params, cache, tokens[:, :cache_index], slot,
                             put(0))[1]
    # Real tokens up to ``last``, then the bucket's padding.
    real = jnp.arange(BUCKET) <= last
    chunk = jnp.where(real, tokens[:, cache_index:], 0)
    args = (chunk, slot, put(cache_index))
    whole, want_cache, *_ = loop.prefill(params, cache, *args)
    row, got_cache, *_ = loop.prefill_last(params, cache, *args, put(last))
    assert whole.shape == (1, BUCKET, cfg.vocab_size)
    assert row.shape == (1, cfg.vocab_size)
    np.testing.assert_allclose(row[0], whole[0, last], rtol=1e-5, atol=1e-5)
    # The slot's rows up to the last real token: what follows is
    # padding's, past the slot's length (a routed family gives its
    # padding to no expert, so those rows may differ).
    rows = cache_index + last + 1
    for key in cache:
        axis = cache[key].ndim - 2              # [.., rows, width]
        take = lambda a: np.asarray(jnp.take(a, jnp.arange(rows), axis))
        np.testing.assert_allclose(take(got_cache[key]),
                                   take(want_cache[key]),
                                   rtol=1e-5, atol=1e-5)
    token, ticked, *counters = loop.prefill_inplace(
        params, jax.tree.map(jnp.copy, cache), *args, put(last))
    assert token.shape == (1,) and token.dtype == jnp.int32
    assert int(token[0]) == int(np.argmax(np.asarray(row[0])))
    for key in cache:
        np.testing.assert_array_equal(ticked[key], got_cache[key])
    assert len(counters) == (1 if family == "glm" else 0)


PROMPTS = [20, 45, 33]


@pytest.mark.parametrize("prefill_chunk", [0, 16], ids=["whole", "chunked"])
@pytest.mark.parametrize("family", FAMILIES)
def test_engine_tokens_are_the_models_greedy_ones(families, family,
                                                  prefill_chunk):
    """End to end, the first token from the tick's device argmax:
    every token is the argmax after what precedes it (teacher-forced
    through the family's full forward), with prompts over one and
    several chunks and a repeated prompt that hits the prefix cache."""
    from ray_tpu.serve.engine.core import InferenceEngine

    model, cfg, params, _ = families[family]
    engine = InferenceEngine(cfg, params, prefill_chunk=prefill_chunk,
                             **ENGINE)
    try:
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, 256, n)] for n in PROMPTS]
        prompts.append(prompts[1][:40] + [3, 1, 4])
        for prompt in prompts:
            out = engine.generate(prompt, max_new_tokens=10)
            got = out["token_ids"]
            logits = model.forward(params, jnp.asarray([prompt + got]), cfg)[0]
            assert got == np.asarray(
                jnp.argmax(logits[len(prompt) - 1:-1], -1)).tolist()
    finally:
        engine.close()
    assert out["cached_prefix_len"] > 0
