"""The looped family (``models/ouro.py``: one stack of sandwich-norm
blocks crossed ``n_loops`` times with the same weights, a cache entry a
(pass, layer), an exit gate after every pass) against its plain float32
reference (``benchmark/reference/looped_decoder.py``), on the CPU at a
small size, seeded weights; its entries pass by pass; one pass without
the branch norms against ``models/llama.py`` on the same weights; the
decode kernel interpreted at 16 KV heads; the family through
`DecodeLoop` and through the engine.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import ouro as builder
from benchmark.reference import looped_decoder as reference
from ray_tpu.models import llama, ouro

CONFIG = dict(
    head_dim=16, hidden_act="silu", hidden_size=64, intermediate_size=128,
    layer_types=["full_attention"] * 3, max_position_embeddings=128,
    num_attention_heads=4, num_hidden_layers=3, num_key_value_heads=4,
    rms_norm_eps=1e-6, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False, total_ut_steps=4,
    early_exit_threshold=1, use_sliding_window=False, vocab_size=256,
    torch_dtype="float32")
ENGINE = dict(max_batch=2, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4, kv_fleet_min_prefix_blocks=-1)


def _made(loops: int, seed: int = 3):
    """(the file's dict, the program's configuration, seeded weights
    whose norm gains and gate bias are off their initial one and 0)."""
    c = dict(CONFIG, total_ut_steps=loops)
    cfg = builder.config(c)
    params = builder.init_params(cfg, seed)
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(("ln_attn", "ln_attn_out", "ln_mlp",
                              "ln_mlp_out")):
        params["blocks"][name] = 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), params["blocks"][name].shape)
    params["ln_out"] = 0.3 * jax.random.normal(jax.random.fold_in(key, 9),
                                               params["ln_out"].shape)
    params["exit_gate"]["b"] = jnp.asarray(0.2)
    return c, cfg, params


@pytest.fixture(scope="module", params=[4, 1], ids=["T4", "T1"])
def made(request):
    return _made(request.param)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(1, 256, (1, 24)).astype(np.int32)


def _prefill(cfg, params, tokens, cache, at=0):
    return jax.jit(lambda p, t, c: ouro.forward_with_cache(
        p, t, c, at, cfg))(params, tokens, cache)


def test_prefill_then_decode_through_the_cache_equals_the_reference(
        made, tokens):
    """Logits and ``lambda_u`` of every pass: a bucket's, then a step
    at a time through the T x L entries."""
    c, cfg, params = made
    want, gates = reference.both_at(params, tokens,
                                    [(0, t) for t in range(24)], c)
    assert gates.shape == (cfg.n_loops, 24) and np.all(
        (gates > 0) & (gates < 1))
    cache = ouro.init_kv_cache(cfg, 1, 32)
    assert cache["k"].shape == (cfg.n_loops * 3, 1, 4, 32, 16)
    logits, cache, counters, seen = _prefill(cfg, params, tokens[:, :16],
                                             cache)
    np.testing.assert_allclose(logits[0], want[:16], atol=2e-5)
    np.testing.assert_allclose(seen["gates"][:, 0], gates[:, :16], atol=2e-6)
    assert {k: int(v) for k, v in counters.items()} == {
        "loop_prefill_passes": cfg.n_loops}
    step = jax.jit(lambda p, t, c, n: ouro.decode_step_with_cache(
        p, t, c, n, cfg))
    for t in range(16, 24):
        logits, cache, counters, seen = step(params, tokens[:, t:t + 1],
                                             cache, jnp.array([t]))
        np.testing.assert_allclose(logits[0], want[t], atol=2e-5)
        np.testing.assert_allclose(seen["gates"][:, 0], gates[:, t],
                                   atol=2e-6)
    assert {k: int(v) for k, v in counters.items()} == {
        "decode_attn_rows": 24, "decode_attn_rows_streamed": 32,
        "loop_passes": cfg.n_loops, "loop_layer_steps": cfg.n_loops * 3}
    # What a check reads of every block application, in the model's
    # order: what entered and the branches as added give what was handed.
    blocks = jax.tree.map(np.asarray, seen["blocks"])
    assert blocks["handed"].shape == (cfg.n_loops * 3, 1, 64)
    np.testing.assert_allclose(
        blocks["handed"], blocks["entered"] + blocks["attn"] + blocks["ffn"],
        atol=1e-6)
    # At the published threshold every token leaves after the last pass.
    assert (reference.exit_pass(gates, 1.0) == cfg.n_loops).all()
    assert (reference.exit_pass(gates, 0.3) <= cfg.n_loops).all()


def test_chunked_prefill_equals_one_prefill(made, tokens):
    """A second bucket at ``cache_index`` > 0 reads the first's rows
    through the cache, entry by entry; the tick's program reads the
    head at ``last`` alone and bucket padding moves nothing."""
    c, cfg, params = made
    whole, filled, _, _ = _prefill(cfg, params, tokens,
                                   ouro.init_kv_cache(cfg, 1, 32))
    _, cache, _, _ = _prefill(cfg, params, tokens[:, :8],
                              ouro.init_kv_cache(cfg, 1, 32))
    logits, cache, _, _ = _prefill(cfg, params, tokens[:, 8:], cache, at=8)
    np.testing.assert_allclose(logits[0], whole[0, 8:], atol=2e-5)
    np.testing.assert_allclose(cache["k"], filled["k"], atol=1e-5)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :10] = tokens[0, 8:18]
    row, _, _, seen = jax.jit(lambda p, t, c: ouro.forward_last_with_cache(
        p, t, c, 8, 9, cfg))(params, padded, cache)
    np.testing.assert_allclose(row[0], whole[0, 17], atol=2e-5)
    assert seen["gates"].shape == (cfg.n_loops, 1, 16)


def test_entry_u_l_holds_pass_u_of_layer_l(tokens):
    """Entry ``(u - 1) x L + l`` is read by pass u of layer l alone:
    overwriting one entry's rows leaves every earlier pass's gate as it
    was and moves that pass's and the later ones'."""
    _, cfg, params = _made(4)
    _, cache, _, _ = _prefill(cfg, params, tokens[:, :16],
                              ouro.init_kv_cache(cfg, 1, 32))
    step = jax.jit(lambda c: ouro.decode_step_with_cache(
        params, tokens[:, 16:17], c, jnp.array([16]), cfg))
    sound = np.asarray(step(cache)[3]["gates"])[:, 0]
    for u, layer in ((0, 1), (2, 0), (3, 2)):
        entry = u * cfg.n_layers + layer
        spoiled = {k: v.at[entry, :, :, :16].set(0.5 * v[entry, :, :, :16])
                   for k, v in cache.items()}
        got = np.asarray(step(spoiled)[3]["gates"])[:, 0]
        np.testing.assert_array_equal(got[:u], sound[:u])
        assert np.all(np.abs(got[u:] - sound[u:]) > 1e-5), (u, got, sound)


def test_one_pass_without_branch_norms_is_llamas_block(monkeypatch, tokens):
    """T = 1 with the branch norms set to identity on llama's weights:
    llama's logits (the shared ops: `rms_norm`, rotary, the cached
    attention, SwiGLU), prefill and a decode step."""
    lcfg = llama.tiny_config(n_layers=3, n_heads=4, n_kv_heads=4,
                             rope_theta=1e6, norm_eps=1e-6)
    lparams = llama.init_params(lcfg, jax.random.PRNGKey(5))
    cfg = ouro.tiny_config(n_loops=1)
    blocks = lparams["blocks"]
    flat = lambda w: w.reshape(w.shape[0], w.shape[1], -1)  # noqa: E731
    params = {
        "embed": lparams["embed"], "head": lparams["lm_head"],
        "ln_out": lparams["ln_out"],
        "exit_gate": {"w": jnp.zeros((64,)), "b": jnp.zeros(())},
        "blocks": dict(
            {k: blocks[k] for k in ("ln_attn", "ln_mlp", "w_gate", "w_up",
                                    "w_down")},
            ln_attn_out=jnp.zeros((3, 64)), ln_mlp_out=jnp.zeros((3, 64)),
            wq=flat(blocks["wq"]).swapaxes(1, 2),
            wk=flat(blocks["wk"]).swapaxes(1, 2),
            wv=flat(blocks["wv"]).swapaxes(1, 2),
            wo=blocks["wo"].reshape(3, -1, 64))}
    monkeypatch.setattr(ouro, "_branch_norm", lambda y, gain, cfg: y)
    want = llama.forward(lparams, jnp.asarray(tokens), lcfg)[0]
    logits, cache, _, _ = ouro.forward_with_cache(
        params, tokens[:, :16], ouro.init_kv_cache(cfg, 1, 32), 0, cfg)
    np.testing.assert_allclose(logits[0], want[:16], atol=2e-5)
    logits, _, _, _ = ouro.decode_step_with_cache(
        params, tokens[:, 16:17], cache, jnp.array([16]), cfg)
    np.testing.assert_allclose(logits[0], want[16], atol=2e-5)


def test_the_decode_kernel_interpreted_at_16_kv_heads():
    """The step under ``interpret_kernels`` at the published head
    geometry (16 KV heads of 128, a slot's rows one 128-row block) is
    the step with the ``jnp`` twin; a slot that is not live reads no
    row and moves no other's logits."""
    cfg = ouro.tiny_config(d_model=128, n_heads=16, n_kv_heads=16,
                           head_dim=128, n_layers=2, n_loops=2)
    params = ouro.init_params(cfg, jax.random.PRNGKey(1))
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape, a.dtype),
        ouro.init_kv_cache(cfg, 2, 128))
    toks, lengths = jnp.array([[7], [9]]), jnp.array([100, 37])
    live = jnp.array([True, False])
    twin, _, counters, _ = ouro.decode_step_with_cache(
        params, toks, cache, lengths, cfg, live)
    kernel, _, _, _ = ouro.decode_step_with_cache(
        params, toks, cache, lengths,
        dataclasses.replace(cfg, interpret_kernels=True), live)
    np.testing.assert_allclose(kernel[0], twin[0], atol=2e-4)
    assert int(counters["decode_attn_rows"]) == 101
    assert int(counters["decode_attn_rows_streamed"]) == 128


def test_a_threshold_under_one_is_refused():
    with pytest.raises(ValueError, match="ROADMAP R14"):
        ouro.tiny_config(exit_threshold=0.9)
    with pytest.raises(ValueError, match="ROADMAP R14"):
        builder.config(dict(CONFIG, early_exit_threshold=0.5))
    assert builder.config(CONFIG).n_entries == 12
    assert ouro.OuroConfig().param_count() == 2_667_974_657


def test_decode_loop_programs_agree_with_the_functional_step(tokens):
    """The family through `DecodeLoop`: the tick's donated prefill
    hands back the argmax of `prefill_last`'s row, a chunk's tokens are
    the whole step's greedy ones and its counters the steps' sums; the
    two donating check programs return what their functional twins
    return, in the caller's own cache."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    _, cfg, params = _made(4)
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    prompt = np.zeros((1, 32), np.int32)
    prompt[0, :20] = tokens[0, :20]
    put = jax.device_put
    args = (put(prompt), put(np.int32(0)), put(np.int32(0)),
            put(np.int32(19)))
    cache = ouro.init_kv_cache(cfg, 2, 128)
    row, kept, _, seen = loop.prefill_last(params, cache, *args)
    token, cache, counters = loop.prefill_inplace(params, cache, *args)
    assert int(token[0]) == int(jnp.argmax(row[0]))
    assert int(counters["loop_prefill_passes"]) == 4
    toks = np.array([[int(token[0])], [0]], np.int32)
    lengths = np.array([20, 127], np.int32)
    whole, _, _, _ = loop.decode_step_whole(params, kept, put(toks),
                                            put(lengths))
    out = loop.decode_chunk(
        params, cache, put(toks), put(lengths),
        put(np.array([8, 0], np.int32)), put(np.array([-1, -1], np.int32)),
        put(np.array([False, True])))
    assert int(np.asarray(out[0])[0, 0]) == int(jnp.argmax(whole[0]))
    assert {k: int(v) for k, v in out[-1].items()} == {
        "decode_attn_rows": 21 + 22 + 23 + 24,
        "decode_attn_rows_streamed": 4 * 128,
        "loop_passes": 16, "loop_layer_steps": 48}

    fresh = ouro.init_kv_cache(cfg, 2, 128)
    row_d, kept_d, _, seen_d = loop.prefill_last_inplace(params, fresh, *args)
    assert fresh["k"].is_deleted()
    np.testing.assert_array_equal(row_d, row)
    jax.tree.map(np.testing.assert_array_equal, seen_d, seen)
    whole_d, stepped, _, _ = loop.decode_step_whole_inplace(
        params, kept_d, put(toks), put(lengths))
    assert kept_d["k"].is_deleted() and not stepped["k"].is_deleted()
    np.testing.assert_array_equal(whole_d, whole)


def test_the_engine_streams_the_references_greedy_tokens(tokens):
    """Through `serve.run(build_llm_deployment(..))`: the stream is
    greedy decoding of the REFERENCE, teacher-forced; a second request
    with the same prompt reuses its rows (the cache holds no state);
    the loop's counters come home on the fetches the tick makes, 4
    passes a step retired; no optional mechanism is offered."""
    from ray_tpu import serve
    from ray_tpu.serve.engine import InferenceEngine
    from ray_tpu.serve.llm import build_llm_deployment

    c, cfg, params = _made(4)
    handle = serve.run(build_llm_deployment(engine_kwargs=dict(
        cfg=cfg, params=params, **ENGINE)), _local_testing_mode=True)
    engine = handle._instance.engine
    try:
        assert set(engine.cache) == {"k", "v"} and engine.params is params
        assert engine.cache["k"].shape == (12, 2, 4, 128, 16)
        prompt = [int(t) for t in tokens[0, :20]]
        ask = lambda: handle.remote(  # noqa: E731
            {"prompt_ids": prompt, "max_new_tokens": 10}).result()
        got = ask()["token_ids"]
        rows = [(0, 19 + j) for j in range(10)]
        want = reference.logits_at(params, np.asarray([prompt + got]), rows,
                                   c)
        assert got == want.argmax(-1).tolist()
        again = ask()
        assert again["token_ids"] == got and again["cached_prefix_len"] > 0
        stats = engine.stats()
        assert stats["kv_bytes_per_token"] == 12 * 2 * 4 * 16 * 4
        assert stats["loop_passes"] == 4 * stats["chunk_steps_retired"] > 0
        assert stats["loop_layer_steps"] == 3 * stats["loop_passes"]
        assert stats["loop_prefill_passes"] == 4 * 2
    finally:
        engine.close()
    for option in ({"quantize": "int8"}, {"spec_draft_len": 2},
                   {"role": "prefill"}):
        with pytest.raises(ValueError, match="ouro"):
            InferenceEngine(cfg=cfg, params=params, **{**ENGINE, **option})
