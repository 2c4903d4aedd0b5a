"""The ZAYA1 family (``models/zaya.py``) against its plain reference on
seeded float32 weights at a tiny size: CCA's tail through the cache
(chunks, padding, a slot's new owner, the live mask), the one-expert
router without drops, rotary on half a head, the engine's seam end to
end; and the grouped product both routed families share."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import zaya as builder
from benchmark.harness import manifest
from benchmark.reference import cca_top1_decoder as reference
from ray_tpu.models import zaya
from ray_tpu.ops import apply_rope
from ray_tpu.ops.grouped_experts import expert_stacks, split_expert_stacks

# Float32 on both sides, the same numbers in another order of
# operations: 1e-6 to 5e-6 at logits of size 4 here; 2e-4 is the dense
# families' tolerance (tests/benchmark/test_reference.py).
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def tiny():
    """(config dict, program configuration, params): the configuration
    file's own rehearsal sizes, norm gains moved off their starting
    values so that both conventions are compared."""
    with open(manifest.BENCH_DIR / "configs" / "zaya1-8b-l16.json") as f:
        c = json.load(f)
    c = {**c, **c["rehearse"]}
    cfg = builder.config(c)
    params = builder.init_params(cfg, 5)
    bump = lambda k, a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(k),
                                                    a.shape, a.dtype)
    for i, name in enumerate(("ln_attn", "ln_mlp", "ln_router")):
        params["layers"][name] = bump(i, params["layers"][name])
    params["ln_out"] = bump(9, params["ln_out"])
    return c, cfg, params


def _tokens(seed, shape, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1, vocab)


def _slot(cache, i):
    return {k: v[:, i:i + 1] for k, v in cache.items()}


def test_full_forward_agrees_with_the_reference(tiny):
    c, cfg, params = tiny
    tokens = _tokens(1, (2, 37))
    rows = [(b, t) for b in range(2) for t in (0, 1, 9, 36)]
    want = reference.logits_at(params, tokens, rows, c)
    got = zaya.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.stack([got[b, t] for b, t in rows]), want,
                               **TOL)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_the_cache(tiny, interpret):
    """Prefill 24 tokens into slot 1 of a two-slot cache, then 16 decode
    steps from the tail and over the rows: every step's logits, and
    what the slot keeps at the end, against the reference's full
    forward pass."""
    c, cfg, params = tiny
    cfg = dataclasses.replace(cfg, interpret_kernels=interpret)
    tokens = _tokens(2, (1, 40))
    want = np.asarray(reference.logits_at(
        params, tokens, [(0, t) for t in range(23, 40)], c))
    cache = zaya.init_kv_cache(cfg, 2, 64)
    assert cache["k"].shape == (3, 2, 2, 64, 16)
    assert cache["tail"].shape == (3, 2, cfg.tail_dim) and cfg.tail_dim == 208
    logits, row, counters, seen = zaya.forward_with_cache(
        params, tokens[:, :24], _slot(cache, 1), 0, cfg)
    assert seen["experts"].shape == (3, 1, 24, 1)
    assert int(counters["state_resets"]) == 1
    np.testing.assert_allclose(logits[0, 23], want[0], **TOL)
    cache = {k: cache[k].at[:, 1:2].set(row[k]) for k in cache}
    step = jax.jit(lambda cache, tok, lens: zaya.decode_step_with_cache(
        params, tok, cache, lens, cfg, jnp.array([False, True])))
    for j in range(16):
        tok = jnp.stack([jnp.zeros((1,), jnp.int32), tokens[0, 24 + j][None]])
        logits, cache, counters, seen = step(
            cache, tok, jnp.array([63, 24 + j], jnp.int32))
        np.testing.assert_allclose(logits[1], want[1 + j], **TOL)
    assert int(counters["moe_layer_steps"]) == 3
    assert 3 <= int(counters["moe_expert_hits"]) <= 6       # 2 tokens a layer
    assert int(counters["decode_attn_rows"]) == 3 * 0 + 40  # the live slot's
    kept = reference.kept_at(params, tokens[0], [0, 2], c)
    for i in (0, 2):
        tail = np.concatenate([kept[i][k][39] for k in ("u", "a", "v_next")])
        np.testing.assert_allclose(cache["tail"][i, 1], tail, **TOL)
        for key in ("k", "v"):
            np.testing.assert_allclose(
                cache[key][i, 1, :, :40].swapaxes(0, 1), kept[i][key], **TOL)
    # The slot that was not live kept its tail: zero, as made.
    assert not cache["tail"][:, 0].any()


def test_a_prompt_prefilled_in_two_chunks_equals_one_prefill(tiny):
    """The tail is carried from chunk to chunk: the second chunk's first
    token reads the first chunk's last."""
    c, cfg, params = tiny
    tokens = _tokens(3, (1, 32))
    row = _slot(zaya.init_kv_cache(cfg, 1, 64), 0)
    want, whole, _, _ = zaya.forward_with_cache(params, tokens, row, 0, cfg)
    first, part, _, _ = zaya.forward_with_cache(params, tokens[:, :16], row,
                                                0, cfg)
    second, part, counters, _ = zaya.forward_with_cache(
        params, tokens[:, 16:], part, 16, cfg)
    assert int(counters["state_resets"]) == 0
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1),
                               want, **TOL)
    for key in whole:
        np.testing.assert_allclose(part[key], whole[key], **TOL)


def test_a_buckets_padding_leaves_the_tail_of_the_last_real_token(tiny):
    """The tick's prefill of 21 real tokens in a bucket of 32: the last
    real row's logits, the tail of token 20 (not of the padding), the
    padding given to no expert."""
    c, cfg, params = tiny
    tokens = _tokens(4, (1, 21))
    row = _slot(zaya.init_kv_cache(cfg, 1, 64), 0)
    want, exact, _, _ = zaya.forward_with_cache(params, tokens, row, 0, cfg)
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :21].set(tokens)
    got, bucket, counters, seen = zaya.forward_last_with_cache(
        params, padded, row, 0, 20, cfg)
    np.testing.assert_allclose(got[0], want[0, 20], **TOL)
    np.testing.assert_allclose(bucket["tail"], exact["tail"], **TOL)
    assert int(counters["moe_prefill_tokens"]) == 21
    assert float(counters["moe_prefill_load_mean"]) == pytest.approx(
        3 * 21 / 8)
    assert seen["router_p"].shape == (3, 1, 32, 8)


def test_a_slot_taken_over_decodes_as_a_fresh_one(tiny):
    """A prefill at row 0 resets the slot: the tail the last owner left
    is not read, so the new owner's logits and first rows are those of
    an empty slot."""
    c, cfg, params = tiny
    old, new = _tokens(5, (1, 24)), _tokens(6, (1, 16))
    empty = _slot(zaya.init_kv_cache(cfg, 1, 64), 0)
    _, used, _, _ = zaya.forward_with_cache(params, old, empty, 0, cfg)
    assert used["tail"].any()
    want, fresh, _, _ = zaya.forward_with_cache(params, new, empty, 0, cfg)
    got, taken, counters, _ = zaya.forward_with_cache(params, new, used, 0,
                                                      cfg)
    assert int(counters["state_resets"]) == 1
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(taken["tail"], fresh["tail"])
    np.testing.assert_array_equal(taken["k"][..., :16, :],
                                  fresh["k"][..., :16, :])
    step = lambda cache: zaya.decode_step_with_cache(
        params, jnp.array([[7]]), cache, jnp.array([16]), cfg)[0]
    np.testing.assert_array_equal(step(taken), step(fresh))


def test_top1_drops_nothing_under_a_router_skewed_to_one_expert(tiny):
    """A bias that sends every token to expert 3: the group holds all of
    them, and each gets that expert's SwiGLU weighted by its own p."""
    c, cfg, params = tiny
    layer = jax.tree.map(lambda a: a[1], params["layers"])
    layer["router_bias"] = layer["router_bias"].at[3].set(10.0)
    stacks = expert_stacks(params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (19, cfg.d_model))
    valid = jnp.arange(19) < 17
    y, expert, load, seen = zaya.moe_ffn(x, layer, stacks, 1, cfg, valid)
    assert (np.asarray(expert) == 3).all()
    assert load.tolist() == [0, 0, 0, 17, 0, 0, 0, 0]
    g, p = seen["router_in"], seen["router_p"]
    np.testing.assert_allclose(
        p, reference.router_probs(params, 1, g, c), rtol=1e-5, atol=1e-7)
    f = lambda name: layer[name][3]
    want = ((jax.nn.silu(g @ f("w_gate")) * (g @ f("w_up"))) @ f("w_down")
            * p[:, 3:4])
    np.testing.assert_allclose(y[:17], want[:17], **TOL)
    assert not y[17:].any()                 # padding reaches no expert


def test_rotary_on_half_a_head_is_the_references_rotate_half():
    """`apply_rope` on the slice ``[..., :64]`` of a head of 128 and the
    untouched half joined on, at theta 5e6 and positions up to 2,047,
    against the reference's own rotate-half (``ops/rotary.py`` is not
    touched: llama's and GLM's programs lower as before)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 3, 128))
    got = jnp.concatenate(
        [apply_rope(x[None, ..., :64], jnp.arange(2048)[None], 5e6)[0],
         x[..., 64:]], axis=-1)
    want = jnp.concatenate(
        [reference._rotate_half(x[..., :64], 5e6), x[..., 64:]], axis=-1)
    # float32 angles up to 2,047 radians: 1e-4 of a unit-size entry.
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    assert not np.allclose(got[2047, :, :64], x[2047, :, :64], atol=1e-2)


def test_the_engine_serves_the_family_through_its_seam(tiny):
    """`InferenceEngine` through ``cfg.model``: greedy tokens equal the
    functional path's, the family's counters are in ``stats()``, and a
    second request in the same slot is a fresh one (the reset)."""
    from ray_tpu.serve.engine.core import InferenceEngine

    c, cfg, params = tiny
    prompts = [[int(t) for t in _tokens(10 + i, (n,))]
               for i, n in enumerate((9, 21, 14))]

    def functional(prompt, answer):
        """Greedy, teacher-forced: one causal pass over prompt + answer
        gives every step's argmax."""
        logits = zaya.forward(params, jnp.asarray([prompt + answer]), cfg)
        at = len(prompt) - 1
        return [int(t) for t in jnp.argmax(logits[0, at:at + len(answer)],
                                           axis=-1)]

    engine = InferenceEngine(cfg, params, max_batch=1, max_len=64,
                             prompt_buckets=[16, 32], decode_chunk=4,
                             kv_fleet_min_prefix_blocks=-1)
    try:
        got = [engine.generate(p, max_new_tokens=6)["token_ids"]
               for p in prompts]
        stats = engine.stats()
    finally:
        engine.close()
    assert got == [functional(p, a) for p, a in zip(prompts, got)]
    assert stats["state_resets"] == 3 and stats["prefix_reuse_vetoed"] == 0
    assert stats["moe_layer_steps"] > 0
    assert stats["moe_expert_hits"] == stats["moe_layer_steps"]  # one slot
    assert stats["moe_decode_load_max"] == stats["moe_layer_steps"]
    assert stats["moe_prefill_load_max"] > 0
    assert stats["state_bytes_per_slot"] == 3 * cfg.tail_dim * 4
    assert stats["kv_bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    assert engine._span_attrs([{"state_resets": 1, "moe_prefill_load_max": 5}
                               ]) == {"state_reset": 1, "experts_max_load": 5}


def test_the_configuration_file_keeps_every_published_width():
    with open(manifest.BENCH_DIR / "configs" / "zaya1-8b-l16.json") as f:
        c = json.load(f)
    cfg = builder.config(c)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 8, 2, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_d_ff,
            cfg.router_d, cfg.vocab_size) == (16, 1, 2048, 256, 262272)
    assert (cfg.cca_time0, cfg.cca_time1, cfg.rotary_dim, cfg.rope_theta) == (
        2, 2, 64, 5e6)
    assert cfg.n_layers == 16 >= 12 and cfg.tail_dim == 2688
    assert set(c["reduced"]) == {"num_hidden_layers", "layer_types",
                                 "max_position_embeddings"}
    assert set(c["departures"]) == {"router_input_averaging",
                                    "residual_scales", "skip_choice"}
    with pytest.raises(ValueError, match="one entry a layer"):
        builder.config({**c, "layer_types": ["hybrid"] * 15})


def test_glm_moe_ffn_is_bit_identical_through_the_shared_grouped_product():
    """``models/glm_moe_lite.moe_ffn`` after its sort, group sizes, three
    `ragged_dot`s and unsort moved to ``ops/grouped_experts.py``: the
    values it gave BEFORE the move (PR 40's parent, XLA:CPU, float32),
    with and without a bucket's padding."""
    from benchmark.builders import glm_moe_lite as glm_builder
    from ray_tpu.models import glm_moe_lite as glm

    with open(manifest.BENCH_DIR / "configs" / "glm-4.7-flash-l7.json") as f:
        c = json.load(f)
    cfg = glm_builder.config({**c, **c["rehearse"]})
    params = glm_builder.init_params(cfg, 5)
    stacks, scanned = split_expert_stacks(params["moe"])
    layer = jax.tree.map(lambda a: a[1], scanned)
    x = jax.random.normal(jax.random.PRNGKey(3), (11, cfg.d_model),
                          jnp.float32)
    pinned = {
        None: ([[0.00885075330734253, -0.04995022714138031,
                 0.6396564245223999],
                [-1.9251670837402344, 0.32802602648735046,
                 -0.7254855632781982],
                [-0.8083844780921936, 1.504697561264038,
                 0.5662774443626404]],
               [3, 5, 3, 3, 2, 2, 1, 3], "0x1.344fc80000000p+9"),
        9: ([[0.00885075330734253, -0.04995022714138031,
              0.6396564245223999],
             [-1.9251670837402344, 0.32802602648735046,
              -0.7254855632781982],
             [0.2496459037065506, 0.45165711641311646,
              0.577458381652832]],
            [2, 4, 3, 3, 2, 1, 0, 3], "0x1.2889e40000000p+9")}
    for n_valid, (rows, load_want, total) in pinned.items():
        valid = None if n_valid is None else jnp.arange(11) < n_valid
        y, experts, load = jax.jit(
            lambda x: glm.moe_ffn(x, layer, stacks, 1, cfg, valid))(x)
        assert np.asarray(y)[[0, 4, 10], :3].tolist() == rows
        assert np.asarray(experts)[:3].tolist() == [[3, 1], [1, 7], [3, 7]]
        assert load.tolist() == load_want
        assert float(np.abs(np.asarray(y)).sum()).hex() == total
