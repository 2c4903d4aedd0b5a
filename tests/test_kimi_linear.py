"""The Kimi-Linear family (``models/kimi_linear.py``, ``ops/kda.py``) at
tiny widths on the CPU, float32, seeded: KDA with a decay constant over
the channels against the repo's gated delta rule, the chunked scan
against the token-by-token recurrence (also at a decay that overflows
the factored form), the decode kernel against its twin, the cache path
against the plain reference
(``benchmark/reference/kda_mla_moe_decoder.py``), the held share tied
to the uncut expert layer, the expert layer against the published
``DeepseekV3MoE`` where `transformers` has it, and through `LLMEngine`
what a per-slot state asks of the engine. KDA and the rotation-free MLA
have no published code on this machine (`transformers` 4.57.6 has no
``kimi_linear``; ``fla`` is not installed): they are held to the
reference written from the report's equations, and to the sibling
delta rule where the two coincide."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import kimi_linear as builder
from ray_tpu.models import kimi_linear as kimi
from ray_tpu.ops import gated_delta, kda

CONFIG = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=7, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    mla_use_nope=True,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6], full_attn_layers=[4, 7],
                            num_heads=4, head_dim=16,
                            short_conv_kernel_size=4),
    num_experts=16, num_experts_per_token=2, num_shared_experts=1,
    moe_router_activation_func="sigmoid", moe_renormalize=True,
    routed_scaling_factor=2.446, num_expert_group=1, topk_group=1,
    use_grouped_topk=True, moe_layer_freq=1, num_nextn_predict_layers=0,
    hidden_act="silu", rope_scaling=None, tie_word_embeddings=False,
    model_max_length=128, rms_norm_eps=1e-5, torch_dtype="float32")
# The same model as one of four chips that share each layer.
SHARE = dict(CONFIG, num_experts=4, expert_parallel={"chips": 4,
                                                     "this_chip": 1},
             reduced={"num_experts": {"source": 16, "run": 4}})
ENGINE = dict(max_batch=2, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4, kv_fleet_min_prefix_blocks=-1)


@pytest.fixture(scope="module")
def tiny():
    """(cfg, the PUBLISHED tree: what the builder draws, the reference
    reads and an engine is handed)."""
    cfg = builder.config(SHARE)
    return cfg, builder.init_params(cfg, 3)


@pytest.fixture(scope="module")
def served(tiny):
    """(cfg, the tree the module's programs read: `serving_params` of
    the published one, as the engine makes it where it takes its
    weights)."""
    cfg, params = tiny
    return cfg, kimi.serving_params(params, cfg)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _scan_inputs(t, seed=0, b=2, h=3, dk=16, dv=24, decay=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gated_delta.l2_normalize(jax.random.normal(ks[0], (b, t, h, dk)))
    k = gated_delta.l2_normalize(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, h, dk), minval=0.05)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return (q * dk ** -0.5, k, v, g, beta), state


# ------------------------------------------------------------------ the ops

def test_a_decay_constant_over_the_channels_is_the_gated_delta_rule():
    (q, k, v, g, beta), state = _scan_inputs(40)
    one = g[..., :1]                                     # a scalar a head
    o, s = kda.recurrence(q, k, v, jnp.broadcast_to(one, g.shape), beta,
                          state)
    want_o, want_s = gated_delta.recurrence(q, k, v, one[..., 0], beta,
                                            jnp.swapaxes(state, -1, -2))
    assert _rel(o, want_o) < 1e-6
    assert _rel(s, jnp.swapaxes(want_s, -1, -2)) < 1e-6


@pytest.mark.parametrize("t, from_zero, decay", [
    (150, True, 2.0), (64, False, 2.0), (70, False, 0.1), (33, True, 6.0),
    (129, False, 12.0)])
def test_chunk_scan_equals_the_recurrence(t, from_zero, decay):
    """From zero and from a state, lengths across a chunk boundary, a
    decay a channel of its own from gentle to -12 a token."""
    xs, state = _scan_inputs(t, seed=t, decay=decay)
    if from_zero:
        state = jnp.zeros_like(state)
    want_o, want_s = kda.recurrence(*xs, state)
    o, s = kda.chunk_scan(*xs, state)
    assert np.isfinite(np.asarray(o)).all()
    assert _rel(o, want_o) < 2e-5 and _rel(s, want_s) < 2e-5


def test_chunk_scan_is_finite_where_the_factored_form_overflows():
    """Every channel forgets -12 a token: inside one chunk of 64 the
    factored ``k_j e^{-G_j}`` is e^{768}, past float32; on differences
    the scan stays finite and equal to the recurrence."""
    (q, k, v, g, beta), state = _scan_inputs(100, seed=7)
    g = jnp.full_like(g, -12.0)
    assert not np.isfinite(np.asarray(
        jnp.exp(-jnp.cumsum(g[:, :kda.CHUNK], axis=1)))).all()
    want_o, want_s = kda.recurrence(q, k, v, g, beta, state)
    o, s = kda.chunk_scan(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()
    assert _rel(o, want_o) < 2e-5 and _rel(s, want_s) < 2e-5


def test_a_padded_bucket_leaves_the_state_at_the_last_real_token():
    (q, k, v, g, beta), state = _scan_inputs(96, seed=3)
    real = jnp.arange(96) < 50
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, want = kda.recurrence(q[:, :50], k[:, :50], v[:, :50], g[:, :50],
                             beta[:, :50], state)
    _, s = kda.chunk_scan(q, k, v, g, beta, state)
    assert _rel(s, want) < 2e-5


@pytest.mark.parametrize("h, dk, dv", [(4, 16, 24), (2, 128, 128)])
def test_kda_decode_kernel_equals_its_twin_and_the_recurrence(h, dk, dv):
    ks = jax.random.split(jax.random.PRNGKey(dk), 7)
    state = jax.random.normal(ks[0], (3, 2, h, dk, dv))
    q = jax.random.normal(ks[1], (2, h, dk))
    k = gated_delta.l2_normalize(jax.random.normal(ks[2], (2, h, dk)))
    v = jax.random.normal(ks[3], (2, h, dv))
    g = -3.0 * jax.random.uniform(ks[4], (2, h, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (2, h)))
    o, s = kda.kda_decode(state, jnp.int32(1), q, k, v, g, beta)
    o_k, s_k = kda.kda_decode(state, jnp.int32(1), q, k, v, g, beta,
                              interpret=True)
    np.testing.assert_array_equal(o_k, o)
    np.testing.assert_array_equal(s_k, s)
    want_o, want_s = kda.recurrence(q[:, None], k[:, None], v[:, None],
                                    g[:, None], beta[:, None], state[1])
    assert _rel(o, want_o[:, 0]) < 1e-6 and _rel(s[1], want_s) < 1e-6
    # The other layers are where they were.
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[2], state[2])


def test_a_slot_that_is_not_live_keeps_its_state():
    state = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 2, 16, 16))
    ones = jnp.ones((2, 2, 16))
    g = jnp.stack([jnp.zeros((2, 16)), -ones[1]])        # slot 0: g = 0
    beta = jnp.asarray([[0.0, 0.0], [0.7, 0.7]])         # slot 0: beta = 0
    for interpret in (None, True):
        _, s = kda.kda_decode(state, jnp.int32(0), ones, ones, ones, g, beta,
                              interpret=interpret)
        np.testing.assert_array_equal(s[0, 0], state[0, 0])
        assert not np.array_equal(s[0, 1], state[0, 1])


# ------------------------------------------------------------ the model

def test_the_published_order_is_scanned_a_period_at_a_time():
    cfg = kimi.KimiLinearConfig()
    assert (cfg.n_layers, cfg.n_kda_layers, cfg.n_mla_layers) == (27, 20, 7)
    runs = cfg.segments
    assert runs[0] == ("kda", True, 1)                   # the dense layer
    assert [r[2] for r in runs] == [1, 2, 1] + [3, 1] * 5 + [2, 1]
    assert [r[0] for r in runs[1:]] == ["kda", "mla"] * 7
    assert sum(r[2] for r in runs if r[0] == "kda") == 20
    # Fifteen runs, seven bodies: K K K M is traced once for its five
    # repetitions.
    k3m = (("kda", False, 3), ("mla", False, 1))
    assert cfg.periods == (
        (1, (runs[0],)), (1, (runs[1],)), (1, (runs[2],)), (5, k3m),
        (1, (runs[13],)), (1, (runs[14],)))
    assert sum(t * n for t, p in cfg.periods for _, _, n in p) == 27
    # An order that repeats nothing is a scan a run, as before.
    plain = kimi.KimiLinearConfig(kinds=("kda", "kda", "mla"))
    assert plain.periods == tuple((1, (r,)) for r in plain.segments)
    assert cfg.cache_row_dim == 640 and cfg.attn_head_dim == 256
    # The file's lists give the same order.
    from benchmark.harness import manifest
    m = manifest.load()
    file = m.config(m.cell("kimilinear.reason.flood"))
    assert tuple(builder.reference.layer_kinds(file)) == cfg.kinds
    assert builder.reference.held_experts(file) == (0, 16, 256)


def test_serving_params_is_a_pure_relayout(tiny, served):
    """Each KDA layer's ``w_in``, cut where its six maps end and turned
    back, IS ``w_q``, ``w_k``, ``w_v`` (q ++ k ++ v, each head-major:
    `conv_w`'s channels), ``w_fa``, ``w_ga`` and ``w_b``, bit for bit;
    every other leaf is the same array, not a copy; `init_params`' keys
    and shapes are the published ones."""
    cfg, params = tiny
    _, laid = served
    nk, d, h, dk, r = (cfg.n_kda_layers, cfg.d_model, cfg.kda_heads,
                       cfg.kda_head_dim, cfg.kda_rank)
    assert set(params["kda"]) - set(laid["kda"]) == set(kimi.KDA_IN)
    assert set(laid["kda"]) - set(params["kda"]) == {"w_in"}
    w_in = laid["kda"]["w_in"]
    c = cfg.conv_channels
    assert kimi.KDA_IN[:3] == ("w_q", "w_k", "w_v") and c == 3 * h * dk
    assert w_in.shape == (nk, c + 2 * r + h, d)
    assert w_in.dtype == params["kda"]["w_q"].dtype
    ends = np.cumsum([h * dk] * 3 + [r, r])
    for name, part in zip(kimi.KDA_IN, jnp.split(w_in, ends, axis=1)):
        published = params["kda"][name]
        np.testing.assert_array_equal(
            part.transpose(0, 2, 1).reshape(published.shape), published,
            err_msg=name)
    same = jax.tree.map(
        lambda a, b: a is b,
        {**params, "kda": {k: v for k, v in params["kda"].items()
                           if k not in kimi.KDA_IN}},
        {**laid, "kda": {k: v for k, v in laid["kda"].items()
                         if k != "w_in"}})
    assert all(jax.tree.leaves(same))
    # The draw is the published form, whatever is served.
    drawn = jax.eval_shape(lambda: kimi.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    assert "w_in" not in drawn["kda"]
    assert all(drawn["kda"][w].shape == (nk, d, h, dk)
               for w in kimi.KDA_IN[:3])
    assert drawn["kda"]["w_b"].shape == (nk, d, h)


def test_forward_equals_the_plain_reference(tiny, served):
    _, params = tiny
    cfg, laid = served
    tokens = np.random.default_rng(0).integers(1, 256, (2, 90))
    rows = [(0, i) for i in range(0, 90, 7)] + [(1, 89), (1, 40)]
    want = builder.reference.logits_at(params, tokens, rows, SHARE)
    logits = kimi.forward(laid, jnp.asarray(tokens), cfg)
    got = jnp.stack([logits[s, p] for s, p in rows])
    assert _rel(got, want) < 2e-4


def test_prefill_then_decode_through_the_cache_equals_the_reference(tiny,
                                                                    served):
    """The tick's prefill of 50 and of 20 tokens in buckets of 64 and 32
    into slots that hold another request's leavings, then 30 steps
    through the cache (the kernels interpreted), a third slot parked on
    its last row: every row of logits is the reference's full forward
    pass's, and the first layer's state its recurrence's."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params = tiny
    _, laid = served
    cfg = dataclasses.replace(cfg, interpret_kernels=True)
    tokens = np.random.default_rng(1).integers(1, 256, (2, 80))
    starts = (50, 20)
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    cache = jax.tree.map(lambda a: a + 1, kimi.init_kv_cache(cfg, 3, 128))
    got = [[], []]
    for s, n in enumerate(starts):
        padded = np.zeros((1, 64 if n > 32 else 32), np.int32)
        padded[0, :n] = tokens[s, :n]
        logits, cache, counters, seen = loop.prefill_last(
            laid, cache, jnp.asarray(padded), jnp.int32(s), jnp.int32(0),
            jnp.int32(n - 1))
        got[s].append(logits[0])
        assert int(counters["kda_prefill_tokens"]) == n
        assert int(counters["state_resets"]) == 1
        assert int(counters["moe_pairs_routed"]) == 6 * 2 * n
        assert 0 < int(counters["moe_pairs_held"]) < 6 * 2 * n
        assert seen["experts"].shape == (6, 1, padded.shape[1], 2)
    for j in range(30):
        step_tokens = np.zeros((3, 1), np.int32)
        lengths = np.asarray([starts[0] + j, starts[1] + j, 127], np.int32)
        for s in range(2):
            step_tokens[s, 0] = tokens[s, starts[s] + j]
        logits, cache, counters, seen = loop.decode_step_whole(
            laid, cache, jnp.asarray(step_tokens), jnp.asarray(lengths))
        assert int(counters["kda_slot_steps"]) == 3 * 5
        assert int(counters["mla_decode_rows"]) == int(lengths.sum()) + 3
        assert int(counters["moe_layer_steps"]) == 6
        assert int(counters["moe_pairs_routed"]) == 6 * 3 * 2
        for s in range(2):
            got[s].append(logits[s])
    rows = [(s, n - 1 + i) for s, n in enumerate(starts) for i in range(31)]
    want = np.asarray(builder.reference.logits_at(
        params, tokens, rows, SHARE)).reshape(2, 31, -1)
    got = np.asarray(got)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-3, err.max()
    for s, n in enumerate(starts):
        state = builder.first_state(cfg, cache, s)
        want_s = builder.reference.first_state(params, tokens[s, :n + 30],
                                               SHARE)
        assert _rel(state, want_s) < 1e-4


def test_chunked_prefill_equals_whole_prefill(served):
    """64 tokens in one piece, and as 32 + 32 (the second continued from
    the slot's state, conv tail and latent rows at ``cache_index`` 32):
    the same logits and the same cache."""
    cfg, params = served
    tokens = jnp.asarray(np.random.default_rng(2).integers(1, 256, (1, 64)))
    fresh = kimi.init_kv_cache(cfg, 1, 128)
    whole, cache_whole, _, _ = kimi.forward_with_cache(params, tokens, fresh,
                                                       0, cfg)
    first, cache, _, _ = kimi.forward_with_cache(params, tokens[:, :32],
                                                 fresh, 0, cfg)
    second, cache, counters, _ = kimi.forward_with_cache(
        params, tokens[:, 32:], cache, 32, cfg)
    assert int(counters["state_resets"]) == 0
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=5e-3)
    for name in cache:
        np.testing.assert_allclose(cache[name], cache_whole[name], atol=5e-3,
                                   err_msg=name)


def test_four_shares_add_up_to_the_uncut_expert_layer():
    """The routed parts that 4 chips of 4 experts give, with the shared
    expert (which every chip computes alike) counted once, are the
    uncut layer's result: in the program and in the reference."""
    whole_cfg = builder.config(CONFIG)
    params = builder.init_params(whole_cfg, 5)
    layer = jax.tree.map(lambda a: a[2], params["moe"])
    n = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    from ray_tpu.ops.grouped_experts import expert_stacks

    def ffn(cfg, w):
        stacks = expert_stacks(jax.tree.map(lambda a: a[None], w))
        return kimi.moe_ffn(n, w, stacks, jnp.int32(0), cfg)

    whole, experts, load = ffn(whole_cfg, layer)
    assert int(load.sum()) == 40 * 2
    shared = kimi._swiglu(n, layer["ws_gate"], layer["ws_up"],
                          layer["ws_down"])
    none = jnp.full((40, 2), -1, jnp.int32)
    ref_whole = builder.reference._expert_layer(n, layer, none, CONFIG)[0]
    assert _rel(whole, ref_whole) < 1e-5
    parts, ref_parts, held = 0.0, 0.0, 0
    for chip in range(4):
        file = dict(SHARE, expert_parallel={"chips": 4, "this_chip": chip})
        cfg = builder.config(file)
        assert cfg.held_experts == (4 * chip, 4) and cfg.n_experts == 16
        mine = dict(layer, **{k: layer[k][4 * chip:4 * chip + 4]
                              for k in ("w_gate", "w_up", "w_down")})
        y, chosen, load = ffn(cfg, mine)
        np.testing.assert_array_equal(chosen, experts)   # routed alike
        held += int(load.sum())
        parts = parts + (y - shared)
        ref_parts = ref_parts + (builder.reference._expert_layer(
            n, mine, none, file)[0] - shared)
    assert held == 40 * 2
    assert _rel(parts + shared, whole) < 1e-5
    assert _rel(ref_parts + shared, ref_whole) < 1e-5


def test_the_expert_layer_equals_the_published_deepseek_v3_moe():
    """Sigmoid scores, selection on score + bias in one group,
    renormalised, scaled, one shared expert: `transformers`'
    ``DeepseekV3MoE`` with the weights copied, against the program's
    layer and the reference's (KDA and the rotation-free MLA have no
    published code on this machine to be held to)."""
    hf = pytest.importorskip("transformers.models.deepseek_v3")
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as mod

    cfg = builder.config(CONFIG)
    params = builder.init_params(cfg, 11)
    layer = jax.tree.map(lambda a: a[1], params["moe"])
    published = mod.DeepseekV3MoE(hf.DeepseekV3Config(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=16,
        n_shared_experts=1, num_experts_per_tok=2, n_group=1, topk_group=1,
        norm_topk_prob=True, routed_scaling_factor=2.446, hidden_act="silu"))
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    with torch.no_grad():
        published.gate.weight.copy_(t(layer["router"]).T)
        published.gate.e_score_correction_bias.copy_(t(layer["router_bias"]))
        for e, expert in enumerate(published.experts):
            expert.gate_proj.weight.copy_(t(layer["w_gate"][e]).T)
            expert.up_proj.weight.copy_(t(layer["w_up"][e]).T)
            expert.down_proj.weight.copy_(t(layer["w_down"][e]).T)
        published.shared_experts.gate_proj.weight.copy_(t(layer["ws_gate"]).T)
        published.shared_experts.up_proj.weight.copy_(t(layer["ws_up"]).T)
        published.shared_experts.down_proj.weight.copy_(
            t(layer["ws_down"]).T)
        n = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
        want = published(t(n)[None])[0].numpy()
    from ray_tpu.ops.grouped_experts import expert_stacks

    stacks = expert_stacks(jax.tree.map(lambda a: a[None], layer))
    got = kimi.moe_ffn(n, layer, stacks, jnp.int32(0), cfg)[0]
    assert _rel(got, want) < 1e-4
    ref = builder.reference._expert_layer(
        n, layer, jnp.full((50, 2), -1, jnp.int32), CONFIG)[0]
    assert _rel(ref, want) < 1e-4


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


def _greedy(served, prompt, got):
    """Teacher-forced: each token the argmax after what precedes it."""
    cfg, params = served
    logits = kimi.forward(params, jnp.asarray([prompt + got]), cfg)[0]
    return np.asarray(jnp.argmax(logits[len(prompt) - 1:-1], -1)).tolist()


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 256, n)] for n in lengths]


def test_engine_resets_a_slots_state_and_reuses_no_prefix(tiny, served):
    """Through `serve.run(build_llm_deployment(..))`, one slot: request
    B after A gets the tokens a fresh engine gives it (state and conv
    tail were reset in the tick's prefill), and A again finds its rows
    resident, reuses none of them and gets the same tokens; the state
    and the routed counters come home on the fetches the tick makes."""
    a, b = _prompts(0, 40, 20)
    handle, engine = _serve(tiny, max_batch=1)
    try:
        assert set(engine.cache) == {"kv", "state", "conv"}
        # Handed the published tree, the engine serves the laid-out one.
        assert "w_in" in engine.params["kda"]
        assert not set(kimi.KDA_IN) & set(engine.params["kda"])
        assert engine.params["moe"]["w_gate"] is tiny[1]["moe"]["w_gate"]
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
    assert got_a == _greedy(served, a, got_a) and again == got_a
    assert got_b == _greedy(served, b, got_b)
    assert stats["prefix_reuse_vetoed"] == 1
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_reused"] == 0
    assert stats["state_resets"] == 3
    assert stats["kda_prefill_tokens"] == 40 + 40 + 20
    # 9 decoded tokens a request, a state a KDA layer each.
    assert stats["kda_slot_steps"] == 3 * 9 * 5
    # Three chunks of 4 steps a request; a frozen slot's token is
    # routed like any other (static shapes).
    assert stats["moe_layer_steps"] == 3 * 12 * 6
    assert stats["moe_pairs_routed"] == 6 * 2 * (100 + 3 * 12)
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]
    # Two MLA layers' latent rows of 16 + 4 values, padded to 128 lanes.
    assert stats["kv_bytes_per_token"] == 2 * 128 * 4
    assert stats["state_bytes_per_slot"] == 5 * (4 * 16 * 16 * 4
                                                 + 3 * 192 * 4)
    # The counters the request's span carries, under the family's names.
    assert engine._span_attrs([{"state_resets": np.int32(1),
                                "moe_pairs_held": np.int32(7)}]) == {
        "state_reset": 1, "expert_pairs_held": 7}


def test_slots_that_are_not_live_leave_the_others_alone(tiny, served):
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
    assert got["long"] == _greedy(served, long, got["long"])
    assert got["short"] == _greedy(served, short, got["short"])
    assert np.isfinite(state).all() and state.any(axis=(0, 2, 3, 4)).all()
