"""The Xing4.0 family (``models/xing_mhc.py``: a four-stream mHC
residual around latent attention under YaRN and a held share of
sigmoid-routed experts) against its plain float32 reference
(``benchmark/reference/mhc_mla_moe_decoder.py``), on the CPU at a small
size, seeded weights; the mixes' kernels interpreted against their
``jnp`` twins against the reference; the share of the experts tied to
the uncut layer; YaRN against values computed by hand; the reference's
attention and router against ``transformers``' DeepSeek-V3; the family
through ``DecodeLoop`` and through the engine.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import xing_mhc as builder
from ray_tpu.models import xing_mhc as xing
from ray_tpu.ops import mhc
from ray_tpu.ops.rotary import YarnScaling

YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=16, type="yarn")
# Hidden size 128: whole lanes, so ``interpret_kernels`` runs the two
# Pallas mixes (``ops/mhc.py`` takes its twins at other widths).
CONFIG = dict(
    attention_bias=False, first_k_dense_replace=2, hidden_act="silu",
    hidden_size=128, intermediate_size=192, kv_lora_rank=32,
    max_position_embeddings=128, moe_intermediate_size=64, moe_layer_freq=1,
    n_group=1, n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=2, num_hidden_layers=5,
    num_key_value_heads=4, num_nextn_predict_layers=0, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, q_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=YARN, routed_scaling_factor=2, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=16, vocab_size=256, torch_dtype="float32")
# One chip's share: 2 of the router's 8 experts.
SHARE = dict(CONFIG, n_routed_experts=2,
             expert_parallel={"chips": 4, "this_chip": 1},
             reduced={"n_routed_experts": {"source": 8, "run": 2}})
ENGINE = dict(max_batch=2, max_len=128, prompt_buckets=[32, 64],
              decode_chunk=4, kv_fleet_min_prefix_blocks=-1)


@pytest.fixture(scope="module")
def tiny():
    cfg = builder.config(SHARE)
    return cfg, builder.init_params(cfg, 3)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------------------------- the mixes

def _mix_inputs(rows, c, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    spec = mhc.MhcSpec()
    streams = 0.7 * jax.random.normal(k[0], (rows, 4 * c), jnp.float32)
    phi_t = jax.random.normal(k[1], (24, 4 * c), jnp.float32) * (4 * c) ** -.5
    alpha = jax.random.uniform(k[2], (3,), jnp.float32, 0.5, 1.5)
    bias = jnp.concatenate([jnp.zeros(8), 1.5 * jnp.eye(4).ravel()]) + (
        jax.random.normal(k[3], (24,), jnp.float32))
    y = jax.random.normal(k[4], (rows, c), jnp.float32)
    return spec, streams, phi_t, alpha, bias, y


def _mixes_agree(spec, streams, phi_t, alpha, bias, y):
    """The interpreted kernels, the ``jnp`` twins and the plain
    reference's `_maps` give the same maps, the same collapsed input and
    the same write-back. Float32 sums in three orders: 1e-5 of a map of
    order one."""
    from benchmark.reference import mhc_mla_moe_decoder as ref

    rows, c = y.shape
    x, maps = mhc.mhc_pre(streams, phi_t, alpha, bias, spec=spec)
    x_k, maps_k = mhc.mhc_pre(streams, phi_t, alpha, bias, spec=spec,
                              interpret=True)
    assert maps.shape == (rows, 128) and x.shape == (rows, c)
    np.testing.assert_allclose(maps_k, maps, atol=1e-5)
    np.testing.assert_allclose(x_k, x, atol=1e-5)
    apart = streams.reshape(rows, 4, c)
    h_pre, h_post, h_res = ref._maps(
        apart, phi_t, alpha, bias, n=4, iters=spec.sinkhorn_iters,
        hc_eps=1e-6, lo=-30.0, hi=30.0, eps=1e-6)
    got = mhc.split_maps(maps_k, 4)
    for a, b in zip(got, (h_pre, h_post, h_res)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(x_k, ref._collapse(apart, h_pre), atol=1e-5)
    out = mhc.mhc_post(streams, y, maps, spec=spec)
    out_k = mhc.mhc_post(streams, y, maps, spec=spec, interpret=True)
    np.testing.assert_allclose(out_k, out, atol=1e-5)
    np.testing.assert_allclose(
        out_k.reshape(rows, 4, c),
        ref._write_back(apart, y, h_post, h_res), atol=1e-5)
    assert not np.any(np.asarray(maps_k[:, 24:]))
    return maps_k


@pytest.mark.parametrize("rows, c", [(1, 128), (32, 256), (256, 512),
                                     (128, 256), (384, 512), (200, 256)])
def test_mhc_kernels_equal_their_twins_and_the_reference(rows, c):
    """One row, a decode step's 32 and a bucket's 256; ONE full tile of
    128 rows, three of them, and a count of rows that ends inside a
    tile."""
    _mixes_agree(*_mix_inputs(rows, c))


def test_mhc_kernels_equal_their_twins_after_one_sinkhorn_pass():
    """``sinkhorn_iters`` 1 (what the control `sinkhorn_one_pass` hands
    in): the kernel stops where the twins stop, rows still off by a
    tenth and more."""
    import dataclasses

    spec, *rest = _mix_inputs(40, 128, seed=2)
    maps = _mixes_agree(dataclasses.replace(spec, sinkhorn_iters=1), *rest)
    assert float(mhc.sinkhorn_error(maps, 4)) > 0.1


def test_mhc_kernels_equal_their_twins_with_logits_at_both_clamps():
    """``a_res`` large (12 where the drawn ones lie in [0.5, 1.5]; the
    products' last bits grow with it): H_res starts from entries down
    to ``exp(-30)`` and up to ``exp(30)``, 26 decimal orders apart, and
    the kernel's reciprocals and the twins' divisions end on the same
    maps."""
    spec, streams, phi_t, alpha, bias, y = _mix_inputs(136, 128, seed=3)
    alpha = alpha.at[2].set(12.0)
    raw = mhc.map_logits(streams, phi_t, alpha, bias, spec)[:, 8:]
    assert float(raw.min()) < -30 and float(raw.max()) > 30
    maps = _mixes_agree(spec, streams, phi_t, alpha, bias, y)
    assert np.all(np.isfinite(maps))


def test_the_packed_product_is_the_float32_product():
    """`rtpu_mhc_pre` multiplies by ``Phi`` in three bf16 passes that
    carry the float32 product's six terms: at K = 2,048 it equals
    ``einsum`` at ``HIGHEST`` on the float32 operands to 2e-6 of a logit
    of order one (and lies nearer the float64 product than that sum
    does), where the three largest terms alone (``bf16_3x``) are ten
    times further off: an edit that drops a term fails here."""
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    u = jax.random.normal(k[0], (128, 2048), jnp.float32)
    phi_t = jax.random.normal(k[1], (24, 2048), jnp.float32) / (3 * 2048 ** .5)
    product = lambda a, b: jnp.einsum("rk,mk->rm", a, b,
                                      precision=jax.lax.Precision.HIGHEST)
    want = product(u, phi_t)
    exact = np.asarray(u, np.float64) @ np.asarray(phi_t, np.float64).T
    sums, packed = [jnp.zeros((128, 128), jnp.float32)] * 3, (
        mhc._packed_terms(phi_t))
    for k0 in range(0, 2048, 512):
        sums = mhc._against_packed(sums, u[:, k0:k0 + 512],
                                   packed[:, k0:k0 + 512])
    got = mhc._six_terms(sums, jnp.roll, 24)[:, :24]
    assert 1.0 < float(jnp.abs(want).max()) < 2.0
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert np.abs(got - exact).max() <= np.abs(want - exact).max()
    (u_hi, u_mid, _), (p_hi, p_mid, _) = (
        [t.astype(jnp.float32) for t in mhc._bf16_terms(a)]
        for a in (u, phi_t))
    three = product(u_hi, p_mid) + product(u_mid, p_hi) + product(u_hi, p_hi)
    assert float(jnp.abs(three - want).max()) > 2 * 2e-6


def test_h_res_is_doubly_stochastic():
    """Twenty passes end on a column pass: every column of every H_res
    sums to one within float32 rounding (1e-5); the rows within what
    twenty passes leave of the drawn logits (spread over +-3: 5e-2 on
    the slowest row of 512; the median row under 1e-5), and ONE pass
    leaves ten times that and more. `sinkhorn_error` (the engine's
    counter) reads the larger of the two."""
    spec, streams, phi_t, alpha, bias, _ = _mix_inputs(512, 128, seed=4)
    _, maps = mhc.mhc_pre(streams, phi_t, alpha, bias, spec=spec,
                          interpret=True)
    h_res = np.asarray(mhc.split_maps(maps, 4)[2])
    assert np.all(h_res > 0)
    assert np.abs(h_res.sum(axis=1) - 1).max() < 1e-5
    rows = np.abs(h_res.sum(axis=2) - 1)
    assert rows.max() < 5e-2 and np.median(rows) < 1e-5
    assert float(mhc.sinkhorn_error(maps, 4)) == pytest.approx(rows.max())
    import dataclasses
    once = mhc.maps_reference(streams, phi_t, alpha, bias,
                              dataclasses.replace(spec, sinkhorn_iters=1))
    assert float(mhc.sinkhorn_error(once, 4)) > 10 * rows.max() > 0


@pytest.mark.parametrize("interpret", [None, True])
def test_the_write_back_keeps_the_streams_when_nothing_is_added(interpret):
    """``X' = X`` when ``y = 0`` and ``H_res`` is the identity, bit for
    bit; and with ``H_res`` the identity a sub-layer's output lands in
    stream i scaled by ``H_post[i]``."""
    spec, streams, _, _, _, y = _mix_inputs(32, 128, seed=1)
    maps = jnp.zeros((32, 128)).at[:, 8:24].set(jnp.eye(4).ravel())
    maps = maps.at[:, 4:8].set(jnp.arange(1.0, 5.0))
    same = mhc.mhc_post(streams, jnp.zeros_like(y), maps, spec=spec,
                        interpret=interpret)
    np.testing.assert_array_equal(same, streams)
    added = mhc.mhc_post(streams, y, maps, spec=spec, interpret=interpret)
    np.testing.assert_allclose(
        (added - streams).reshape(32, 4, 128),
        jnp.arange(1.0, 5.0)[None, :, None] * y[:, None, :], atol=1e-5)


# -------------------------------------------------------------------- YaRN

def test_yarn_frequencies_and_scale_against_values_computed_by_hand():
    """The published keys (factor 64 over 4,096 positions, beta 32 and
    1, rope 64, theta 10,000). By hand: frequency i is 10000^(-i/32)
    and turns 4096 f / 2 pi times in the original length; 32 turns fall
    at index 32 ln(4096 / 64 pi) / ln 10000 = 10.47 and one turn at
    22.51, so indices 0 to 10 are kept, 23 to 31 divided by 64 and
    index i between blends with weight (i - 10) / 13 on the divided
    one. mscale = 0.1 ln 64 + 1 = 1.41589; the softmax scale 192^-1/2
    x 1.41589^2; cos and sin x 1."""
    yarn = YarnScaling.of(dict(YARN, original_max_position_embeddings=4096))
    freqs = np.asarray(yarn.frequencies(64, 10000.0), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert 32 * math.log(4096 / (64 * math.pi)) / math.log(1e4) == (
        pytest.approx(10.47, abs=5e-3))
    assert 32 * math.log(4096 / (2 * math.pi)) / math.log(1e4) == (
        pytest.approx(22.51, abs=5e-3))
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)
    for i in range(11, 23):
        w = (i - 10) / 13
        assert freqs[i] == pytest.approx(
            plain[i] * (1 - w) + plain[i] / 64 * w, rel=1e-6)
    assert freqs[11] == pytest.approx(0.0389765, rel=1e-5)
    assert freqs[22] == pytest.approx(1.62439e-4, rel=1e-5)
    assert yarn.rotation_mscale == 1.0
    assert yarn.softmax_mscale == pytest.approx(1.41589 ** 2, rel=1e-5)
    cfg = xing.XingMhcConfig()
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 2.004740, rel=1e-5)
    # The reference computes them on its own.
    from benchmark.reference import mhc_mla_moe_decoder as ref
    published = dict(qk_rope_head_dim=64, qk_nope_head_dim=128,
                     rope_theta=10000, rope_scaling=dict(
                         YARN, original_max_position_embeddings=4096))
    np.testing.assert_allclose(ref.yarn_inv_freq(published), freqs,
                               rtol=1e-6)
    assert ref.yarn_scales(published) == (
        1.0, pytest.approx(cfg.attn_scale, rel=1e-6))
    # Without scaling the frequencies are the plain ones.
    none = dict(published, rope_scaling=None)
    np.testing.assert_allclose(ref.yarn_inv_freq(none), plain, rtol=1e-12)


# ------------------------------------------------ the model, the reference

@pytest.mark.parametrize("interpret", [False, True])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        interpret):
    """Logits, not tokens: the functional prefill of 16 tokens, then 7
    steps through the latent cache, against the reference's full
    forward pass, with the reference following the system's experts
    (none differs at float32). Both are float32 here, so what is left
    is the order of the sums (1e-4 relative L2 a row; the kernels
    interpreted and the twins alike); the first sub-layer's maps within
    1e-5."""
    cfg = builder.config(SHARE, interpret_kernels=interpret)
    params = builder.init_params(cfg, 3)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 24)).astype(
        np.int32)
    cache = xing.init_kv_cache(cfg, 2, 64)
    assert cache["kv"].shape == (5, 2, 64, 128)
    logits, cache, counters, seen = jax.jit(
        lambda p, t, c: xing.forward_with_cache(p, t, c, 0, cfg))(
            params, tokens[:, :16], cache)
    assert int(counters["mhc_prefill_rows"]) == 2 * 5 * 32
    assert int(counters["moe_pairs_routed"]) == 3 * 2 * 32
    assert 0 < int(counters["moe_pairs_held"]) < 3 * 2 * 32
    assert 0 < float(counters["mhc_sinkhorn_err_max"]) < 0.1
    chosen = np.full((3, 2, 24, 2), -1, np.int32)
    chosen[:, :, :16] = np.asarray(seen["experts"])
    rows = [(i, j) for i in range(2) for j in range(16)]
    want, report = builder.reference.routed_logits_at(params, tokens, rows,
                                                      SHARE, chosen)
    assert not report["differs"].any()
    want = np.asarray(want).reshape(2, 16, -1)
    for i in range(2):
        for j in range(16):
            assert _rel(logits[i, j], want[i, j]) < 1e-4
    first = builder.reference.first_maps(params, tokens[0, :16], SHARE)
    np.testing.assert_allclose(seen["mhc_maps"][0], first, atol=1e-5)
    end = seen["mhc_end"]
    assert end["streams"].shape == (2, 4, 128)
    np.testing.assert_allclose(end["read"], end["streams"].sum(axis=1),
                               rtol=1e-6, atol=1e-6)
    mixes = seen["mhc_mixes"]
    assert mixes["first"].shape == (2, 512)
    assert {k: mixes[k].shape for k in ("y", "maps", "after")} == {
        "y": (5, 2, 2, 128), "maps": (5, 2, 2, 24), "after": (5, 2, 2, 512)}
    np.testing.assert_array_equal(mixes["maps"][0, 0],
                                  seen["mhc_maps"][:, 15])
    np.testing.assert_array_equal(
        mixes["after"][-1, 1].reshape(2, 4, 128), end["streams"])

    step = jax.jit(lambda p, t, c, n: xing.decode_step_with_cache(
        p, t, c, n, cfg))
    for j in range(16, 23):
        got, cache, counters, seen = step(params, tokens[:, j:j + 1], cache,
                                          jnp.full((2,), j, jnp.int32))
        chosen[:, :, j] = np.asarray(seen["experts"])[:, :, 0]
        want, report = builder.reference.routed_logits_at(
            params, tokens, [(0, j), (1, j)], SHARE, chosen)
        assert not report["differs"].any()
        assert _rel(got, want) < 1e-4
        assert int(counters["mhc_step_rows"]) == 2 * 5 * 2
        assert int(counters["mla_decode_rows"]) == 2 * (j + 1)
    want = builder.reference.first_maps(params, tokens[1, :23], SHARE)
    np.testing.assert_allclose(seen["mhc_maps"][1, 0], want[22], atol=1e-5)
    end = seen["mhc_end"]
    np.testing.assert_allclose(end["read"], end["streams"].sum(axis=1),
                               rtol=1e-6, atol=1e-6)


def test_the_tick_prefill_masks_padding_and_reuses_a_prefix(tiny):
    """`forward_last_with_cache` on a bucket of 32 with 20 real tokens
    gives the whole prefill's row 19 and routes 20 tokens; continued at
    ``cache_index`` 16 (a prefix hit: the expanded path through the
    cache) it gives the same row."""
    cfg, params = tiny
    tokens = np.random.default_rng(5).integers(1, 256, (1, 32)).astype(
        np.int32)
    fresh = xing.init_kv_cache(cfg, 1, 64)
    whole = xing.forward_with_cache(params, tokens[:, :20], fresh, 0, cfg)[0]
    padded = tokens.copy()
    padded[:, 20:] = 0
    row, cache, counters, _ = xing.forward_last_with_cache(
        params, padded, fresh, 0, 19, cfg)
    assert int(counters["moe_prefill_tokens"]) == 20
    assert int(counters["mhc_prefill_rows"]) == 2 * 5 * 20
    assert _rel(row[0], whole[0, 19]) < 1e-4
    _, cache, _, _ = xing.forward_with_cache(params, tokens[:, :16], fresh, 0,
                                             cfg)
    rest = np.zeros((1, 32), np.int32)
    rest[:, :4] = tokens[:, 16:20]
    row, _, _, _ = xing.forward_last_with_cache(params, rest, cache, 16, 3,
                                                cfg)
    assert _rel(row[0], whole[0, 19]) < 1e-4


def test_four_shares_add_up_to_the_uncut_expert_layer():
    """The routed parts that 4 chips of 2 experts give, with the shared
    expert (which every chip computes alike) counted once, are the
    uncut layer's result: in the program and in the reference."""
    from ray_tpu.models import kimi_linear
    from ray_tpu.ops.grouped_experts import expert_stacks

    whole_cfg = builder.config(CONFIG)
    assert whole_cfg.held_experts == (0, 8) and whole_cfg.n_experts == 8
    params = builder.init_params(whole_cfg, 5)
    layer = jax.tree.map(lambda a: a[1], params["moe"])
    n = jax.random.normal(jax.random.PRNGKey(9), (40, 128))

    def ffn(cfg, w):
        stacks = expert_stacks(jax.tree.map(lambda a: a[None], w))
        return kimi_linear.moe_ffn(n, w, stacks, jnp.int32(0), cfg)

    whole, experts, load = ffn(whole_cfg, layer)
    assert int(load.sum()) == 40 * 2
    shared = kimi_linear._swiglu(n, layer["ws_gate"], layer["ws_up"],
                                 layer["ws_down"])
    none = jnp.full((40, 2), -1, jnp.int32)
    ref_whole = builder.reference.expert_layer(n, layer, none, CONFIG)[0]
    assert _rel(whole, ref_whole) < 1e-5
    parts, ref_parts, held = 0.0, 0.0, 0
    for chip in range(4):
        file = dict(SHARE, expert_parallel={"chips": 4, "this_chip": chip})
        cfg = builder.config(file)
        assert cfg.held_experts == (2 * chip, 2) and cfg.n_experts == 8
        mine = dict(layer, **{k: layer[k][2 * chip:2 * chip + 2]
                              for k in ("w_gate", "w_up", "w_down")})
        y, chosen, load = ffn(cfg, mine)
        np.testing.assert_array_equal(chosen, experts)   # routed alike
        held += int(load.sum())
        parts = parts + (y - shared)
        ref_parts = ref_parts + (builder.reference.expert_layer(
            n, mine, none, file)[0] - shared)
    assert held == 40 * 2
    assert _rel(parts + shared, whole) < 1e-5
    assert _rel(ref_parts + shared, ref_whole) < 1e-5


def _published(torch, hf):
    return hf.DeepseekV3Config(
        hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=64,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.0, hidden_act="silu", rms_norm_eps=1e-6,
        rope_theta=10000.0, max_position_embeddings=128,
        rope_scaling=dict(YARN, rope_type="yarn"), rope_interleave=False,
        attention_bias=False, attn_implementation="eager")


def test_the_references_attention_equals_the_published_deepseek_v3_mla():
    """Latent attention with query compression, the one rotary key,
    YaRN's frequencies and ``mscale^2`` on the softmax scale:
    `transformers`' ``DeepseekV3Attention`` under its own rotary
    embedding (rotate-half: ``rope_interleave`` off), the weights
    copied, against the reference's `_attention` (the mHC residual has
    no published code on this machine to be held to)."""
    hf = pytest.importorskip("transformers.models.deepseek_v3")
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as mod
    from benchmark.reference import mhc_mla_moe_decoder as ref

    cfg = builder.config(CONFIG)
    w = jax.tree.map(lambda a: a[0], builder.init_params(cfg, 7)["dense"])
    config = _published(torch, hf)
    attention = mod.DeepseekV3Attention(config, layer_idx=0)
    rotary = mod.DeepseekV3RotaryEmbedding(config)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    seq = 40
    with torch.no_grad():
        attention.q_a_proj.weight.copy_(t(w["w_dq"]).T)
        attention.q_b_proj.weight.copy_(t(w["w_uq"]).reshape(24, -1).T)
        attention.kv_a_proj_with_mqa.weight.copy_(t(w["w_dkv"]).T)
        attention.kv_b_proj.weight.copy_(t(jnp.concatenate(
            [w["w_uk"], w["w_uv"]], axis=-1)).reshape(32, -1).T)
        attention.o_proj.weight.copy_(t(w["w_o"]).reshape(-1, 128).T)
        x = jax.random.normal(jax.random.PRNGKey(1), (seq, 128))
        h = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        mask = torch.full((seq, seq), float("-inf")).triu(1)[None, None]
        want = attention(t(h)[None], rotary(t(h)[None],
                                            torch.arange(seq)[None]),
                         mask)[0][0].numpy()
    np.testing.assert_allclose(rotary.inv_freq.numpy(),
                               ref.yarn_inv_freq(CONFIG), rtol=1e-6)
    rotation, scale = ref.yarn_scales(CONFIG)
    assert attention.scaling == pytest.approx(scale, rel=1e-6)
    got = ref._attention(
        x, w, jnp.asarray(ref.yarn_inv_freq(CONFIG), jnp.float32), eps=1e-6,
        nope=16, rkv=32, rotation=rotation, scale=scale)
    assert _rel(got, want) < 1e-4


def test_the_expert_layer_equals_the_published_deepseek_v3_moe():
    """Sigmoid scores, selection on score + bias in one group,
    renormalised, scaled, one shared expert: `transformers`'
    ``DeepseekV3MoE`` with the weights copied, against the program's
    layer and the reference's."""
    hf = pytest.importorskip("transformers.models.deepseek_v3")
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as mod
    from ray_tpu.models import kimi_linear
    from ray_tpu.ops.grouped_experts import expert_stacks

    cfg = builder.config(CONFIG)
    layer = jax.tree.map(lambda a: a[1],
                         builder.init_params(cfg, 11)["moe"])
    published = mod.DeepseekV3MoE(_published(torch, hf))
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
        n = jax.random.normal(jax.random.PRNGKey(2), (50, 128))
        want = published(t(n)[None])[0].numpy()
    stacks = expert_stacks(jax.tree.map(lambda a: a[None], layer))
    got = kimi_linear.moe_ffn(n, layer, stacks, jnp.int32(0), cfg)[0]
    assert _rel(got, want) < 1e-4
    ref = builder.reference.expert_layer(
        n, layer, jnp.full((50, 2), -1, jnp.int32), CONFIG)[0]
    assert _rel(ref, want) < 1e-4


# -------------------------------------------------------------- the engine

def test_decode_loop_programs_agree_with_the_functional_step(tiny):
    """The family through `DecodeLoop` at the rehearsal's shape: the
    tick's donated prefill hands back the argmax of `prefill_last`'s
    row, and a chunk's tokens are the whole step's greedy ones, its
    counters the steps' sums, but for ``mhc_sinkhorn_err_max``
    (`COUNTER_MAXES`), of which the chunk keeps its steps' largest; the
    two donating check programs return what their functional twins
    return, in the caller's own cache."""
    from ray_tpu.serve.engine.decode_loop import DecodeLoop

    cfg, params = tiny
    loop = DecodeLoop(cfg, max_len=128, chunk=4)
    prompt = np.random.default_rng(2).integers(1, 256, (1, 32)).astype(
        np.int32)
    put = jax.device_put
    args = (put(prompt), put(np.int32(0)), put(np.int32(0)),
            put(np.int32(19)))
    cache = xing.init_kv_cache(cfg, 2, 128)
    row, kept, _, seen = loop.prefill_last(params, cache, *args)
    assert seen["mhc_maps"].shape == (1, 32, 24)
    token, cache, counters = loop.prefill_inplace(params, cache, *args)
    assert int(token[0]) == int(jnp.argmax(row[0]))
    assert int(counters["mhc_prefill_rows"]) == 2 * 5 * 20
    tokens = np.array([[int(token[0])], [0]], np.int32)
    lengths = np.array([20, 127], np.int32)
    whole, _, about, _ = loop.decode_step_whole(params, kept, put(tokens),
                                                put(lengths))
    out = loop.decode_chunk(
        params, cache, put(tokens), put(lengths),
        put(np.array([8, 0], np.int32)), put(np.array([-1, -1], np.int32)),
        put(np.array([False, True])))
    toks, counters = np.asarray(out[0]), out[-1]
    assert int(toks[0, 0]) == int(jnp.argmax(whole[0]))
    assert int(counters["mhc_step_rows"]) == 4 * 2 * 5 * 1   # one live slot
    # The first of the chunk's four steps is ``whole``'s: the chunk's
    # largest is at least that step's, and no sum of four of its size.
    one = float(about["mhc_sinkhorn_err_max"])
    assert one <= float(counters["mhc_sinkhorn_err_max"]) < 0.1
    assert xing.COUNTER_MAXES == ("mhc_sinkhorn_err_max",)

    fresh = xing.init_kv_cache(cfg, 2, 128)
    row_d, kept_d, _, seen_d = loop.prefill_last_inplace(params, fresh, *args)
    assert fresh["kv"].is_deleted()
    np.testing.assert_array_equal(row_d, row)
    jax.tree.map(np.testing.assert_array_equal, seen_d, seen)
    whole_d, stepped, about_d, _ = loop.decode_step_whole_inplace(
        params, kept_d, put(tokens), put(lengths))
    assert kept_d["kv"].is_deleted() and not stepped["kv"].is_deleted()
    np.testing.assert_array_equal(whole_d, whole)


def test_the_engine_serves_the_family_and_refuses_what_it_cannot(tiny):
    """Through `serve.run(build_llm_deployment(..))`: greedy tokens are
    the model's own, teacher-forced; a second request with the same
    prompt reuses its rows (the cache holds no state); the mHC and
    routed counters come home on the fetches the tick makes; and the
    four options the latent cache cannot serve are refused by name."""
    from ray_tpu import serve
    from ray_tpu.serve.engine import InferenceEngine
    from ray_tpu.serve.llm import build_llm_deployment

    cfg, params = tiny
    handle = serve.run(build_llm_deployment(engine_kwargs=dict(
        cfg=cfg, params=params, **ENGINE)), _local_testing_mode=True)
    engine = handle._instance.engine
    try:
        assert set(engine.cache) == {"kv"} and engine.params is params
        prompt = [int(t) for t in
                  np.random.default_rng(0).integers(1, 256, 40)]
        ask = lambda: handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 10}).result()["token_ids"]
        got = ask()
        logits = xing.forward(params, jnp.asarray([prompt + got]), cfg)[0]
        assert got == np.asarray(
            jnp.argmax(logits[len(prompt) - 1:-1], -1)).tolist()
        assert ask() == got
        stats = engine.stats()
        assert stats["mhc_prefill_rows"] > 0 and stats["mhc_step_rows"] > 0
        # The largest of any call, not their sum (`COUNTER_MAXES`).
        assert 0 < stats["mhc_sinkhorn_err_max"] < 0.1
        assert stats["moe_pairs_held"] < stats["moe_pairs_routed"]
        assert stats["mla_decode_rows"] > 0
    finally:
        engine.close()
    for option in ({"quantize": "int8"}, {"spec_draft_len": 2},
                   {"role": "prefill"}):
        with pytest.raises(ValueError, match="xing_mhc"):
            InferenceEngine(cfg=cfg, params=params, **{**ENGINE, **option})
