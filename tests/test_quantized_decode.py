"""Weight-only int8 decode (models/quant.py + LLMEngine(quantize)):

- quantize-params mechanics: shapes, dtypes, per-channel scale axes;
- int8-vs-f32 decode logits within a written tolerance, and greedy tokens
  identical wherever float32 decides by more than the int8 error;
- the engine knob end-to-end, including speculative decoding on a
  quantized engine: PR 3's greedy-equivalence invariant (spec on == spec
  off, token for token) must survive quantization — both engines run the
  same quantized weights, so the invariant is exact, not approximate.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.quant import (QuantTensor, dequantize,  # noqa: E402
                                  quantize_params)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.tiny_config(max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


# ------------------------------------------------------------ mechanics


def test_quantize_params_shapes_and_dtypes(tiny_model):
    cfg, params = tiny_model
    qp = quantize_params(params)
    blocks = qp["blocks"]
    l, d, h, hd = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim
    assert blocks["wq"].q.dtype == jnp.int8
    assert blocks["wq"].q.shape == (l, d, h, hd)
    # Per-OUTPUT-channel scales: the contracted (input) dims are gone.
    assert blocks["wq"].scale.shape == (l, h, hd)
    assert blocks["wo"].scale.shape == (l, d)
    assert blocks["w_gate"].scale.shape == (l, cfg.d_ff)
    assert blocks["w_down"].scale.shape == (l, d)
    assert qp["lm_head"].scale.shape == (cfg.vocab_size,)
    assert blocks["wq"].scale.dtype == jnp.float32
    # Norm scales and the embedding table stay untouched.
    assert not isinstance(blocks["ln_attn"], QuantTensor)
    assert not isinstance(qp["embed"], QuantTensor)
    assert qp["embed"].dtype == params["embed"].dtype
    # int8 range actually used, never exceeded.
    assert int(jnp.max(jnp.abs(blocks["wq"].q))) == 127


def test_quantize_roundtrip_error_bounded(tiny_model):
    """Dequantized weights are within half a quantization step of the
    originals, per channel."""
    _, params = tiny_model
    qp = quantize_params(params)
    w = np.asarray(params["blocks"]["w_gate"], np.float32)
    back = np.asarray(dequantize(qp["blocks"]["w_gate"], (1,)))
    step = np.asarray(qp["blocks"]["w_gate"].scale)[:, None, :]
    assert np.all(np.abs(w - back) <= 0.5 * step + 1e-7)


def test_quantize_rejects_unknown_dtype(tiny_model):
    _, params = tiny_model
    with pytest.raises(ValueError):
        quantize_params(params, dtype="fp4")


# ------------------------------------------------- forward equivalence


# What weight-only int8 may cost a logit against float32 on the tiny
# random model: |lq - lf| <= INT8_ATOL + INT8_RTOL * |lf|. A greedy token
# can be held to the float32 one only where float32 itself decides by
# more than that: the int8 error moves the two best logits towards each
# other by at most twice the bound, and inside it a swap is a near-tie,
# not a fault (the benchmark's `TOL_TOKEN_MARGIN` is the same rule).
INT8_RTOL, INT8_ATOL = 0.1, 0.15


def _int8_bound(lf) -> float:
    return INT8_ATOL + INT8_RTOL * float(np.max(np.abs(lf)))


def test_int8_forward_logits_close_and_greedy_identical(tiny_model):
    """Short-horizon greedy rollout, teacher-forced on the float32
    stream: int8 logits track f32 within the written tolerance at every
    step, and the argmax is identical wherever the float32 margin
    between the two best logits exceeds the int8 error."""
    cfg, params = tiny_model
    qp = quantize_params(params)
    ids = [1, 2, 3, 4, 5]
    decided = 0
    for _ in range(8):
        lf = np.asarray(llama.forward(params, jnp.asarray([ids]), cfg)[0, -1])
        lq = np.asarray(llama.forward(qp, jnp.asarray([ids]), cfg)[0, -1])
        np.testing.assert_allclose(lq, lf, rtol=INT8_RTOL, atol=INT8_ATOL)
        tf, tq = int(np.argmax(lf)), int(np.argmax(lq))
        best2 = np.sort(lf)[-2:]
        if best2[1] - best2[0] > 2 * float(np.max(np.abs(lq - lf))):
            decided += 1
            assert tf == tq, ids
        else:  # a near-tie: int8's choice is one of float32's contenders
            assert lf[tf] - lf[tq] <= 2 * _int8_bound(lf), ids
        ids.append(tf)
    assert decided >= 2, "the rollout held hardly a token to float32"


def test_int8_cache_decode_matches_full_forward(tiny_model):
    """The quantized pytree flows through forward_with_cache (prefill +
    per-token decode) and agrees with its own full forward — the cache
    path adds no quantization-specific error."""
    cfg, params = tiny_model
    qp = quantize_params(params)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                                cfg.vocab_size)
    full = llama.forward(qp, tokens, cfg)
    cache = llama.init_kv_cache(cfg, 2, 16)
    logits_p, cache = llama.forward_with_cache(qp, tokens[:, :8], cache,
                                               0, cfg)
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(full[:, :8]), rtol=2e-3,
                               atol=2e-3)
    for i in range(8, 12):
        logits_d, cache = llama.forward_with_cache(
            qp, tokens[:, i:i + 1], cache, i, cfg)
        np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                                   np.asarray(full[:, i]), rtol=2e-3,
                                   atol=2e-3)


def test_int8_with_fused_ops_interpret(tiny_model):
    """Quantized weights + fused kernels compose: the two knobs touch
    different einsum operands."""
    cfg, params = tiny_model
    qp = quantize_params(params)
    cfg_f = dataclasses.replace(cfg, fused_ops="interpret")
    tokens = jnp.asarray([[5, 9, 3, 7]], jnp.int32)
    a = llama.forward(qp, tokens, cfg)
    b = llama.forward(qp, tokens, cfg_f)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------- the engine


def make_engine(tiny_model, **kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", [8, 16])
    return LLMEngine(cfg, params, **kw)


def test_engine_quantize_knob(tiny_model):
    eng = make_engine(tiny_model, quantize="int8", decode_chunk=4)
    try:
        assert isinstance(eng.params["blocks"]["wq"], QuantTensor)
        stats = eng.stats()
        assert stats["quantize"] == "int8"
        # Matmul weights dominate the tiny tree minus embed/lm-norm f32
        # leaves: the quantized tree must actually be smaller.
        assert stats["weight_bytes"] < stats["weight_bytes_f32"]
        out = eng.generate([1, 2, 3, 4, 5], max_new_tokens=6)
        assert len(out["token_ids"]) == 6
        assert all(0 <= t < eng.cfg.vocab_size for t in out["token_ids"])
    finally:
        eng.close()


def test_engine_int8_greedy_matches_f32_short_horizon(tiny_model):
    """The int8 engine's greedy tokens are the f32 engine's over short
    horizons, up to the first float32 near-tie: where the two streams
    part, the int8 token's float32 logit lies within the int8 error of
    the best one (after that the two engines read different contexts and
    are no longer comparable)."""
    cfg, params = tiny_model
    f32 = make_engine(tiny_model, decode_chunk=4)
    q8 = make_engine(tiny_model, quantize="int8", decode_chunk=4)
    try:
        agreed = 0
        for prompt in ([1, 2, 3, 4, 5], [9, 8, 7], [5] * 8):
            a = f32.generate(prompt, max_new_tokens=8)["token_ids"]
            b = q8.generate(prompt, max_new_tokens=8)["token_ids"]
            assert len(a) == len(b) == 8
            k = next((i for i in range(8) if a[i] != b[i]), 8)
            agreed += k
            if k == 8:
                continue
            lf = np.asarray(llama.forward(
                params, jnp.asarray([prompt + a[:k]]), cfg)[0, -1])
            assert int(np.argmax(lf)) == a[k], prompt
            assert lf[a[k]] - lf[b[k]] <= 2 * _int8_bound(lf), (prompt, k)
        assert agreed >= 12, "int8 parted from f32 almost at once"
    finally:
        f32.close()
        q8.close()


def test_engine_int8_spec_greedy_equivalence(tiny_model):
    """PR 3's invariant under quantization: speculative greedy decode on
    an int8 engine is token-identical to plain greedy decode on an int8
    engine, and the verify path actually ran (drafts accepted)."""
    plain = make_engine(tiny_model, quantize="int8", decode_chunk=4)
    spec = make_engine(tiny_model, quantize="int8", decode_chunk=4,
                       spec_draft_len=4, spec_chunk=2, spec_ngram_max=4)
    try:
        for prompt in ([1, 2, 3, 4, 5], [5] * 8, [16] * 10):
            for n in (1, 6, 20):
                a = plain.generate(prompt, max_new_tokens=n)
                b = spec.generate(prompt, max_new_tokens=n)
                assert a["token_ids"] == b["token_ids"], (prompt, n)
        assert spec.metrics.spec_chunks > 0
        assert spec.metrics.spec_accepted > 0
    finally:
        plain.close()
        spec.close()
