"""Pallas decode-attention kernel vs the pure-jnp reference (interpret
mode on CPU — the reference's kernels are tested the same way off-TPU)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.decode_attention import (decode_attention,
                                          decode_attention_reference)


def _inputs(b=2, h=8, kh=4, s=640, d=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d), dtype)
    lengths = jnp.asarray(
        jax.random.randint(ks[3], (b,), 1, s + 1), jnp.int32)
    return q, k, v, lengths


def test_reference_matches_dense_softmax():
    """The reference itself against an independent dense computation."""
    q, k, v, lengths = _inputs(b=1, h=4, kh=4, s=16, d=8)
    out = decode_attention_reference(q, k, v, lengths)
    kk = np.asarray(k)[0]  # [S,KH,D]
    probs_out = np.empty((4, 8))
    L = int(lengths[0])
    for hh in range(4):
        logits = (np.asarray(q)[0, hh] @ kk[:L, hh].T) / np.sqrt(8)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        probs_out[hh] = p @ np.asarray(v)[0, :L, hh]
    np.testing.assert_allclose(np.asarray(out)[0], probs_out, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("shape", [
    dict(b=2, h=8, kh=4, s=640, d=64),    # GQA, ragged block tail
    dict(b=1, h=4, kh=4, s=512, d=128),   # MHA, exact blocks
    dict(b=3, h=16, kh=2, s=1024, d=64),  # deep GQA groups
])
def test_pallas_kernel_matches_reference(shape):
    q, k, v, lengths = _inputs(**shape)
    expect = decode_attention_reference(q, k, v, lengths)
    got = decode_attention(q, k, v, lengths, block_s=256, interpret=True)
    # kernel and reference are BOTH ~1e-3 from float64 truth (different
    # f32 summation orders); 2e-3 is the seed-robust bound, not a
    # correctness concession — the masking test below is exact-structure.
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


def test_pallas_kernel_short_lengths_mask():
    """Cache positions past each sequence's length must not contribute —
    poison the tail with huge values and check invariance."""
    q, k, v, lengths = _inputs(b=2, h=4, kh=4, s=512, d=64)
    lengths = jnp.asarray([3, 200], jnp.int32)
    k_poison = k.at[0, 3:].set(100.0).at[1, 200:].set(100.0)
    v_poison = v.at[0, 3:].set(-77.0).at[1, 200:].set(-77.0)
    expect = decode_attention_reference(q, k, v, lengths)
    got = decode_attention(q, k_poison, v_poison, lengths, block_s=128,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


def test_zero_length_slot_attends_nothing():
    """A length-0 slot (empty/freed serving slot in a mixed batch) must
    output ~0, never the mean of padding/stale cache."""
    q, k, v, lengths = _inputs(b=2, h=4, kh=4, s=256, d=64)
    lengths = jnp.asarray([0, 256], jnp.int32)
    got = decode_attention(q, k, v, lengths, block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0], 0.0, atol=1e-6)
    # The live slot is unaffected.
    expect = decode_attention_reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got)[1], np.asarray(expect)[1],
                               rtol=2e-3, atol=2e-3)


def test_bfloat16_inputs():
    q, k, v, lengths = _inputs(b=1, h=4, kh=2, s=256, d=64,
                               dtype=jnp.bfloat16)
    expect = decode_attention_reference(q, k, v, lengths)
    got = decode_attention(q, k, v, lengths, block_s=128, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------- traffic follows the lengths

BLOCK, ROWS = 16, 64
# Every length a block boundary can go wrong at, mixed in one batch:
# an idle slot first, last and between two live ones.
MIXES = [[0, 1, BLOCK - 1, BLOCK, BLOCK + 1, ROWS],
         [ROWS, BLOCK + 1, 0, 0, BLOCK, 1],
         [0, 0, 0, 0, 0, 0]]


def _cache(form, kh, d=32, seed=0):
    """-> (k, v as the kernel takes them, the call's keywords, the
    [B,S,KH,D] arrays the reference reads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    rows = ROWS - 4 if form == "padded" else ROWS
    k, v = (jax.random.normal(key, (3, 6, kh, rows, d)) for key in ks)
    if form == "layered":
        return k, v, dict(layer=jnp.int32(1)), k[1], v[1]
    return k[1], v[1], {}, k[1], v[1]


def _want(q, k_ref, v_ref, lengths):
    """The reference's rows, and 0 for a slot of length 0."""
    want = decode_attention_reference(q, k_ref.transpose(0, 2, 1, 3),
                                      v_ref.transpose(0, 2, 1, 3), lengths)
    return np.asarray(jnp.where((lengths > 0)[:, None, None], want, 0.0))


@pytest.mark.parametrize("form", ["layered", "one", "padded"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("lengths", MIXES)
def test_kernel_over_mixed_lengths(lengths, group, form):
    """The whole cache and a layer index, a cache of one layer, and rows
    the block does not divide (sliced and padded first); one query head
    a KV head and four. A slot of length 0 reads 0."""
    kh = 2
    k, v, kw, k_ref, v_ref = _cache(form, kh)
    lengths = jnp.minimum(jnp.asarray(lengths, jnp.int32), k.shape[-2])
    q = jax.random.normal(jax.random.PRNGKey(9), (6, kh * group, 32))
    got = decode_attention(q, k, v, lengths, layout="bksd", block_s=BLOCK,
                           interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got),
                               _want(q, k_ref, v_ref, lengths),
                               rtol=2e-5, atol=2e-5)


def test_kernel_serves_its_slots_in_groups(monkeypatch):
    """More slots than one grid step's q may hold: each step lists and
    reads its own group's blocks."""
    module = sys.modules["ray_tpu.ops.decode_attention"]
    monkeypatch.setattr(module, "_Q_GROUP_BYTES", 2 * 2 * 16 * 32 * 4)
    jax.clear_caches()
    k, v, kw, k_ref, v_ref = _cache("layered", 2)
    lengths = jnp.asarray(MIXES[1], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(9), (6, 2, 32))
    got = decode_attention(q, k, v, lengths, layout="bksd", block_s=BLOCK,
                           interpret=True, **kw)
    jax.clear_caches()
    np.testing.assert_allclose(np.asarray(got),
                               _want(q, k_ref, v_ref, lengths),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", MIXES[:2])
def test_the_counted_blocks_are_the_blocks_the_loop_visits(lengths):
    """``decode_attn_rows_streamed`` comes from ``blocks_streamed``, the
    loop's own trip count. No fewer blocks are visited (the result is
    the reference's) and no more: a NaN in V anywhere outside the
    counted blocks would reach the output through ``0 * NaN`` (it does
    in the kernel that read whole slots and masked)."""
    from ray_tpu.ops.decode_attention import (blocks_streamed,
                                              decode_block_rows,
                                              decode_step_rows)

    k, v, kw, k_ref, v_ref = _cache("layered", 2)
    lengths = np.asarray(lengths, np.int32)
    blocks = np.asarray(blocks_streamed(jnp.asarray(lengths), BLOCK))
    np.testing.assert_array_equal(blocks, -(-lengths // BLOCK))
    unvisited = (np.arange(ROWS)[None, :] >= (blocks * BLOCK)[:, None])
    v = jnp.where(unvisited[None, :, None, :, None], jnp.nan, v)
    v = v.at[0].set(jnp.nan).at[2].set(jnp.nan)      # the other layers
    q = jax.random.normal(jax.random.PRNGKey(9), (6, 8, 32))
    got = decode_attention(q, k, v, jnp.asarray(lengths), layout="bksd",
                           block_s=BLOCK, interpret=True, **kw)
    np.testing.assert_allclose(
        np.asarray(got), _want(q, k_ref, v_ref, jnp.asarray(lengths)),
        rtol=2e-5, atol=2e-5)
    # The step's counters, at the block the shapes give.
    live = lengths > 0
    seen, counters = decode_step_rows(jnp.asarray(lengths - 1),
                                      jnp.asarray(live), k)
    block = decode_block_rows(ROWS, 2, 32, 4)
    np.testing.assert_array_equal(np.asarray(seen), lengths)
    assert int(counters["decode_attn_rows"]) == lengths.sum()
    assert int(counters["decode_attn_rows_streamed"]) == (
        -(-lengths // block) * block).sum()
