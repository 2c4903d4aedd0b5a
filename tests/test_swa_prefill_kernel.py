"""``rtpu_swa_prefill_attention`` (``ops/swa_prefill.py``) under the
Pallas interpreter against its jnp twin at whole-tile shapes: the
published window (513 rows: a reach of 512) and the sliding layers' head
widths (192 + 64, 128) at 2 heads and a small latent, through
``dots3_note._sliding_prefill_block`` so that the ring, `_is_a_row` and
``last`` are the model's own. What the chip's compiler makes of the
kernel at 64 heads is `tests/test_chip_compile.py`'s; what it computes
there, the cell's check."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import dots3_note
from ray_tpu.ops import swa_prefill

WINDOW, REACH = 513, 512
CFG = dots3_note.Dots3NoteConfig(
    vocab_size=64, d_model=64,
    layer_types=(dots3_note.FULL, dots3_note.SLIDING), n_dense_layers=1,
    full=dots3_note.LatentGeometry(2, 16, 16, 8, 8, 8, 8e7),
    sliding=dots3_note.LatentGeometry(2, 32, 32, 192, 64, 128, 5e4),
    index_heads=2, index_head_dim=16, index_topk=8, window=WINDOW, d_ff=64,
    moe_d_ff=32, n_experts=4, held_experts=(0, 4), n_experts_per_tok=2,
    dtype=jnp.float32)

CASES = {
    # queries, cache_index, last real row (None: all), slots
    "a-prompts-first-chunk": (512, 0, None, 1),
    "history-shorter-than-the-reach": (512, 200, None, 1),
    "mid-prompt-ring-not-yet-full": (1024, 512, None, 1),
    "ring-wrapped": (2048, 4096, None, 1),
    "ring-wrapped-off-a-blocks-edge": (1024, 1736, None, 1),
    "bucket-padded-past-last": (1024, 2048, 700, 1),
    "padded-first-chunk": (512, 0, 37, 1),
    "two-slots": (512, 1024, None, 2),
}


def _layer_and_inputs(cfg, queries, slots, seed=0):
    layer = dots3_note._layer_of(
        dots3_note.init_params(cfg, jax.random.PRNGKey(seed))["sliding"], 0)
    kx, kw = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(kx, (slots, queries, cfg.d_model), cfg.dtype)
    # Whatever the ring holds: which rows are the slot's follows from
    # the position alone.
    win = jax.random.normal(
        kw, (slots, cfg.ring_rows, cfg.sliding.row_dim), cfg.dtype)
    return layer, x, win


def _block(cfg, layer, x, win, cache_index, last):
    slots, queries = x.shape[:2]
    positions = cache_index + jnp.broadcast_to(
        jnp.arange(queries, dtype=jnp.int32), (slots, queries))
    return dots3_note._sliding_prefill_block(
        x, layer, win, jnp.int32(cache_index), positions, last, cfg)


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_its_twin(case):
    """Float32 on both sides, the same sums in another order; what each
    query read is counted by the kernel from its own mask, equals the
    twin's exactly, and is the published window."""
    queries, cache_index, last, slots = CASES[case]
    layer, x, win = _layer_and_inputs(CFG, queries, slots)
    assert swa_prefill.takes(queries, REACH, 256, 128)
    want = _block(CFG, layer, x, win, cache_index, last)
    got = _block(dataclasses.replace(CFG, interpret_kernels=True), layer, x,
                 win, cache_index, last)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])              # the ring
    pos = cache_index + np.arange(queries)
    for mine, twins in zip(jax.tree.leaves(got[2]), jax.tree.leaves(want[2])):
        assert mine.shape == (slots, queries) and mine.dtype == jnp.int32
        np.testing.assert_array_equal(mine, twins)
    assert (np.asarray(got[2]["rows"]) == np.minimum(pos + 1, WINDOW)).all()
    assert (np.asarray(got[2]["first"]) == np.maximum(pos - REACH, 0)).all()
    assert float(jnp.abs(got[0] - x).max()) > 1e-3     # attention happened


def _operands(queries, cache_index, dtype, heads=2, seed=3, reach=REACH):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, queries, heads, 256), dtype)
    k = jax.random.normal(ks[1], (1, heads, reach + queries, 256), dtype)
    v = jax.random.normal(ks[2], (1, heads, reach + queries, 128), dtype)
    q_pos = cache_index + jnp.arange(queries, dtype=jnp.int32)[None]
    k_pos = cache_index - reach + jnp.arange(reach + queries,
                                             dtype=jnp.int32)[None]
    return q, k, v, q_pos, jnp.where(k_pos >= 0, k_pos, swa_prefill.NO_ROW)


@pytest.mark.parametrize("q_sub", [128, 256, 512])
def test_the_sub_blocks_of_a_step_read_what_the_whole_span_would(
        monkeypatch, q_sub):
    """Queries ``q_sub`` at a time multiply ``reach + q_sub`` rows: what
    they leave out is masked for each of them."""
    monkeypatch.setattr(swa_prefill, "_q_sub", lambda reach: q_sub)
    jax.clear_caches()
    args = _operands(1024, 1300, jnp.float32)
    got = swa_prefill.swa_prefill_attention(*args, reach=REACH, scale=0.06,
                                            interpret=True)
    jax.clear_caches()
    want = swa_prefill.swa_prefill_attention(*args, reach=REACH, scale=0.06)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


@pytest.mark.parametrize("reach, queries, cache_index", [
    (128, 384, 50), (384, 768, 1000), (1024, 1024, 0)])
def test_the_block_is_the_reach_whatever_the_window(reach, queries,
                                                    cache_index):
    """A window of 129, 385 or 1,025 rows in whole tiles: blocks of
    ``reach`` queries, the twin's blocks of 512 (or the chunk) beside
    them."""
    args = _operands(queries, cache_index, jnp.float32, reach=reach)
    got = swa_prefill.swa_prefill_attention(*args, reach=reach, scale=0.06,
                                            interpret=True)
    want = swa_prefill.swa_prefill_attention(*args, reach=reach, scale=0.06)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    pos = cache_index + np.arange(queries)
    assert (np.asarray(got[1][0]) == np.minimum(pos, reach) + 1).all()
    assert (np.asarray(got[2][0]) == np.maximum(pos - reach, 0)).all()
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_the_kernel_rounds_where_its_twin_rounds():
    """bf16 operands: ``p`` rounded to bf16 for the second product on
    both sides, everything between in float32: the two agree to a
    rounding of ``p`` (a sum in another order can move one), inside
    what rounding ``p`` at all costs against float32 throughout."""
    args = _operands(512, 300, jnp.bfloat16)
    got = swa_prefill.swa_prefill_attention(*args, reach=REACH, scale=0.0625,
                                            interpret=True)
    want = swa_prefill.swa_prefill_attention(*args, reach=REACH, scale=0.0625)
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=3e-4)
    exact = swa_prefill.swa_prefill_attention(
        *(a.astype(jnp.float32) for a in args[:3]), *args[3:], reach=REACH,
        scale=0.0625)
    assert float(jnp.abs(got[0] - exact[0]).max()) > 5e-4


def test_a_row_handed_over_as_none_is_not_read():
    """`NO_ROW` lies past every query: a row its caller disowns adds
    nothing and is not counted, wherever in the span it lies and
    whatever (finite) it holds."""
    q, k, v, q_pos, k_pos = _operands(512, 2048, jnp.float32)
    gone = k_pos.at[0, 700].set(swa_prefill.NO_ROW)     # position 2236
    out, rows, first = swa_prefill.swa_prefill_attention(
        q, k.at[0, :, 700].set(1e3), v.at[0, :, 700].set(1e6), q_pos, gone,
        reach=REACH, scale=0.0625, interpret=True)
    assert float(jnp.abs(out).max()) < 10
    sees = (np.asarray(q_pos[0]) >= 2236) & (np.asarray(q_pos[0]) <= 2236 + REACH)
    assert sees.any() and (np.asarray(rows[0]) == WINDOW - sees).all()


@pytest.mark.parametrize("why, queries, reach, qk, v_dim", [
    ("the-rehearsals-window-of-5", 8, 4, 12, 8),
    ("a-window-one-row-wider", 1024, 513, 256, 128),
    ("a-chunk-the-reach-does-not-divide", 768, 512, 256, 128),
    ("a-head-narrower-than-the-lanes", 512, 512, 192, 128),
])
def test_the_twin_is_chosen_where_the_shapes_are_not_whole_tiles(
        why, queries, reach, qk, v_dim):
    assert not swa_prefill.takes(queries, reach, qk, v_dim)
    shapes = (jax.ShapeDtypeStruct((1, queries, 2, qk), jnp.float32),
              jax.ShapeDtypeStruct((1, 2, reach + queries, qk), jnp.float32),
              jax.ShapeDtypeStruct((1, 2, reach + queries, v_dim), jnp.float32),
              jax.ShapeDtypeStruct((1, queries), jnp.int32),
              jax.ShapeDtypeStruct((1, reach + queries), jnp.int32))
    text = str(jax.make_jaxpr(lambda *a: swa_prefill.swa_prefill_attention(
        *a, reach=reach, scale=0.1, interpret=True))(*shapes))
    assert "pallas_call" not in text


def test_the_kernel_is_chosen_at_the_cells_buckets():
    for queries in (512, 1024, 2048):
        assert swa_prefill.takes(queries, REACH, 256, 128)
    shapes = (jax.ShapeDtypeStruct((1, 512, 2, 256), jnp.float32),
              jax.ShapeDtypeStruct((1, 2, 1024, 256), jnp.float32),
              jax.ShapeDtypeStruct((1, 2, 1024, 128), jnp.float32),
              jax.ShapeDtypeStruct((1, 512), jnp.int32),
              jax.ShapeDtypeStruct((1, 1024), jnp.int32))
    text = str(jax.make_jaxpr(lambda *a: swa_prefill.swa_prefill_attention(
        *a, reach=REACH, scale=0.1, interpret=True))(*shapes))
    assert "pallas_call" in text and swa_prefill.NAME in text
    # Off the TPU and not interpreted: the twin.
    text = str(jax.make_jaxpr(lambda *a: swa_prefill.swa_prefill_attention(
        *a, reach=REACH, scale=0.1))(*shapes))
    assert "pallas_call" not in text
