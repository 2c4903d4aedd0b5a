"""The latent decode-attention kernel, interpreted, against its jnp
reference: what it reads follows the slots' lengths block by block
(``tests/test_decode_attention.py`` holds the GQA kernel the same way),
under a mask of kept rows or none."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.decode_attention import blocks_streamed
from ray_tpu.ops.mla_decode import (mla_block_rows, mla_decode_attention,
                                    mla_decode_attention_reference,
                                    mla_step_rows)

BLOCK, ROWS, WIDTH, VALUES, HEADS = 16, 64, 48, 32, 4
SCALE = WIDTH ** -0.5
# Every length a block boundary can go wrong at, mixed in one call: an
# idle slot first, last and between two live ones.
MIXES = [[0, 1, BLOCK - 1, BLOCK, BLOCK + 1, ROWS],
         [ROWS, BLOCK + 1, 0, 0, BLOCK, 1],
         [0, 0, 0, 0, 0, 0]]


def _cache(layered: bool, seed=0, rows=ROWS):
    """-> (the [L,B,S,W] cache the kernel takes, its layer, the [B,S,W]
    rows the reference reads)."""
    cache = jax.random.normal(jax.random.PRNGKey(seed),
                              (3 if layered else 1, 6, rows, WIDTH))
    layer = 1 if layered else 0
    return cache, jnp.int32(layer), cache[layer]


def _queries(seed=9, slots=6):
    return jax.random.normal(jax.random.PRNGKey(seed), (slots, HEADS, WIDTH))


def _keep(lengths, seed=5, rows=ROWS):
    """About half of the rows kept; the WHOLE second block of every slot
    dropped, so a slot longer than a block meets a block under its
    length none of whose rows it attends to."""
    keep = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5,
                                (len(lengths), rows))
    return keep.at[:, BLOCK:2 * BLOCK].set(False)


def _want(q, kv, lengths, keep=None):
    """The reference's rows, and 0 for a slot that attends to no row."""
    want = mla_decode_attention_reference(q, kv, lengths, v_dim=VALUES,
                                          scale=SCALE, keep=keep)
    some = jnp.arange(kv.shape[1])[None, :] < lengths[:, None]
    if keep is not None:
        some = some & (keep > 0)
    return np.asarray(jnp.where(jnp.any(some, -1)[:, None, None], want, 0.0))


def _got(q, cache, layer, lengths, keep=None, block_s=BLOCK):
    return np.asarray(mla_decode_attention(
        q, cache, lengths, layer=layer, v_dim=VALUES, scale=SCALE,
        block_s=block_s, keep=keep, interpret=True))


@pytest.mark.parametrize("layered", [True, False], ids=["layered", "one"])
@pytest.mark.parametrize("kept", [False, True], ids=["all", "keep"])
@pytest.mark.parametrize("lengths", MIXES)
def test_kernel_over_mixed_lengths(lengths, kept, layered):
    """The whole cache and a layer index, and a cache of one layer; all
    rows under the length, and the kept ones. A slot of length 0 reads
    exactly 0."""
    cache, layer, kv = _cache(layered)
    lengths = jnp.asarray(lengths, jnp.int32)
    keep = _keep(lengths) if kept else None
    q = _queries()
    got = _got(q, cache, layer, lengths, keep)
    np.testing.assert_allclose(got, _want(q, kv, lengths, keep),
                               rtol=2e-5, atol=2e-5)
    assert not got[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("kept", [False, True], ids=["all", "keep"])
def test_kernel_serves_its_slots_in_groups(monkeypatch, kept):
    """More slots than one grid step's queries may hold: each step lists
    and reads its own group's blocks (and its own group's masks)."""
    module = sys.modules["ray_tpu.ops.decode_attention"]
    monkeypatch.setattr(module, "_Q_GROUP_BYTES", 2 * 16 * WIDTH * 4)
    assert module.slot_group(6, 16 * WIDTH * 4) == 2
    jax.clear_caches()
    cache, layer, kv = _cache(True)
    lengths = jnp.asarray(MIXES[1], jnp.int32)
    keep = _keep(lengths) if kept else None
    q = _queries()
    got = _got(q, cache, layer, lengths, keep)
    jax.clear_caches()
    np.testing.assert_allclose(got, _want(q, kv, lengths, keep),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kept", [False, True], ids=["all", "keep"])
@pytest.mark.parametrize("lengths", MIXES[:2])
def test_the_counted_blocks_are_the_blocks_the_loop_visits(lengths, kept):
    """``blocks_streamed`` is the loop's own trip count. No fewer blocks
    are visited (the result is the reference's) and no more: a NaN
    anywhere outside the counted blocks, and in the other layers, would
    reach the output through ``0 * NaN`` (it does in a kernel that reads
    whole slots and masks)."""
    cache, layer, kv = _cache(True)
    lengths = np.asarray(lengths, np.int32)
    blocks = np.asarray(blocks_streamed(jnp.asarray(lengths), BLOCK))
    np.testing.assert_array_equal(blocks, -(-lengths // BLOCK))
    unvisited = np.arange(ROWS)[None, :] >= (blocks * BLOCK)[:, None]
    cache = jnp.where(unvisited[None, :, :, None], jnp.nan, cache)
    cache = cache.at[0].set(jnp.nan).at[2].set(jnp.nan)
    lengths = jnp.asarray(lengths)
    keep = _keep(lengths) if kept else None
    q = _queries()
    np.testing.assert_allclose(_got(q, cache, layer, lengths, keep),
                               _want(q, kv, lengths, keep),
                               rtol=2e-5, atol=2e-5)


def test_a_block_under_the_length_with_no_kept_row_adds_nothing():
    """The slot's FIRST blocks hold no kept row (the running maximum is
    still the floor when the first kept row comes), and a slot under
    whose length nothing is kept reads 0."""
    cache, layer, kv = _cache(True)
    lengths = jnp.asarray([ROWS, ROWS, 3 * BLOCK, 1, ROWS, 0], jnp.int32)
    keep = jnp.zeros((6, ROWS), bool)
    keep = keep.at[0, 2 * BLOCK + 3].set(True)       # one row, third block
    keep = keep.at[1, 3 * BLOCK:].set(True)          # the last block whole
    keep = keep.at[4, ::BLOCK].set(True)             # one row a block
    q = _queries()
    got = _got(q, cache, layer, lengths, keep)
    np.testing.assert_allclose(got, _want(q, kv, lengths, keep),
                               rtol=2e-5, atol=2e-5)
    assert not got[[2, 3, 5]].any()
    # One kept row: the output is that row's values, whatever the query.
    np.testing.assert_allclose(
        got[0], np.broadcast_to(np.asarray(kv)[0, 2 * BLOCK + 3, :VALUES],
                                got[0].shape), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", [[40, 0, 40, 40, 0, 40],
                                     [40, 40, 40, 40, 40, 40]])
def test_a_ring_is_read_as_one_block(lengths):
    """A window family's call: ``block_s`` = the ring, every live slot
    "full" (its length the ring), the window as the mask."""
    ring = 40
    cache, layer, kv = _cache(True, rows=ring)
    lengths = jnp.asarray(lengths, jnp.int32)
    keep = _keep(lengths, rows=ring).at[:, BLOCK:2 * BLOCK].set(True)
    q = _queries()
    got = _got(q, cache, layer, lengths, keep, block_s=ring)
    np.testing.assert_allclose(got, _want(q, kv, lengths, keep),
                               rtol=2e-5, atol=2e-5)


def test_rows_the_block_does_not_divide_take_the_reference():
    cache, layer, kv = _cache(True, rows=ROWS - 4)
    lengths = jnp.asarray([ROWS - 4, 1, 0, 17, 16, 33], jnp.int32)
    q = _queries()
    np.testing.assert_allclose(
        _got(q, cache, layer, lengths),
        np.asarray(mla_decode_attention_reference(
            q, kv, lengths, v_dim=VALUES, scale=SCALE)),
        rtol=2e-5, atol=2e-5)


def test_bfloat16_operands():
    cache, layer, kv = _cache(True)
    cache, kv, q = (x.astype(jnp.bfloat16) for x in (cache, kv, _queries()))
    lengths = jnp.asarray(MIXES[0], jnp.int32)
    got = mla_decode_attention(q, cache, lengths, layer=layer, v_dim=VALUES,
                               scale=SCALE, block_s=BLOCK, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _want(q, kv, lengths).astype(np.float32),
                               rtol=2e-2, atol=2e-2)


# (slots, rows a slot, heads, row width) -> the block: the four cells
# that call it.
CELLS = {"glm47flash.code.flood": ((32, 4096, 20, 640), 256),
         "kimilinear.reason.flood": ((64, 2048, 32, 640), 256),
         "xing4.rag.flood": ((32, 2048, 32, 640), 256),
         "dots3.longdoc.flood": ((16, 32768, 128, 640), 512)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_block_comes_from_the_shapes(cell):
    """At the cells' geometries: a power of two of at least 128 rows
    that divides the slot, whatever the slots; twice the rows from 128
    heads on; a slot shorter than a block is one block."""
    (slots, rows, heads, width), want = CELLS[cell]
    block = mla_block_rows(rows, width, 2, heads)
    assert block == want
    assert rows % block == 0 and block & (block - 1) == 0
    assert mla_block_rows(96, width, 2, heads) == 96
    # The window layers' rows of 1,152 columns, were they not a ring.
    assert mla_block_rows(rows, 1152, 2, 64) == 128


@pytest.mark.parametrize("lengths,exact", [
    ([0, 127, 128, 129, 300, 511], False),
    ([255, 511, 767, 1023, 255, 511], True),
    ([0, 0, 0, 0, 0, 0], False)])
def test_the_streamed_rows_cover_the_rows_asked_for(lengths, exact):
    """``mla_decode_rows_streamed`` >= ``mla_decode_rows`` (Σ lengths +
    1 over ALL slots), equal where every slot's rows fill its blocks;
    the block is the one the kernel derives for the cache."""
    cache = jax.ShapeDtypeStruct((2, 6, 1024, 640), jnp.bfloat16)
    block = mla_block_rows(1024, 640, 2, HEADS)
    lengths = np.asarray(lengths, np.int32)
    counters = jax.jit(lambda l: mla_step_rows(l, cache, HEADS))(lengths)
    assert set(counters) == {"mla_decode_rows", "mla_decode_rows_streamed"}
    rows, streamed = (int(counters[k]) for k in (
        "mla_decode_rows", "mla_decode_rows_streamed"))
    assert rows == (lengths + 1).sum()
    assert streamed == (-(-(lengths + 1) // block) * block).sum()
    assert streamed >= rows and (streamed == rows) == exact
