"""``rtpu_dsa_prefill_attention`` (``ops/dsa_prefill.py``) under the
Pallas interpreter against its jnp twin at a small geometry: 4 heads in
groups of 2, blocks of 8 queries, tiles of 16 rows of a 64-row slot, so
that a chunk crosses tiles, blocks of queries are skipped before the
chunk's own rows, and the last tile is cut by ``rows_seen``. What the
chip's compiler makes of the kernel at the published sizes is
`tests/test_chip_compile.py`'s; what it computes there, the cell's
check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dsa_prefill

HEADS, RANK, NOPE, ROPE, V, WIDTH, ROWS = 4, 16, 8, 4, 8, 128, 64
TILING = (2, 8, 16)         # heads a grid step, queries a block, rows a tile


def _operands(seed, batch, queries, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, queries, HEADS, NOPE + ROPE), dtype)
    rows = jax.random.normal(ks[1], (batch, ROWS, WIDTH), dtype)
    # Past the rotary key a cache row holds what its writer padded it
    # with: the twin never reads it, the kernel multiplies it by zeros.
    rows = rows.at[..., RANK + ROPE:].set(1.0)
    w_uk = jax.random.normal(ks[2], (RANK, HEADS, NOPE), dtype) * RANK ** -0.5
    w_uv = jax.random.normal(ks[3], (RANK, HEADS, V), dtype) * RANK ** -0.5
    return q, rows, w_uk, w_uv


def _keep(seed, batch, queries, first, kept):
    """Each query at ``first + t`` keeps ``kept`` of the rows up to its
    own, chosen at random a slot (all of them while there are fewer)."""
    pos = first + jnp.arange(queries)
    visible = jnp.arange(ROWS)[None, :] <= pos[:, None]
    draw = jnp.where(visible[None], jax.random.uniform(
        jax.random.PRNGKey(seed), (batch, queries, ROWS)), -1.0)
    kth = jnp.sort(draw, -1)[..., -kept][..., None]
    return (draw >= kth) & visible[None]


CASES = {
    # first row, queries, rows kept a query, batch, what the case holds
    "rows_seen-on-a-tiles-edge": (16, 16, 6, 1),
    "rows_seen-inside-a-tile": (21, 19, 6, 1),
    "one-tile-only": (0, 16, 4, 1),
    "chunk-at-row-0-over-two-tiles": (0, 24, 5, 1),
    "deep-in-a-prompt": (40, 24, 7, 1),
    "exactly-one-row-kept": (28, 20, 1, 1),
    "two-slots-two-masks": (24, 16, 6, 2),
    "queries-the-block-does-not-divide": (30, 13, 6, 1),
}


@pytest.mark.parametrize("padded", [False, True], ids=["all-real", "padded"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_its_twin(monkeypatch, case, padded):
    """Float32 on both sides, the same sums in another order. ``padded``
    makes the bucket's queries past ``last`` (the chunk's last third)
    keep NO row: they come out 0 and move no real query's result."""
    first, queries, kept, batch = CASES[case]
    monkeypatch.setattr(dsa_prefill, "_tiling", lambda t, s, h: TILING)
    q, rows, w_uk, w_uv = _operands(1, batch, queries, jnp.float32)
    keep = _keep(2, batch, queries, first, kept)
    last = queries - 1 - (queries // 3 if padded else 0)
    keep = keep & (jnp.arange(queries) <= last)[None, :, None]
    counts = np.asarray(keep.sum(-1))
    assert (counts[:, :last + 1] == np.minimum(
        first + np.arange(last + 1) + 1, kept)).all()
    if batch == 2:
        assert (np.asarray(keep[0]) != np.asarray(keep[1])).any()
    rows_seen = jnp.int32(first + queries)
    got = dsa_prefill.dsa_prefill_attention(
        q, rows, keep, w_uk, w_uv, rows_seen, scale=0.3, interpret=True)
    want = jax.vmap(
        lambda q, rows, keep: dsa_prefill.dsa_prefill_attention_reference(
            q, rows, keep, w_uk, w_uv, rows_seen, scale=0.3,
            kv_tile=TILING[2]))(q, rows, keep)
    assert got.shape == (batch, queries, HEADS, V) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[:, last + 1:]).any()
    assert np.asarray(jnp.abs(got[:, :last + 1]).sum((-1, -2)) > 0).all()


def test_the_kernel_rounds_where_its_twin_rounds():
    """bf16 operands: keys, values and ``p`` rounded to bf16 on both
    sides, everything between in float32: the two agree to a rounding
    of ``p`` (a sum in another order can move one), far inside what
    keeping the scores or ``p`` in bf16 throughout would cost."""
    first, queries = 40, 24
    q, rows, w_uk, w_uv = _operands(3, 1, queries, jnp.bfloat16)
    keep = _keep(4, 1, queries, first, 7)
    args = (q, rows, keep, w_uk, w_uv, jnp.int32(first + queries))
    got = dsa_prefill.dsa_prefill_attention(*args, scale=0.3, interpret=True)
    want = dsa_prefill.dsa_prefill_attention(*args, scale=0.3)   # the twin
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-3)
    coarse = jax.vmap(
        lambda q, rows, keep: dsa_prefill.dsa_prefill_attention_reference(
            q, rows, keep, w_uk, w_uv, first + queries, scale=0.3))(
        q.astype(jnp.float32), rows.astype(jnp.float32), keep)
    assert float(jnp.abs(got - coarse).max()) > 4e-3


def test_the_tiles_follow_the_shapes():
    """48 rows are one tile of 48; 520 rows, which no tile of 512
    divides, are refused by kernel and twin alike (`init_kv_cache`
    hands out whole tiles); the cell's shapes take the tiling the sweep
    on the chip chose."""
    q, rows, w_uk, w_uv = _operands(5, 1, 16, jnp.float32)
    keep = _keep(6, 1, 16, 8, 5)
    args = (q, rows[:, :48], keep[..., :48], w_uk, w_uv, jnp.int32(24))
    np.testing.assert_allclose(
        dsa_prefill.dsa_prefill_attention(*args, scale=0.3, interpret=True),
        dsa_prefill.dsa_prefill_attention(*args, scale=0.3),
        rtol=1e-5, atol=1e-5)
    tall = jnp.zeros((1, 520, WIDTH), jnp.float32)
    for interpret in (None, True):
        with pytest.raises(ValueError, match="512 rows do not divide 520"):
            dsa_prefill.dsa_prefill_attention(
                q, tall, jnp.zeros((1, 16, 520), bool), w_uk, w_uv,
                jnp.int32(24), scale=0.3, interpret=interpret)
    assert dsa_prefill._tiling(2048, 32768, 128) == (4, 512, 512)
    assert dsa_prefill._tiling(512, 1536, 64) == (4, 512, 512)
    assert dsa_prefill._tiling(37, 192, 4) == (4, 64, 192)
