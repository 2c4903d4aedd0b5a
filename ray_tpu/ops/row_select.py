"""A query that CHOOSES the single rows it attends to (the learned
sparse attention of the DeepSeek-V3.2 family, which ``dots3_note``
carries): an indexer scores every visible row for each query,

    I[t, s] = sum_i w[t, i] * relu(q_i[t] . k[s])        float32

(``w`` already holds the constant ``(heads * head_dim) ** -0.5``), and
the query attends to the ``k`` rows of largest ``I[t, .]``, all of its
attention heads over the same rows; with no more than ``k`` rows
visible it attends to them all. The rule is by QUERY POSITION, so one
pass, a prefill in chunks and a decode step compute one function.

- `index_scores`: the scores of a chunk of queries against a slot's
  index keys, a tile of rows at a time (the [T, heads, rows] products
  of a whole slot do not fit), tiles past the chunk's end not touched.
- `top_rows`: the exact top-k as a MASK over rows, with no sort, as
  `ops/sparse_attention._best` finds its blocks, but on SIGNED scores
  (an order-preserving integer key) and over tens of thousands of
  rows, where a running count of the ties would be a scan: the k-th
  largest key is found a bit at a time, and so is the last tied row
  that still has room; ties go to the lower row, as `lax.top_k`'s do.
  NOT `lax.approx_max_k`: an approximate choice is another result.
- `top_rows_within`: `top_rows` over the shortest of a few static
  extents that holds every visible row (each pass of the search reads
  the whole score array: a chunk at row 4,000 of 32,768 would pay for
  eight times what it can see).
- `select_decode_rows`: a decode step's scoring AND selection for every
  slot in ONE Pallas call (``rtpu_dsa_select``): a slot's index keys
  are streamed a block of rows at a time up to its length and no
  further, the scores stay in fast memory (32,768 float32 are 32
  registers' worth), the two searches run there, and what comes back
  is the mask over rows that the latent kernel
  (``ops/mla_decode.py``'s ``keep``) attends under. Off the TPU its
  ``jnp`` twin runs (`index_scores`' arithmetic and `top_rows`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

F32 = jnp.float32
_INT_MIN = -2 ** 31


def order_keys(scores, visible):
    """float32 scores -> int32 keys that order as the scores do (-0.0
    with 0.0), a row that is not visible below every real score."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(F32))
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(visible, keys, jnp.int32(_INT_MIN))


def top_rows(scores, visible, k: int):
    """scores [.., S] float32, visible [.., S] bool -> [.., S] bool: the
    ``k`` visible rows of largest score, ties to the lower row; every
    visible row where there are no more than ``k``."""
    keys = order_keys(scores, visible)
    s = keys.shape[-1]

    def at_least(threshold):
        return jnp.sum(keys >= threshold[..., None], axis=-1)

    # The sign first: INT_MIN | bits grows with the bits, as 0 | bits.
    lead = keys.shape[:-1]
    kth = jnp.where(at_least(jnp.zeros(lead, jnp.int32)) >= k,
                    jnp.int32(0), jnp.int32(_INT_MIN))

    def bit(i, kth):
        tried = kth | lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(at_least(tried) >= k, tried, kth)

    kth = lax.fori_loop(0, 31, bit, kth)
    above = keys > kth[..., None]
    tied = keys == kth[..., None]
    room = k - jnp.sum(above, axis=-1)                       # >= 1
    rows = jnp.arange(s, dtype=jnp.int32)

    def tied_before(row):
        return jnp.sum(tied & (rows < row[..., None]), axis=-1)

    # The largest row with fewer than ``room`` tied rows before it: the
    # last tied row that is taken.
    def row_bit(i, last):
        tried = last | lax.shift_left(jnp.int32(1),
                                      (s - 1).bit_length() - 1 - i)
        return jnp.where(tied_before(tried) < room, tried, last)

    last = lax.fori_loop(0, (s - 1).bit_length(), row_bit,
                         jnp.zeros(lead, jnp.int32))
    return (above | (tied & (rows <= last[..., None]))) & visible


def top_rows_within(scores, visible, k: int, rows_seen):
    """`top_rows` where no row at or past ``rows_seen`` (a traced
    scalar) is visible: over the first quarter, half, three quarters
    or the whole of the rows, whichever is the shortest that holds
    them all. On a v5e, [2,048 queries, 32,768 rows]: `top_rows` 14.4
    ms whatever is visible; this 1.2 ms up to row 8,192, 7.8 up to
    16,384, 11.2 up to 24,576, 15.1 past it (PERF.md, PR 42)."""
    s = scores.shape[-1]
    if s % 4 or s // 4 < k:
        return top_rows(scores, visible, k)

    def upto(extent):
        def run(_):
            mask = top_rows(scores[..., :extent], visible[..., :extent], k)
            return jnp.pad(mask, [(0, 0)] * (mask.ndim - 1)
                           + [(0, s - extent)])
        return run

    which = jnp.clip((jnp.asarray(rows_seen, jnp.int32) - 1) // (s // 4),
                     0, 3)
    return lax.switch(which, [upto(s * (i + 1) // 4) for i in range(4)],
                      None)


def index_scores(q, w, keys, rows_seen=None, *, tile: int = 512):
    """q [T, Hi, Di], w [T, Hi] float32 (the heads' weights, the
    constant folded in), keys [S, Di] (a slot's index keys of one
    layer) -> [T, S] float32, zero in the tiles that begin at or past
    ``rows_seen`` (None: every tile)."""
    t, s = q.shape[0], keys.shape[0]
    tile = min(tile, s)
    if s % tile:
        raise ValueError(f"tiles of {tile} rows do not divide {s}")
    q = q.astype(keys.dtype)

    def one(i, out):
        k_t = lax.dynamic_slice_in_dim(keys, i * tile, tile, axis=0)
        part = jnp.einsum("thd,sd->ths", q, k_t, preferred_element_type=F32)
        # float32 x float32: the chip's default would round both to
        # bf16, and the scores are stated in float32.
        part = jnp.einsum("ths,th->ts", jax.nn.relu(part), w.astype(F32),
                          precision=lax.Precision.HIGHEST)
        # i < s / tile: the loop's own bound.
        return lax.dynamic_update_slice_in_dim(  # rtpu-lint: disable=unclamped-dynamic-update-slice
            out, part, i * tile, axis=1)

    n = s // tile if rows_seen is None else jnp.minimum(
        lax.div(jnp.asarray(rows_seen, jnp.int32) + (tile - 1), tile),
        s // tile)
    return lax.fori_loop(0, n, one, jnp.zeros((t, s), F32))


def select_decode_rows_reference(q, w, keys, positions, k: int):
    """q [B,Hi,Di], w [B,Hi] float32, keys [B,S,Di] (one layer's index
    keys), positions [B] (the query's own row; negative: the slot reads
    nothing) -> [B,S] float32, 1.0 on the rows the query attends to."""
    s = keys.shape[1]
    scores = jnp.einsum("bhd,bsd->bhs", q.astype(keys.dtype), keys,
                        preferred_element_type=F32)
    scores = jnp.einsum("bhs,bh->bs", jax.nn.relu(scores), w.astype(F32),
                        precision=lax.Precision.HIGHEST)
    visible = jnp.arange(s)[None, :] <= positions[:, None]
    keep = jnp.where((positions + 1 <= k)[:, None], visible,
                     top_rows(scores, visible, k))
    return keep.astype(F32)


def _select_kernel(pos_ref, layer_ref, q_ref, w_ref, keys_ref, keep_ref,
                   scores_ref, *, block_s: int, k: int):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    s_idx = pl.program_id(1)
    n_s = pl.num_programs(1)
    pos = pos_ref[b]

    @pl.when(s_idx * block_s <= pos)
    def _scores():
        part = lax.dot_general(
            q_ref[0], keys_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=F32)                   # [Hi, block_s]
        # The heads' weighted sum on the vector unit: exact float32.
        row = jnp.sum(jnp.maximum(part, 0.0) * w_ref[0], axis=0,
                      keepdims=True)                      # [1, block_s]
        scores_ref[pl.ds(s_idx, 1), :] = row

    @pl.when(s_idx == n_s - 1)
    def _choose():
        shape = scores_ref.shape                          # [n_s, block_s]
        rows = (lax.broadcasted_iota(jnp.int32, shape, 0) * block_s
                + lax.broadcasted_iota(jnp.int32, shape, 1))
        visible = rows <= pos
        # Blocks past the slot's length were never scored: whatever the
        # scratch holds there is not visible.
        scores = scores_ref[...]
        scores = jnp.where(scores == 0, 0.0, scores)
        bits = lax.bitcast_convert_type(scores, jnp.int32)
        keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        keys = jnp.where(visible, keys, jnp.int32(_INT_MIN))

        def count(mask):                                  # -> [1, 1]
            n = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
            return jnp.sum(n, axis=0, keepdims=True)

        zero = jnp.zeros((1, 1), jnp.int32)
        kth = jnp.where(count(keys >= zero) >= k, zero,
                        jnp.int32(_INT_MIN))

        def bit(i, kth):
            tried = kth | lax.shift_left(jnp.int32(1), 30 - i)
            return jnp.where(count(keys >= tried) >= k, tried, kth)

        kth = lax.fori_loop(0, 31, bit, kth)
        above = keys > kth
        tied = keys == kth
        room = k - count(above)
        n_bits = (shape[0] * shape[1] - 1).bit_length()

        def row_bit(i, last):
            tried = last | lax.shift_left(jnp.int32(1), n_bits - 1 - i)
            return jnp.where(count(tied & (rows < tried)) < room, tried,
                             last)

        last = lax.fori_loop(0, n_bits, row_bit, zero)
        best = above | (tied & (rows <= last))
        keep_ref[0] = jnp.where(pos + 1 <= k, visible.astype(F32),
                                (best & visible).astype(F32))


@functools.partial(jax.jit, static_argnames=("k", "block_s", "interpret"))
def select_decode_rows(q, w, cache, positions, *, layer, k: int,
                       block_s: int = 2048,
                       interpret: Optional[bool] = None):
    """One decode step's choice of rows, every slot: q [B,Hi,Di] (the
    indexer's rotated queries), w [B,Hi] float32 (the heads' weights,
    the constant folded in), cache [L,B,S,Di] (the index keys, this
    step's written), positions [B] int32 (the query's own row; negative
    for a slot that reads nothing), ``layer`` a traced int32 scalar ->
    [B,S] float32: 1.0 on the rows the query attends to (every row up
    to its own while no more than ``k`` are visible, else the ``k`` of
    largest score, ties to the lower row). The Pallas kernel on the TPU
    (or under ``interpret``), its twin elsewhere and where ``block_s``
    does not divide the cache's rows."""
    on_tpu = jax.default_backend() == "tpu"
    n_layers, b, s, di = cache.shape
    block_s = min(block_s, s)
    positions = positions.astype(jnp.int32)
    if not ((on_tpu or interpret) and s % block_s == 0):
        keys = lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        return select_decode_rows_reference(q, w, keys, positions, k)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hi = q.shape[1]
    n_s = s // block_s

    def _keys_index(bi, si, pos, layer):
        # A block past the slot's last row is parked on the last valid
        # one: no fresh copy, and its arithmetic is skipped.
        last = jnp.maximum(pos[bi], 0) // block_s
        return layer[0], bi, jnp.minimum(si, last), 0

    def _slot_index(bi, si, pos, layer):
        return bi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_s),
        in_specs=[pl.BlockSpec((1, hi, di), _slot_index),
                  pl.BlockSpec((1, hi, 1), _slot_index),
                  pl.BlockSpec((1, 1, block_s, di), _keys_index)],
        out_specs=pl.BlockSpec((1, n_s, block_s), _slot_index),
        scratch_shapes=[pltpu.VMEM((n_s, block_s), F32)],
    )
    keep = pl.pallas_call(
        functools.partial(_select_kernel, block_s=block_s, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_s, block_s), F32),
        interpret=bool(interpret),
        name="rtpu_dsa_select",
        metadata={"kernel": "rtpu_dsa_select"},
    )(positions, jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(cache.dtype), w.astype(F32)[..., None], cache)
    return keep.reshape(b, s)
