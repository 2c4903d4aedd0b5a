"""Pallas TPU kernel: single-token decode attention over a LATENT cache
(multi-head latent attention, absorbed form).

With the key and value up-projections absorbed into the query and the
output, MLA's decode step is multi-query attention over ONE shared
"head": every query head of a slot attends to the same cached row, whose
first ``v_dim`` columns (the normed latent ``c_kv``) are also the value
and whose remaining columns are the shared rotary key:

  q      [B, H, Dk]       q~_h = q_nope_h W_uk,h^T  ++  q_rope_h
  cache  [L, B, S, Dk]    c_kv (v_dim) ++ k_rope; the engine's WHOLE
                          cache is the operand and ``layer`` picks the
                          blocks, as in ``ops/decode_attention.py``
  len    [B]              int32, SMEM scalar-prefetch
  layer  [1]              int32, SMEM scalar-prefetch
  out    [B, H, v_dim]    Σ_s softmax(q · row_s * scale) row_s[:v_dim]

The cache stays in HBM. One grid step serves a group of slots whose
queries and outputs are resident in VMEM: it lists in SMEM the (slot,
block) pairs whose block BEGINS under the slot's length, and ONE loop
walks the list with the copies ``_NBUF - 1`` blocks ahead of a block's
softmax and values, so the DMA queue stays full across slot boundaries
(the design, the listing and the walk are ``ops/decode_attention.py``'s,
PR 34: a (slot, block) grid that parked the blocks past a length paid
a fifth of a microsecond for each). The scores of the NEXT block are
computed beside the softmax and the values of this one, so a block's
chain of latencies hides under its neighbour's. Each block is read ONCE
and serves both the score product (all Dk columns) and the value
product (its first v_dim columns, a lane-aligned slice); the
online-softmax carry of the slot under way lives in VMEM scratch. A
block past ``lengths[b]``, and a slot of length 0, costs no DMA, no
arithmetic and no grid step. The rows of a block come from the shapes
(``mla_block_rows``).

``keep`` [B, S] (optional) says which of a slot's rows under its length
the query attends to at all: a family whose queries CHOOSE their rows
(a learned top-k over single rows), or whose rows are a ring that keeps
only the last few hundred (a window), hands the kernel its choice as a
mask and the rows are read whole under it, where they lie. ``name`` is
what the call is known by in a device trace: a family that runs the
kernel at two geometries in one step tells them apart by it.

Off the TPU the jnp reference runs (``interpret=True`` runs the kernel
under the Pallas interpreter, for the CPU tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.decode_attention import (DMA_TARGET_BYTES, blocks_streamed,
                                          decode_block_rows, list_blocks,
                                          slot_group, walk_blocks)

NEG_INF = -1e30


def mla_decode_attention_reference(q, kv, lengths, *, v_dim: int,
                                   scale: float, keep=None):
    """q [B,H,Dk], kv [B,S,Dk], lengths [B], keep [B,S] | None ->
    [B,H,v_dim] (0 where a slot attends to no row)."""
    logits = jnp.einsum("bhd,bsd->bhs", q, kv,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(kv.shape[1])[None, :] < lengths[:, None]
    if keep is not None:
        mask = mask & (keep > 0)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[:, None, :], probs, 0.0)
    out = jnp.einsum("bhs,bsd->bhd", probs.astype(kv.dtype),
                     kv[..., :v_dim], preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# Measured on a v5e (PERF.md, PR 58). A block's chain (scores, maximum,
# exp, values) is some 0.4 us of latency whatever its rows, so the
# scores of block t + 1 are computed beside the rest of block t
# (``walk_blocks``' ``arrive``): then 256 rows (320 KB, 0.4 us of copy)
# hide it at 20 to 32 heads, 128 rows pay it twice as often and 512
# round a slot up by twice as much. At 128 heads the products and the
# softmax of a block take as long as its copy and nothing hides what is
# left a block: twice the rows halve it.
_NBUF = 5
BLOCK_TARGET_BYTES = 2 * DMA_TARGET_BYTES   # ONE operand for both products


def mla_block_rows(s: int, w: int, itemsize: int, h: int) -> int:
    """Rows of a slot that one block holds: ``decode_block_rows`` for
    the one operand of ``w`` columns this kernel streams, twice that
    from 128 query heads on."""
    rows = decode_block_rows(s, 1, w, itemsize, BLOCK_TARGET_BYTES)
    return min(s, rows * (2 if h >= 128 else 1))


def mla_step_rows(lengths, cache, heads: int):
    """What a decode step asks of this kernel, for ``lengths`` [B] (each
    slot's write position; ALL slots: a family's step does not read
    ``live``), the [L,B,S,W] cache and the query heads -> one layer's
    counters: ``mla_decode_rows`` = Σ (lengths + 1), the rows the call
    is asked to read, ``mla_decode_rows_streamed`` = the rows of the
    blocks the kernel fetches for them."""
    seen = lengths.astype(jnp.int32) + 1
    _, _, s, w = cache.shape
    block_s = mla_block_rows(s, w, cache.dtype.itemsize, heads)
    return {
        "mla_decode_rows": jnp.sum(seen),
        "mla_decode_rows_streamed":
            jnp.sum(blocks_streamed(seen, block_s)) * block_s}


def _mla_kernel(len_ref, layer_ref, q_ref, *rest, block_s: int, v_dim: int,
                scale: float, kept: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    keep_ref = rest[0] if kept else None
    (cache_hbm, o_ref, buf_ref, sems, work_ref, m_ref, l_ref,
     acc_ref) = rest[-8:]
    slots, nbuf = q_ref.shape[0], buf_ref.shape[0]
    first = pl.program_id(0) * slots
    layer = layer_ref[0]
    total = list_blocks(len_ref, work_ref, first, slots, block_s)
    o_ref[...] = jnp.zeros_like(o_ref)

    def entry(t):
        return work_ref[0, t], work_ref[1, t], jax.lax.rem(t, nbuf)

    def copies(t):
        j, i, buf = entry(t)
        rows = pl.ds(pl.multiple_of(i * block_s, block_s), block_s)
        return [pltpu.make_async_copy(
            cache_hbm.at[layer, first + j, rows, :], buf_ref.at[buf],
            sems.at[buf])]

    def scores(t):
        """Block t's logits [H, block_s], NEG_INF where the query does
        not attend."""
        j, i, buf = entry(t)
        logits = jax.lax.dot_general(
            q_ref[j], buf_ref[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        positions = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        valid = positions < len_ref[first + j]
        if kept:
            valid = valid & (keep_ref[j, pl.ds(i, 1), :] > 0)  # [1, block_s]
        return jnp.where(valid, logits, NEG_INF)

    def block(t, logits):
        j, i, buf = entry(t)
        # A slot's first block starts its carry: a select, not a branch
        # (a branch would end the basic block the two chains share).
        fresh = i == 0
        m_prev = jnp.where(fresh, NEG_INF, m_ref[...])
        l_prev = jnp.where(fresh, 0.0, l_ref[...])
        acc_prev = jnp.where(fresh, 0.0, acc_ref[...])
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        if kept:
            # A block may hold no kept row: its running maximum is still
            # the floor, and exp(floor - floor) is one, not nothing.
            p = jnp.where(logits > 0.5 * NEG_INF, p, 0.0)
        rows = buf_ref[buf]                          # [block_s, Dk]
        l_ref[...] = l_prev * correction + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_prev * correction + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(i == blocks_streamed(len_ref[first + j], block_s) - 1)
        def _finish():
            # A slot none of whose rows is kept accumulated nothing:
            # 0 / eps = 0.
            o_ref[j] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    walk_blocks(total, nbuf, copies, block, arrive=scores)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "block_s",
                                             "interpret", "name"))
def mla_decode_attention(q, cache, lengths, *, layer, v_dim: int,
                         scale: float, block_s: Optional[int] = None,
                         interpret: Optional[bool] = None, keep=None,
                         name: str = "rtpu_mla_decode_attention"):
    """q [B,H,Dk], cache [L,B,S,Dk], lengths [B] int32, ``layer`` a
    traced int32 scalar, ``keep`` [B,S] (optional; > 0: the row is
    attended to) -> [B,H,v_dim]: the Pallas kernel on the TPU (or under
    ``interpret``), the jnp reference elsewhere and where the block
    does not divide the cache's rows. ``block_s``, the rows of a block,
    comes from the shapes (``mla_block_rows``); tests pass small ones,
    and a family whose rows are a ring hands the ring as ONE block."""
    on_tpu = jax.default_backend() == "tpu"
    n_layers, b, s, dk = cache.shape
    h = q.shape[1]
    if block_s is None:
        block_s = mla_block_rows(s, dk, cache.dtype.itemsize, h)
    block_s = min(block_s, s)
    if not ((on_tpu or interpret) and s % block_s == 0):
        kv = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        return mla_decode_attention_reference(q, kv, lengths, v_dim=v_dim,
                                              scale=scale, keep=keep)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # A [H, Dk] tile of 16-bit queries is padded to 16 sublanes.
    group = slot_group(b, (h + -h % 16) * dk * q.dtype.itemsize)
    kept = keep is not None
    in_specs = [pl.BlockSpec((group, h, dk), lambda g, *_: (g, 0, 0))]
    operands = [q]
    if kept:
        # A group's masks resident beside its queries, a block's tile a
        # row: [1, block_s] at a dynamic sublane of a 32-bit array.
        in_specs.append(pl.BlockSpec((group, s // block_s, block_s),
                                     lambda g, *_: (g, 0, 0)))
        operands.append(keep.astype(jnp.float32).reshape(
            b, s // block_s, block_s))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    operands.append(cache)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // group,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((group, h, v_dim), lambda g, *_: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_NBUF, block_s, dk), cache.dtype),
            pltpu.SemaphoreType.DMA((_NBUF,)),
            # (slot of the group, block) of every block to read
            pltpu.SMEM((2, group * (s // block_s)), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),         # running max
            pltpu.VMEM((h, 1), jnp.float32),         # running denom
            pltpu.VMEM((h, v_dim), jnp.float32),     # running numerator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, block_s=block_s, v_dim=v_dim,
                          scale=scale, kept=kept),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        interpret=bool(interpret),
        name=name,
        metadata={"kernel": name},
    )(lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *operands)
