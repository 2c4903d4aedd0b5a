"""Pallas TPU kernel: single-token decode attention over a KV cache.

The serving engine's hot op (one token per slot per step): q is ONE
query position per sequence attending to a long cache. The
training-shaped flash kernel (ops/attention.py) wants big q blocks;
decode has q_len == 1, so its arithmetic is pure KV streaming, and what
the kernel streams follows ``lengths``: a slot's rows are split into
blocks of ``block_s`` rows (all KV heads of them at once), and only the
blocks that begin under ``lengths[b]`` leave HBM. A block past it, and a
slot of length 0, costs no DMA, no arithmetic and no grid step.

  q      [B, KH, G, D]     the GQA group folded into the product's
                           rows; the slots of one grid step resident
                           in VMEM
  k,v    [L, B, KH, S, D]  left in HBM — the engine's WHOLE cache is the
                           operand and ``layer`` picks the blocks, so no
                           layer is sliced out of it first (a
                           [B,KH,S,D] cache is the L == 1 case)
  len    [B]               int32, SMEM scalar-prefetch
  layer  [1]               int32, SMEM scalar-prefetch
  out    [B, KH, G, D]     0 for a slot of length 0

One grid step serves a group of slots: it lists the (slot, block) pairs
it must read in SMEM, then ONE loop walks the list with the copies
``_NBUF - 1`` blocks ahead of the arithmetic (``make_async_copy`` into
rotating VMEM buffers), so the DMA queue stays full across slot
boundaries; the online-softmax carry of the slot under way lives in
VMEM scratch. On the chip (PERF.md, PR 34) a (slot, block) grid with
past blocks parked on the last fetched one paid a third of a
microsecond for every block it skipped, which is what the skipped
bytes saved in a batch with idle slots.

Falls back to a pure-jnp reference implementation off-TPU (and under
``interpret=True`` for the CPU test suite, which checks the kernel against
that reference exactly).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_reference(q, k, v, lengths):
    """Pure-jnp reference: q [B,H,D], k/v [B,S,KH,D], lengths [B] ->
    [B,H,D]. GQA via head-group repetition; masked softmax over the
    valid cache prefix."""
    b, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    rep = h // kh
    qg = q.reshape(b, kh, rep, d)
    kk = k.transpose(0, 2, 1, 3)  # [B,KH,S,D]
    vv = v.transpose(0, 2, 1, 3)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, kk,
                        preferred_element_type=jnp.float32)
    logits = logits * (d ** -0.5)
    mask = jnp.arange(s)[None, :] < lengths[:, None]  # [B,S]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(vv.dtype), vv)
    return out.reshape(b, h, d)


# A block is all KV heads x ``block_s`` rows of one operand. Measured on
# a v5e (PERF.md, PR 34): 256 KiB blocks three deep keep the queue as
# full as 512 KiB ones two deep and round a slot's rows up by half as
# much; under 128 rows the logits are less than a lane tile.
_NBUF = 3
DMA_TARGET_BYTES = 256 << 10
# q and the output of one grid step's slots, each way (a [G, D] tile
# is padded to 16 sublanes; the pipeline holds two of each).
_Q_GROUP_BYTES = 1 << 20
LANES = 128


def decode_block_rows(s: int, kh: int, d: int, itemsize: int,
                      target: int = DMA_TARGET_BYTES) -> int:
    """Rows of a slot that one block holds: the largest power of two
    whose block stays under the DMA's byte target (a row fills whole
    lane tiles), at least 128, at most the slot."""
    rows = max(128, target // (kh * (d + -d % LANES) * itemsize))
    return min(s, 1 << (rows.bit_length() - 1))


def blocks_streamed(lengths, block_s: int):
    """Blocks of ``block_s`` rows that begin under ``lengths``: what the
    kernel fetches of a slot, as the trip count of its loop."""
    return jax.lax.div(lengths + (block_s - 1), block_s)


def decode_step_rows(lengths, live, cache):
    """What a decode step asks of this kernel, for ``lengths`` [B] (each
    slot's write position), ``live`` [B] bool (None: all) and the
    [L,B,KH,S,D] cache: -> (seen [B] int32, the rows of each slot that
    attention reads: its new row and those before it, none of a slot
    that is not live; one layer's counters: ``decode_attn_rows`` = their
    sum, ``decode_attn_rows_streamed`` = the rows of the blocks the
    kernel fetches for them)."""
    seen = (lengths + 1).astype(jnp.int32)
    if live is not None:
        seen = jnp.where(live, seen, 0)
    _, _, kh, s, d = cache.shape
    block_s = decode_block_rows(s, kh, d, cache.dtype.itemsize)
    return seen, {
        "decode_attn_rows": jnp.sum(seen),
        "decode_attn_rows_streamed":
            jnp.sum(blocks_streamed(seen, block_s)) * block_s}


def slot_group(b: int, slot_bytes: int) -> int:
    """The slots of one grid step: the largest divisor of ``b`` whose
    queries (``slot_bytes`` a slot) fit ``_Q_GROUP_BYTES``."""
    group = max(1, min(b, _Q_GROUP_BYTES // slot_bytes))
    while b % group:
        group -= 1
    return group


def list_blocks(len_ref, work_ref, first, slots: int, block_s: int):
    """Write into ``work_ref`` [2, n] (SMEM) the (slot of the group,
    block) pairs of the ``slots`` slots from ``first`` on whose block
    begins under the slot's length, in reading order -> how many."""
    def list_slot(j, t):
        def list_block(i, t):
            work_ref[0, t] = j
            work_ref[1, t] = i
            return t + 1
        return jax.lax.fori_loop(
            0, blocks_streamed(len_ref[first + j], block_s), list_block, t)

    return jax.lax.fori_loop(0, slots, list_slot, 0)


def walk_blocks(total, nbuf: int, copies, block, arrive=None):
    """ONE loop over a list of ``total`` blocks: ``copies(t)`` are block
    t's copies into buffer ``t mod nbuf``, started ``nbuf - 1`` blocks
    ahead of ``block(t)``, which finds them landed.

    ``arrive(t)`` (optional) is the part of block t's work that needs
    its copies and nothing of the blocks before it. It runs ONE
    iteration early, in the same basic block as ``block(t - 1, ...)``,
    so the two chains run under each other, and ``block(t, arrived)``
    is handed its result; block t's buffer stays whole until
    ``block(t)`` is through, so the copies are ``nbuf - 2`` blocks ahead
    of what is read first."""
    import jax.experimental.pallas as pl

    def start(t):
        @pl.when(t < total)
        def _():
            for copy in copies(t):
                copy.start()

    def wait(t):
        for copy in copies(t):
            copy.wait()

    for t in range(nbuf - 1):
        start(t)

    if arrive is None:
        def step(t, _):
            start(t + nbuf - 1)     # into the buffer block t - 1 has left
            wait(t)
            block(t)

        jax.lax.fori_loop(0, total, step, None)
        return

    @pl.when(total > 0)
    def _walk():
        wait(0)

        def step(t, arrived):
            start(t + nbuf - 1)

            @pl.when(t + 1 < total)
            def _():
                wait(t + 1)

            # The list's last block arrives twice; nobody reads the
            # second.
            ahead = arrive(jnp.minimum(t + 1, total - 1))
            block(t, arrived)
            return ahead

        jax.lax.fori_loop(0, total, step, arrive(0))


def _decode_kernel(len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, work_ref, m_ref, l_ref, acc_ref, *,
                   block_s: int, scale: float):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, nbuf = q_ref.shape[0], k_buf.shape[0]
    first = pl.program_id(0) * slots
    layer = layer_ref[0]
    total = list_blocks(len_ref, work_ref, first, slots, block_s)
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(t):
        j, i, buf = work_ref[0, t], work_ref[1, t], jax.lax.rem(t, nbuf)
        rows = pl.ds(pl.multiple_of(i * block_s, block_s), block_s)
        return [pltpu.make_async_copy(
            hbm.at[layer, first + j, :, rows, :], vmem.at[buf],
            sems.at[n, buf])
            for n, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf)))]

    def block(t):
        j, i, buf = work_ref[0, t], work_ref[1, t], jax.lax.rem(t, nbuf)
        length = len_ref[first + j]

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Inputs stay in their storage dtype (bf16 on the serving path):
        # the MXU takes bf16 operands with f32 accumulation, and the f32
        # upcasts cost ~1.8x end-to-end (measured on v5e).
        q, k, v = q_ref[j], k_buf[buf], v_buf[buf]   # [KH,G,D], [KH,bs,D]
        logits = jnp.einsum("hgd,hsd->hgs", q, k,
                            preferred_element_type=jnp.float32) * scale
        positions = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        # The block begins under ``length``: at least one row is real.
        logits = jnp.where(positions < length, logits, NEG_INF)
        m_prev = m_ref[...]                          # [KH, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                  # [KH, G, bs] f32
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.einsum(
            "hgs,hsd->hgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(i == blocks_streamed(length, block_s) - 1)
        def _finish():
            o_ref[j] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    walk_blocks(total, nbuf, copies, block)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "layout"))
def decode_attention(q, k, v, lengths, *, layer=None,
                     block_s: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     layout: str = "bskd"):
    """q [B,H,D], lengths [B] int32 -> [B,H,D]. Uses the Pallas kernel on
    TPU (or interpret mode when forced); pure-jnp reference elsewhere.

    ``layout`` names the cache layout: "bskd" = [B,S,KH,D] (the training
    convention; transposed on entry — a full HBM round trip) or "bksd" =
    [B,KH,S,D] (the engine-native layout this kernel streams directly —
    store the cache this way for decode-bound serving).

    ``layer`` (a traced int32 scalar): k and v are the engine's whole
    [L,B,KH,S,D] cache and the query attends to that layer of it. The
    kernel then reads the layer's blocks where they lie; only a cache
    whose rows the block does not divide, or whose head size does not
    fill lane tiles (a DMA cannot slice such an array: head size 64
    pays a copy of the layer a call), is sliced and padded first, as is
    the reference's.

    ``block_s``, the rows of a block, comes from the shapes
    (``decode_block_rows``); tests pass small ones."""
    kernel = jax.default_backend() == "tpu" or bool(interpret)
    if layer is not None and layout != "bksd":
        raise ValueError("a layered cache is [L,B,KH,S,D]: layout 'bksd'")
    b, h, d = q.shape
    s, kh = ((k.shape[-2], k.shape[-3]) if layout == "bksd"
             else (k.shape[1], k.shape[2]))
    if block_s is None:
        block_s = decode_block_rows(s, kh, d, k.dtype.itemsize)
    pad_s, pad_d = -s % block_s, -d % LANES
    if layer is not None and not (kernel and pad_s == pad_d == 0):
        k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        layer = None
    if not kernel:
        if layout == "bksd":
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
        return decode_attention_reference(q, k, v, lengths)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layer is None:
        if layout == "bskd":
            k = k.transpose(0, 2, 1, 3)  # [B,KH,S,D]
            v = v.transpose(0, 2, 1, 3)
        if pad_s or pad_d:      # zero columns add nothing to a product
            pad = ((0, 0), (0, 0), (0, pad_s), (0, pad_d))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_d)))
        k, v, layer = k[None], v[None], 0    # [1,B,KH,S,D]: layer 0 of one
    rep, scale, d = h // kh, d ** -0.5, d + pad_d
    group = slot_group(b, kh * max(rep, 16) * d * q.dtype.itemsize)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((group, kh, rep, d), lambda g, *_: (g, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // group,),
        in_specs=[q_spec, in_hbm, in_hbm],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((_NBUF, kh, block_s, d), k.dtype),
            pltpu.VMEM((_NBUF, kh, block_s, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, _NBUF)),
            # (slot of the group, block) of every block to read
            pltpu.SMEM((2, group * (k.shape[3] // block_s)), jnp.int32),
            pltpu.VMEM((kh, rep, 1), jnp.float32),   # running max
            pltpu.VMEM((kh, rep, 1), jnp.float32),   # running denom
            pltpu.VMEM((kh, rep, d), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
        interpret=bool(interpret),
        name="rtpu_decode_attention",
        metadata={"kernel": "rtpu_decode_attention"},
    )(lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(b, kh, rep, d),
      k, v)
    return out.reshape(b, h, d)[..., :d - pad_d]
