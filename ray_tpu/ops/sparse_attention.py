"""Block-sparse attention that chooses its own rows (the InfLLM-v2
selection of the MiniCPM4 family), over the engine's K/V cache.

Beside its K and V rows a slot keeps COMPRESSED keys, one a window of
``kernel`` rows every ``stride`` rows (``kc_j = mean(k[stride j :
stride j + kernel])``, kernel = 2 stride). A query at position t (t + 1
rows visible) whose context is past ``dense_len`` reads only ``topk``
blocks of ``block`` rows, chosen a KV head at a time for all the G
query heads of its group:

    p^h   = softmax_j(q^h . kc_j / sqrt(D))   over the windows that are
                                              complete: stride j + kernel <= t + 1
    s[j]  = sum_{h in group} p^h[j]
    score[n] = max s[j] over the windows that overlap block n
    selected = block 0 .. init_blocks-1 and the blocks that hold the last
               ``window`` rows (forced), then the best-scoring others,
               ``topk`` in all

and attends, causally, over the rows of those blocks. At or under
``dense_len`` it reads every row. The rule is taken BY QUERY POSITION,
so a prefill in chunks, a decode step and one pass over the whole
sequence compute one function.

- `window_means` makes compressed keys from rows.
- `select_blocks` ranks a decode step's blocks: a list of block ids a
  (slot, KV head) and how many of them count: a block table made anew
  every step. A dense slot's list is all its blocks.
- `sparse_decode_attention` reads the listed blocks of every slot where
  they lie in the engine's ``[L, B, KH, S, D]`` cache: one Pallas call
  (``rtpu_sparse_decode_attention``) whose grid step walks ONE list of
  (slot, head, group of ``_PER`` blocks) with the copies ahead of the
  arithmetic, as ``ops/decode_attention.py`` walks (slot, block); a
  block is 64 rows x 128 x 2 B = 16 KiB, so ``_PER`` of them are
  fetched into one buffer and multiplied at once. Its ``jnp`` twin
  gathers the blocks.
- `sparse_prefill_attention` serves a chunk of queries of ONE slot:
  each query's own selection as a mask over blocks (`_best`: no sort),
  attention tile by tile over the rows up to the chunk's end and no
  further (``jnp`` under a loop whose trip count follows the slot's
  length; no kernel yet). It hands the mask back beside its output.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30
_FORCED = 1e30          # a forced block's score: above any sum of softmaxes
_NBUF = 3               # buffers of `_PER` blocks a kernel keeps in flight
_PER = 8                # blocks fetched into one buffer and multiplied at once


@dataclasses.dataclass(frozen=True)
class Selection:
    """The sizes of the selection (MiniCPM4's published ``sparse_config``)."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if (self.kernel != 2 * self.stride or self.block % self.stride
                or self.block & (self.block - 1)):
            raise ValueError("the selection is written for kernel = 2 x "
                             "stride and blocks of whole strides, a power "
                             "of two of rows")
        if self.init_blocks * self.block + self.window + self.block \
                > self.topk * self.block:
            raise ValueError("the forced blocks alone pass topk")
        if self.dense_len < self.topk * self.block:
            raise ValueError("a context past dense_len must hold topk "
                             "blocks to choose")

    def list_len(self, rows: int) -> int:
        """Entries of a decode step's block list: ``topk``, or all the
        blocks of a context that is still dense."""
        return min(rows // self.block,
                   max(self.topk, -(-self.dense_len // self.block)))


def window_means(rows, sel: Selection):
    """rows [.., R, D] (R whole strides) -> [.., R/stride - 1, D]
    float32: the mean of every window of ``kernel`` rows that begins at
    a multiple of ``stride`` and ends inside R."""
    r, d = rows.shape[-2:]
    halves = jnp.mean(rows.astype(F32).reshape(
        rows.shape[:-2] + (r // sel.stride, sel.stride, d)), axis=-2)
    return 0.5 * (halves[..., :-1, :] + halves[..., 1:, :])


def _block_scores(q, kc, t1, sel: Selection):
    """q [KH,G,T,D], kc [KH,NW,D], t1 [T] (rows visible to each query)
    -> [KH,T,NW // (block/stride)] float32: every block's score, 0
    where no complete window overlaps it."""
    d, nw = q.shape[-1], kc.shape[1]
    logits = jnp.einsum("kgtd,kwd->kgtw", q.astype(kc.dtype), kc,
                        preferred_element_type=F32) * d ** -0.5
    complete = (sel.stride * jnp.arange(nw)[None, :] + sel.kernel
                <= t1[:, None])                                  # [T,NW]
    logits = jnp.where(complete, logits, NEG_INF)
    p = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
    p = jnp.where(complete, p, 0.0)
    s = jnp.sum(p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30), 1)
    # Window j overlaps block n for j in [per n - 1, per n + per - 1].
    per = sel.block // sel.stride
    groups = s.reshape(s.shape[:-1] + (nw // per, per))
    inside = jnp.max(groups, -1)
    before = jnp.pad(groups[..., :-1, -1], ((0, 0), (0, 0), (1, 0)))
    return jnp.maximum(inside, before)


def _keyed(scores, t1, sel: Selection):
    """scores [..,NBLK], t1 [..,1] -> (the scores as the selection ranks
    them: a forced block above any sum of softmaxes, a block past the
    context below any; whether the context is dense [..,1]; its last
    block [..,1])."""
    blocks = jnp.arange(scores.shape[-1])
    last = (t1 - 1) // sel.block
    dense = t1 <= sel.dense_len
    forced = (blocks < sel.init_blocks) | (
        blocks >= (t1 - sel.window) // sel.block) | dense
    scores = jnp.where(forced, _FORCED, scores)
    return jnp.where(blocks <= last, scores, -1.0), dense, last


def _ranked(scores, t1, sel: Selection, n: int):
    """scores [..,NBLK], t1 [..] -> (ids [..,n] int32 best first, count
    [..]): forced blocks first, blocks past the context never (their
    ids stand in the list past ``count``, in range, not to be read). A
    context at or under ``dense_len``: every visible block."""
    keyed, dense, last = _keyed(scores, t1[..., None], sel)
    ids = lax.top_k(keyed, n)[1].astype(jnp.int32)
    count = jnp.where(dense[..., 0], jnp.minimum(last[..., 0] + 1, n),
                      sel.topk)
    return ids, count.astype(jnp.int32)


def _best(scores, t1, sel: Selection):
    """The ``topk`` blocks `_ranked` would list first, as a mask
    [..,NBLK], with no sort (a `top_k` over 512 blocks for each of a chunk's 4,096
    (query, head) pairs was a full sort on the chip: an eighth of the
    cell's device time, PR 35): the k-th largest score is found a bit
    at a time (a non-negative float32's bits order as it does; the one
    negative value, a block past the context, orders below them), and
    ties at it go to the lower blocks, as `top_k`'s do."""
    keyed, _, _ = _keyed(scores, t1[..., None], sel)
    keys = lax.bitcast_convert_type(keyed, jnp.int32)

    def at_least(threshold):
        return jnp.sum(keys >= threshold[..., None], axis=-1)

    def bit(i, kth):
        tried = kth | lax.shift_left(jnp.int32(1), 30 - i)
        return jnp.where(at_least(tried) >= sel.topk, tried, kth)

    kth = lax.fori_loop(0, 31, bit, jnp.zeros(keys.shape[:-1], jnp.int32))
    above = keys > kth[..., None]
    tied = keys == kth[..., None]
    room = sel.topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def select_blocks(q, kc, t1, sel: Selection):
    """A decode step's block table. q [B,H,D], kc [B,KH,NW,D] (the
    slots' compressed keys of one layer), t1 [B] (rows each slot's
    query sees; 0: none) -> (ids [B,KH,N] int32, count [B] int32), N =
    ``sel.list_len``: the first ``count[b]`` ids of slot b are the
    blocks its query reads."""
    b, h, d = q.shape
    kh, nw = kc.shape[1:3]
    rows = nw * sel.stride
    qg = q.reshape(b, kh, h // kh, 1, d)
    scores = jax.vmap(lambda q, kc, t: _block_scores(q, kc, t, sel))(
        qg, kc, t1[:, None])[:, :, 0]                           # [B,KH,NBLK]
    ids, count = _ranked(scores, jnp.broadcast_to(t1[:, None], (b, kh)),
                         sel, sel.list_len(rows))
    return ids, jnp.where(t1 > 0, count[:, 0], 0)


# Decode -------------------------------------------------------------------

def sparse_decode_attention_reference(q, k, v, ids, count, seen, *,
                                      block: int):
    """The kernel's ``jnp`` twin: q [B,H,D], k, v [B,KH,S,D], ids
    [B,KH,N], count, seen [B] -> [B,H,D] (0 where count is 0)."""
    b, h, d = q.shape
    kh, s = k.shape[1:3]
    n = ids.shape[-1]

    def gather(rows):       # [B,KH,S,D] -> [B,KH,N,block,D]
        blocks = rows.reshape(b, kh, s // block, block, d)
        return jnp.take_along_axis(blocks, ids[..., None, None], axis=2)

    kk, vv = gather(k), gather(v)
    logits = jnp.einsum("bkgd,bknrd->bkgnr", q.reshape(b, kh, h // kh, d),
                        kk, preferred_element_type=F32) * d ** -0.5
    pos = ids[..., None] * block + jnp.arange(block)            # [B,KH,N,blk]
    valid = ((pos < seen[:, None, None, None])
             & (jnp.arange(n)[:, None] < count[:, None, None, None]))
    logits = jnp.where(valid[:, :, None], logits, NEG_INF)
    m = jnp.max(logits, (-2, -1), keepdims=True)
    p = jnp.where(valid[:, :, None], jnp.exp(logits - m), 0.0)
    out = jnp.einsum("bkgnr,bknrd->bkgd", p.astype(vv.dtype), vv,
                     preferred_element_type=F32)
    out = out / jnp.maximum(jnp.sum(p, (-2, -1)), 1e-30)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)


def _sparse_kernel(ids_ref, count_ref, len_ref, layer_ref, q_ref, k_hbm,
                   v_hbm, o_ref, k_buf, v_buf, sems, work_ref, m_ref, l_ref,
                   acc_ref, *, block: int, per: int, n_list: int,
                   scale: float):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, kh = q_ref.shape[0], q_ref.shape[1]
    nbuf = k_buf.shape[0]
    first = pl.program_id(0) * slots
    layer = layer_ref[0]

    def groups_of(j):
        return lax.div(count_ref[first + j] + (per - 1), per)

    def list_slot(j, t):
        def list_head(h, t):
            def list_group(i, t):
                work_ref[0, t] = j
                work_ref[1, t] = h
                work_ref[2, t] = i
                return t + 1
            return lax.fori_loop(0, groups_of(j), list_group, t)
        return lax.fori_loop(0, kh, list_head, t)

    total = lax.fori_loop(0, slots, list_slot, 0)
    o_ref[...] = jnp.zeros_like(o_ref)
    # A buffer's rows past a list's end are never copied into and are
    # masked: they must hold numbers.
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)

    def block_ids(t):
        j, h, i = work_ref[0, t], work_ref[1, t], work_ref[2, t]
        base = ((first + j) * kh + h) * n_list + i * per
        return [ids_ref[base + n] for n in range(per)]

    def copies(t):
        j, h, buf = work_ref[0, t], work_ref[1, t], lax.rem(t, nbuf)
        out = []
        for n, block_id in enumerate(block_ids(t)):
            rows = pl.ds(pl.multiple_of(block_id * block, block), block)
            for op, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                out.append(pltpu.make_async_copy(
                    hbm.at[layer, first + j, h, rows, :],
                    vmem.at[buf, pl.ds(n * block, block), :],
                    sems.at[op, buf]))
        return out

    def start(t):
        @pl.when(t < total)
        def _():
            for copy in copies(t):
                copy.start()

    for t in range(nbuf - 1):
        start(t)

    def group(t, _):
        start(t + nbuf - 1)     # into the buffer group t - 1 has left
        for copy in copies(t):
            copy.wait()
        j, h, i = work_ref[0, t], work_ref[1, t], work_ref[2, t]
        buf = lax.rem(t, nbuf)
        length, count = len_ref[first + j], count_ref[first + j]

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q, k, v = q_ref[j, h], k_buf[buf], v_buf[buf]   # [G,D], [per*blk,D]
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, per*blk]
        col = lax.broadcasted_iota(jnp.int32, (1, per * block), 1)
        # ``block`` is a power of two: shifts, no vector division.
        which = lax.shift_right_logical(col, block.bit_length() - 1)
        start_row = jnp.zeros_like(col)
        for n, block_id in enumerate(block_ids(t)):
            start_row = jnp.where(which == n, block_id * block, start_row)
        valid = ((start_row + (col & (block - 1)) < length)
                 & (i * per + which < count))
        logits = jnp.where(valid, logits, NEG_INF)
        # Block 0 is forced and listed first: the first group of a slot
        # holds row 0, which every query sees.
        m_prev = m_ref[...]                              # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(i == groups_of(j) - 1)
        def _finish():
            o_ref[j, h] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    lax.fori_loop(0, total, group, None)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sparse_decode_attention(q, k, v, ids, count, seen, *, layer, block: int,
                            interpret: Optional[bool] = None):
    """q [B,H,D]; k, v the engine's whole [L,B,KH,S,D] cache, ``layer``
    a traced int32 scalar; ids [B,KH,N], count [B] (`select_blocks`),
    seen [B] (rows of each slot that are real) -> [B,H,D]: softmax
    attention of each slot's query over the rows under ``seen`` of its
    first ``count`` listed blocks. The Pallas kernel on the TPU (or
    under ``interpret``), its ``jnp`` twin elsewhere."""
    b, h, d = q.shape
    kh, s = k.shape[2:4]
    n_list = ids.shape[-1]
    # Blocks a buffer: the most, up to `_PER`, that divide the list.
    per = max(p for p in range(1, _PER + 1) if n_list % p == 0)
    if not (jax.default_backend() == "tpu" or interpret):
        k = lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        v = lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
        return sparse_decode_attention_reference(q, k, v, ids, count, seen,
                                                 block=block)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep = h // kh
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((b, kh, rep, d), lambda g, *_: (0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[q_spec, in_hbm, in_hbm],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((_NBUF, per * block, d), k.dtype),
            pltpu.VMEM((_NBUF, per * block, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, _NBUF)),
            # (slot, head, group) of every group of blocks to read
            pltpu.SMEM((3, b * kh * (n_list // per)), jnp.int32),
            pltpu.VMEM((rep, 1), jnp.float32),   # running max
            pltpu.VMEM((rep, 1), jnp.float32),   # running denom
            pltpu.VMEM((rep, d), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, block=block, per=per,
                          n_list=n_list, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
        interpret=bool(interpret),
        name="rtpu_sparse_decode_attention",
        metadata={"kernel": "rtpu_sparse_decode_attention"},
    )(ids.reshape(-1).astype(jnp.int32), count.astype(jnp.int32),
      seen.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, kh, rep, d), k, v)
    return out.reshape(b, h, d)


# Prefill ------------------------------------------------------------------

def sparse_prefill_attention(q, k, v, kc, positions, sel: Selection, *,
                             q_tile: int = 256, kv_tile: int = 512):
    """A chunk of queries of ONE slot. q [T,H,D] at ``positions`` [T]
    (consecutive); k, v [KH,S,D] and kc [KH,NW,D]: the slot's rows and
    compressed keys of this layer, the chunk's own written -> (out
    [T,H,D], chosen [T,KH,NBLK] bool: the blocks each query attends
    over, every one where its context is dense: THE MASK THE ATTENTION
    BELOW READS, so a check that follows it judges what ran). Each query
    attends, causally, over its own blocks; tiles of rows past the
    chunk's last position are not read."""
    t, h, d = q.shape
    kh, s = k.shape[:2]
    g, nblk = h // kh, s // sel.block
    q_tile, kv_tile = min(q_tile, t), min(kv_tile, s)
    if t % q_tile or s % kv_tile or kv_tile % sel.block:
        raise ValueError(f"tiles {q_tile} x {kv_tile} do not divide a chunk "
                         f"of {t} over {s} rows of blocks of {sel.block}")
    qg = q.reshape(t, kh, g, d).transpose(1, 2, 0, 3)           # [KH,G,T,D]
    t1 = positions.astype(jnp.int32) + 1

    def select(xs):
        q_t, t1_t = xs                                  # [KH,G,qt,D], [qt]
        scores = _block_scores(q_t, kc, t1_t, sel)      # [KH,qt,NBLK]
        t1_b = jnp.broadcast_to(t1_t, scores.shape[:2])
        dense = (t1_t <= sel.dense_len)[None, :, None]
        return _best(scores, t1_b, sel) | dense

    n_q = t // q_tile
    chosen = lax.map(select, (
        qg.reshape(kh, g, n_q, q_tile, d).transpose(2, 0, 1, 3, 4),
        t1.reshape(n_q, q_tile)))
    chosen = chosen.transpose(1, 0, 2, 3).reshape(kh, t, nblk)

    scale = d ** -0.5
    per = kv_tile // sel.block
    qg = qg.astype(k.dtype)

    def tile(i, carry):
        m, l, acc = carry
        start = i * kv_tile
        k_t = lax.dynamic_slice_in_dim(k, start, kv_tile, axis=1)
        v_t = lax.dynamic_slice_in_dim(v, start, kv_tile, axis=1)
        logits = jnp.einsum("kgtd,ksd->kgts", qg, k_t,
                            preferred_element_type=F32) * scale
        rows = start + jnp.arange(kv_tile)
        mask = jnp.repeat(
            lax.dynamic_slice_in_dim(chosen, i * per, per, axis=2),
            sel.block, axis=2) & (rows[None, :] < t1[:, None])   # [KH,T,kv]
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        # Row 0 lies in block 0, which every query reads: after the
        # first tile every running maximum is a real logit.
        m_new = jnp.maximum(m, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m - m_new)
        p = jnp.where(mask[:, None], jnp.exp(logits - m_new), 0.0)
        l = l * correction + jnp.sum(p, -1, keepdims=True)
        acc = acc * correction + jnp.einsum(
            "kgts,ksd->kgtd", p.astype(v_t.dtype), v_t,
            preferred_element_type=F32)
        return m_new, l, acc

    n_tiles = lax.div(t1[-1] + (kv_tile - 1), kv_tile)
    m, l, acc = lax.fori_loop(
        0, n_tiles, tile,
        (jnp.full((kh, g, t, 1), NEG_INF, F32),
         jnp.zeros((kh, g, t, 1), F32), jnp.zeros((kh, g, t, d), F32)))
    out = (acc / l).transpose(2, 0, 1, 3).reshape(t, h, d)
    return out.astype(q.dtype), chosen.transpose(1, 0, 2)
