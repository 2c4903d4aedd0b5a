"""Pallas TPU kernel: paged single-token decode attention.

PagedAttention-style (Kwon et al. 2023) counterpart to
``decode_attention.py``: instead of attending over one contiguous
``[B, KH, S, D]`` cache row per sequence, the kernel reads a
block-granular KV cache IN PLACE through a **block table** — sequence
``b``'s logical page ``p`` lives wherever ``block_table[b, p]`` says,
anywhere in the cache pool. No gather, no copy: the table drives the
kernel's BlockSpec index map, so each page is DMA'd straight from its
resident location, and pages past ``ceil(length/page)`` are never
streamed (the index map parks them on the last valid page, which Pallas'
revisited-block elision turns into zero extra traffic).

Page-id convention: the pool is the engine's own cache array
``[B_pool, KH, S, D]`` viewed as ``B_pool * S/page`` pages in row-major
(pool row, then page-within-row) order — page ``t`` is rows
``[(t % np_row) * page, ...)`` of pool row ``t // np_row``. The serving
engine's table is slot-identity today (``kv_manager`` keeps prefixes
slot-affine), which makes the paged read bit-equal to the contiguous
one; the table indirection is the seam that lets future cross-slot
paging / disaggregated-prefill KV shipping land without touching the
kernel.

Falls back to a pure-jnp gather reference off-TPU (and checks the
kernel against it exactly under ``interpret=True`` — the
``decode_attention.py``/``fused.py`` test idiom).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_decode_attention_reference(q, k, v, block_table, lengths,
                                     page_size: int):
    """Pure-jnp reference: q [B,H,D], k/v [Bp,KH,S,D] page pools,
    block_table [B,NP] int32 flat page ids, lengths [B] -> [B,H,D].

    Gathers the table's pages into a contiguous per-sequence cache and
    runs the masked-softmax reference — the exact computation the
    in-place kernel must reproduce (and exactly what the kernel
    replaces: this gather is the HBM round trip the paged read avoids).
    """
    b, h, d = q.shape
    bp, kh, s, _ = k.shape
    np_row = s // page_size
    n_pages = block_table.shape[1]
    # Page t = rows [(t % np_row) * page, ...) of pool row t // np_row:
    # split S into pages FIRST, then flatten (pool row, page-in-row).
    kp = jnp.moveaxis(k.reshape(bp, kh, np_row, page_size, d),
                      2, 1).reshape(bp * np_row, kh, page_size, d)
    vp = jnp.moveaxis(v.reshape(bp, kh, np_row, page_size, d),
                      2, 1).reshape(bp * np_row, kh, page_size, d)
    # [B, NP, KH, page, D] -> [B, KH, NP*page, D]
    kk = jnp.moveaxis(kp[block_table], 2, 1).reshape(
        b, kh, n_pages * page_size, d)
    vv = jnp.moveaxis(vp[block_table], 2, 1).reshape(
        b, kh, n_pages * page_size, d)
    rep = h // kh
    qg = q.reshape(b, kh, rep, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, kk,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    mask = (jnp.arange(n_pages * page_size)[None, :]
            < lengths[:, None])  # [B, NP*page]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # A zero-length slot's row is fully masked: uniform softmax over
    # NEG_INF would attend to garbage — zero it like the kernel does.
    probs = jnp.where(mask[:, None, None, :], probs, 0.0)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(vv.dtype), vv)
    return out.reshape(b, h, d)


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, scale: float):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # Pages at or past ceil(length/page) were remapped by the index map
    # onto the last valid page (no fresh DMA); skip their compute too.
    @pl.when(p * page_size < length)
    def _accumulate():
        q = q_ref[0, 0]                          # [G, D]
        k = k_ref[0, 0]                          # [page, D]
        v = v_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, page] f32
        positions = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(positions < length, logits, NEG_INF)
        m_prev = m_ref[...]                      # [G, 1] carried max
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)          # [G, page] f32
        probs = jnp.where(m_new == NEG_INF, 0.0, probs)
        l_ref[...] = (l_ref[...] * correction
                      + jnp.sum(probs, -1, keepdims=True))
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(p == n_p - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret"))
def paged_decode_attention(q, k, v, block_table, lengths, *,
                           page_size: int,
                           interpret: Optional[bool] = None):
    """q [B,H,D], k/v [Bp,KH,S,D] page pools (S a multiple of
    ``page_size``), block_table [B,NP] int32 flat page ids, lengths [B]
    int32 -> [B,H,D]. Pallas kernel on TPU (or under ``interpret``);
    pure-jnp gather reference elsewhere."""
    bp, kh, s, d = k.shape
    if s % page_size:
        raise ValueError(f"cache rows {s} not a multiple of the "
                         f"{page_size}-row page (pad the allocation)")
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = False
    if not on_tpu and not interpret:
        return paged_decode_attention_reference(q, k, v, block_table,
                                                lengths, page_size)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _ = q.shape
    np_row = s // page_size
    n_pages = block_table.shape[1]
    rep = h // kh
    qg = q.reshape(b, kh, rep, d)

    def _kv_index(bi, ki, pi, table, lens):
        """Physical block of logical page ``pi`` of sequence ``bi`` —
        pages past ceil(length/page) park on the last valid one, so the
        revisited block needs no fresh copy."""
        valid = jax.lax.div(lens[bi] + page_size - 1, page_size)
        p_eff = jnp.minimum(pi, jnp.maximum(valid - 1, 0))
        t = table[bi, p_eff]
        return jax.lax.div(t, np_row), ki, jax.lax.rem(t, np_row), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda bi, ki, pi, table, lens: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d), _kv_index),
            pl.BlockSpec((1, 1, page_size, d), _kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rep, d),
            lambda bi, ki, pi, table, lens: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),   # running max
            pltpu.VMEM((rep, 1), jnp.float32),   # running denom
            pltpu.VMEM((rep, d), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
        interpret=interpret,
        name="rtpu_paged_decode_attention",
        metadata={"kernel": "rtpu_paged_decode_attention"},
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k, v)
    return out.reshape(b, h, d)
