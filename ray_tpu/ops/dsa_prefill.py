"""Pallas TPU kernel: a CHUNK of queries over a slot's LATENT rows under a
mask over single rows (the prefill of a family whose queries choose
their rows: ``models/dots3_note.py``'s full layers), expanded MLA with
the scores kept in fast memory.

  q      [B, T, H, qk]    rotated; ``q_nope`` ++ ``q_rope``
  rows   [B, S, W]        the layer's cache rows of the slot(s), the
                          chunk's own written: ``c_kv`` (r) ++ the shared
                          rotary key ++ zeros to whole lanes
  keep   [B, T, S] bool   the rows each query attends to; none past its
                          own (the chunk's queries sit at rows
                          ``[rows_seen - T, rows_seen)``)
  w_uk   [r, H, nope]     the key half of ``kv_b_proj``
  w_uv   [r, H, v]        its value half
  out    [B, T, H, v]     float32

What it computes is `dsa_prefill_attention_reference`, rounded where
that rounds: per head ``k_nope = c_kv w_uk`` and ``v = c_kv w_uv``
(float32 sums, rounded to the rows' type), scores in float32 times
``scale`` with the rotary key shared by the heads, the mask applied to
the scores, ``p`` rounded to the rows' type for the second product,
float32 running maximum, sum and accumulator, one division at the end.
The reference writes a tile's ``[H, T, 512]`` float32 scores to HBM and
reads them back for each pass over them (537 MB a tile at 128 heads and
2,048 queries); here they live a ``[q_block, kv_tile]`` block at a time.

Grid = (B, H / group, S / kv_tile), the row tiles innermost and
sequential. A grid step holds the whole chunk's queries of ``group``
heads and their accumulator (the output block itself) and takes ONE tile
of rows: the tile is expanded once for the group's heads, then every
block of ``q_block`` queries that can see it runs its online-softmax
update, head by head. A grid over tiles of queries would expand each
tile of rows once a query tile. Tiles that begin at or past
``rows_seen`` are not read (the index map parks them on the last one
that is) and a block of queries that lies wholly before a tile is
skipped.

A head's key is laid out ``k_nope ++ (the row past c_kv)``: the rotary
key and the row's padding as they lie in the cache, against a query
``q_nope ++ q_rope ++ zeros``: one product over whole lanes, and the
padding (finite by the cache's construction: zeros) meets zeros.

Off the TPU the jnp reference runs (``interpret=True`` runs the kernel
under the Pallas interpreter, for the CPU tests), and on it where a
width or the tile of rows is not whole 128-lane tiles (the toy
geometries). Both take the slot's rows in whole tiles
(``init_kv_cache`` rounds them).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30
LANES = 128
NAME = "rtpu_dsa_prefill_attention"
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def dsa_prefill_attention_reference(q, rows, keep, w_uk, w_uv, rows_seen, *,
                                    scale: float, kv_tile: int = 512):
    """ONE slot: q [T,H,qk], rows [S,W], keep [T,S] bool (every real
    query keeps at least one row) -> [T,H,v] float32. Expanded MLA a
    tile of rows at a time, online softmax; tiles that begin at or past
    ``rows_seen`` are not read."""
    t, h = q.shape[:2]
    s = rows.shape[0]
    rank, rope = w_uk.shape[0], q.shape[-1] - w_uk.shape[-1]
    kv_tile = min(kv_tile, s)
    if s % kv_tile:
        raise ValueError(f"tiles of {kv_tile} rows do not divide {s}")

    def tile(i, carry):
        m, l, acc = carry
        start = i * kv_tile
        r_t = lax.dynamic_slice_in_dim(rows, start, kv_tile, 0)
        c_kv = r_t[:, :rank]
        k_nope = jnp.einsum("sr,rhk->shk", c_kv, w_uk)
        v_t = jnp.einsum("sr,rhv->shv", c_kv, w_uv)
        k_rope = jnp.broadcast_to(r_t[:, None, rank:rank + rope],
                                  k_nope.shape[:2] + (rope,))
        k_t = jnp.concatenate([k_nope, k_rope], axis=-1)
        logits = jnp.einsum("thk,shk->hts", q, k_t,
                            preferred_element_type=F32) * scale
        mask = lax.dynamic_slice_in_dim(keep, start, kv_tile, axis=1)[None]
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        l = l * correction + jnp.sum(p, -1, keepdims=True)
        acc = acc * correction + jnp.einsum(
            "hts,shv->htv", p.astype(v_t.dtype), v_t,
            preferred_element_type=F32)
        return m_new, l, acc

    n_tiles = jnp.minimum(
        lax.div(jnp.asarray(rows_seen, jnp.int32) + (kv_tile - 1), kv_tile),
        s // kv_tile)
    m, l, acc = lax.fori_loop(
        0, n_tiles, tile,
        (jnp.full((h, t, 1), NEG_INF, F32), jnp.zeros((h, t, 1), F32),
         jnp.zeros((h, t, w_uv.shape[-1]), F32)))
    return (acc / jnp.maximum(l, 1e-30)).transpose(1, 0, 2)


def _kernel(scalars_ref, q_ref, rows_ref, keep_ref, w_uk_ref, w_uv_ref, o_ref,
            k_ref, v_ref, m_ref, l_ref, *, group: int, q_block: int,
            kv_tile: int, rank: int, nope: int, v_dim: int, scale: float):
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    rows_seen, first = scalars_ref[0], scalars_ref[1]
    n_blocks = q_ref.shape[1] // q_block
    dq = k_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    start = i * kv_tile

    @pl.when(start < rows_seen)
    def _tile():
        rows = rows_ref[0]                                  # [kv_tile, W]
        c_kv = rows[:, :rank]
        # The group's heads' keys and values of this tile, ONCE.
        k_nope = jnp.dot(c_kv, w_uk_ref[...],
                         preferred_element_type=F32).astype(rows.dtype)
        v_ref[...] = jnp.dot(c_kv, w_uv_ref[...],
                             preferred_element_type=F32).astype(rows.dtype)
        for h in range(group):
            k_ref[h, :, :nope] = k_nope[:, h * nope:(h + 1) * nope]
            k_ref[h, :, nope:] = rows[:, rank:]

        def block(j, _):
            at = pl.ds(pl.multiple_of(j * q_block, q_block), q_block)
            mask = keep_ref[0, at, :] != 0              # [q_block, kv_tile]
            for h in range(group):
                out = pl.ds(h * v_dim, v_dim)
                s = lax.dot_general(q_ref[0, at, pl.ds(h * dq, dq)], k_ref[h],
                                    _NT, preferred_element_type=F32) * scale
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h, at, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
                correction = jnp.exp(m_prev - m_new)
                # A query with no kept row so far: its maximum is still
                # the floor, and exp(floor - floor) is one, not nothing.
                p = jnp.exp(s - jnp.where(m_new > NEG_INF, m_new, 0.0))
                l_ref[h, at, :] = (l_ref[h, at, :] * correction
                                   + jnp.sum(p, -1, keepdims=True))
                o_ref[0, at, out] = o_ref[0, at, out] * correction + jnp.dot(
                    p.astype(v_ref.dtype), v_ref[:, out],
                    preferred_element_type=F32)
                m_ref[h, at, :] = m_new

        # A block of queries whose last one sits before the tile's first
        # row keeps none of it.
        lax.fori_loop(jnp.clip(lax.div(start - first, q_block), 0, n_blocks),
                      n_blocks, block, None)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        for h in range(group):
            out = pl.ds(h * v_dim, v_dim)
            o_ref[0, :, out] = o_ref[0, :, out] / jnp.maximum(l_ref[h], 1e-30)


def _tiling(t: int, s: int, heads: int):
    """(heads a grid step, queries a block, rows a tile) for a chunk of
    ``t`` queries over ``s`` rows. On a v5e at 128 heads, 2,048 queries
    and 12,288 rows written (PERF.md, PR 43): (4, 512, 512) 23.0 ms,
    (4, 256, 512) 24.9, (4, 128, 512) 31.5, (4, 256, 256) 42.6; 2 or 8
    heads a step or 1,024 queries a block within 2 % of the first. Tiles
    of 1,024 rows read 20.9: not taken, because at the reference's 512
    kernel and reference agree to the last bit of float32 (the running
    maximum meets the same rows in the same order, so every ``p`` is
    rounded from the same number), and a check reads what it read."""
    group = next(g for g in (4, 2, 1) if heads % g == 0)
    q_block = 512 if t >= 512 else -(-t // 32) * 32
    return group, q_block, min(512, s)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def dsa_prefill_attention(q, rows, keep, w_uk, w_uv, rows_seen, *,
                          scale: float, interpret: Optional[bool] = None):
    """q [B,T,H,qk], rows [B,S,W], keep [B,T,S] bool, w_uk [r,H,nope],
    w_uv [r,H,v], ``rows_seen`` a traced int32 scalar (the rows written:
    the chunk's last query sits at ``rows_seen - 1``) -> [B,T,H,v]
    float32: the Pallas kernel on the TPU (or under ``interpret``), the
    jnp reference elsewhere and where a width is not whole lanes."""
    b, t, heads, qk = q.shape
    s, width = rows.shape[1:]
    rank, nope = w_uk.shape[0], w_uk.shape[-1]
    v_dim = w_uv.shape[-1]
    group, q_block, kv_tile = _tiling(t, s, heads)
    whole_lanes = all(n % LANES == 0
                      for n in (rank, nope, v_dim, width, kv_tile))
    if s % kv_tile:
        raise ValueError(f"tiles of {kv_tile} rows do not divide {s}")
    on_tpu = jax.default_backend() == "tpu"
    if not (interpret or (on_tpu and whole_lanes)):
        return jax.vmap(
            lambda q, rows, keep: dsa_prefill_attention_reference(
                q, rows, keep, w_uk, w_uv, rows_seen, scale=scale,
                kv_tile=kv_tile))(q, rows, keep)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dq = nope + width - rank
    t_pad = -(-t // q_block) * q_block
    # q_nope ++ q_rope ++ zeros against k_nope ++ (the row past c_kv);
    # queries past T keep no row and come out 0.
    q = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, dq - qk))).reshape(
        b, t_pad, heads * dq)
    keep = jnp.pad(keep.astype(jnp.int8), ((0, 0), (0, t_pad - t), (0, 0)))
    rows_seen = jnp.asarray(rows_seen, jnp.int32)

    def tile(i, scalars):
        # Tiles past the rows written park on the last one that is.
        return jnp.minimum(
            i, jnp.maximum(lax.div(scalars[0] + kv_tile - 1, kv_tile) - 1, 0))

    heads_of = lambda bi, g, i, scalars: (bi, 0, g)     # queries, output
    weights_of = lambda bi, g, i, scalars: (0, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, heads // group, s // kv_tile),
        in_specs=[
            pl.BlockSpec((1, t_pad, group * dq), heads_of),
            pl.BlockSpec((1, kv_tile, width),
                         lambda bi, g, i, sc: (bi, tile(i, sc), 0)),
            pl.BlockSpec((1, t_pad, kv_tile),
                         lambda bi, g, i, sc: (bi, 0, tile(i, sc))),
            pl.BlockSpec((rank, group * nope), weights_of),
            pl.BlockSpec((rank, group * v_dim), weights_of),
        ],
        out_specs=pl.BlockSpec((1, t_pad, group * v_dim), heads_of),
        scratch_shapes=[
            pltpu.VMEM((group, kv_tile, dq), rows.dtype),      # keys
            pltpu.VMEM((kv_tile, group * v_dim), rows.dtype),  # values
            pltpu.VMEM((group, t_pad, 1), F32),                # running max
            pltpu.VMEM((group, t_pad, 1), F32),                # running sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, group=group, q_block=q_block,
                          kv_tile=kv_tile, rank=rank, nope=nope, v_dim=v_dim,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t_pad, heads * v_dim), F32),
        # At 4 heads, 2,048 queries and tiles of 512 rows: queries and
        # output 8 MB each (two buffers), the statistics 8, mask, rows
        # and weights 6, keys, values and a block's scores 7.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=96 * 2 ** 20),
        interpret=bool(interpret),
        name=NAME,
        metadata={"kernel": NAME},
    )(jnp.stack([rows_seen, rows_seen - t]), q, rows, keep,
      w_uk.reshape(rank, heads * nope), w_uv.reshape(rank, heads * v_dim))
    return out[:, :t].reshape(b, t, heads, v_dim)
