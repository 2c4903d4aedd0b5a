"""Causal flash attention on the TPU: forward, dkv and dq Pallas kernels
that skip what the causal mask removes at the INNER tile.

The kernels keep the tiling of
``jax.experimental.pallas.ops.tpu.flash_attention`` and its
`BlockSizes`: a grid over major blocks, ``block_q`` x ``block_k`` tiles
unrolled inside one, float32 softmax statistics kept 128 lanes wide.
The library decides by the MAJOR block what the causal mask removes, so
its large blocks compute 3 quarters of the square where the causal half
needs 2, and its small ones pay a grid step and a pass over the running
statistics each. Here every tile is placed against the diagonal by
itself (`_causal_regions`): one wholly above it is not computed, one
wholly below it is computed without a mask, and the major blocks stay
large (K and V of a head resident, few grid steps).

The forward leaves ONE statistic a row, ``m + log l``, as a
``[B, H, 1, S]`` float32 row (4 bytes a query: it is what a train step
KEEPS a layer beside ``o``, `FLASH_SAVED`), and the backward kernels
take it as it is and turn their block of it into a column in fast
memory, as they make ``di`` there: nothing is sliced or broadcast in
HBM between the calls (the library's dq wrapper writes ``di`` out
``block_k_major`` lanes wide: 2.1 GB a call at the train cell's
shapes; 128 lanes of the statistic were 268 MB). Products take the
operands' dtype and accumulate in float32; the softmax is float32
throughout.

Operands are [batch, heads, seq, head_dim]; `ops/attention.py` owns the
layout, the block choice and the dispatch. The trace names are
``flash_attention`` (forward: `benchmark/metrics/flash_roofline.py`
reads it), ``flash_mha_bwd_dkv`` and ``flash_mha_bwd_dq``.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import FLASH_SAVED

LANES = 128
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _as_row(x):
    """A [rows, 128] lane-replicated statistic as a [1, rows] row."""
    return x.T[:1]


def _as_column(x):
    """A [1, rows] row as a [rows, 128] lane-replicated statistic."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T


def _lanes(x, n: int):
    """A [rows, 128] lane-replicated statistic at ``n`` columns."""
    if n < LANES:
        return x[:, :n]
    return x if n == LANES else jnp.tile(x, (1, n // LANES))


def _causal_regions(d, seq: int, rows: int, cols: int, tiles, load_rows,
                    tile) -> None:
    """The tiles of one ``rows`` x ``cols`` major block of a causal
    product, whose first row lies ``d`` past its first column.

    ``d`` is traced (it comes from the grid's indices) but takes few
    values, so each value that puts the diagonal inside the block gets a
    region of its own, entered where ``d`` equals it, in which every
    decision is static: ``tile(q_start, k_start, offset, rows)`` is
    emitted for the tiles (``(q_start, block_q, k_start, block_k)``)
    that hold an unmasked entry, in a straight line, with ``offset`` =
    the tile's first row less its first column where the diagonal
    crosses it and None where it lies below it, and ``rows`` what
    ``load_rows(q_start)`` gave, once a region. One more region serves
    every block wholly below the diagonal. A straight line is what lets
    the compiler's scheduler start a tile's products under the softmax
    of the tile before it; a region a tile cannot.
    """
    step = math.gcd(rows, cols)

    def region(dd: int):
        def emit():
            loaded = {}
            for q_start, bq, k_start, bk in tiles:
                if k_start - q_start - (bq - 1) <= dd:
                    if q_start not in loaded:
                        loaded[q_start] = load_rows(q_start)
                    crossed = dd < k_start + (bk - 1) - q_start
                    tile(q_start, k_start,
                         dd + q_start - k_start if crossed else None,
                         loaded[q_start])
        return emit

    first = max(step - rows, cols - seq)
    for dd in range(first, min(cols, seq - rows + 1), step):
        pl.when(d == dd)(region(dd))
    if seq - rows >= cols:
        pl.when(d >= cols)(region(cols))


def _split_scale(scale: float):
    """(on q, on the scores): a power of two (head sizes 64 and 256)
    goes onto q once a block, where it rounds nothing in bf16, instead
    of onto every tile of scores; ``((q a) k^T) b`` is ``(q k^T) scale``
    to the bit either way."""
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _scaled(q, q_scale: float):
    return q if q_scale == 1.0 else q * q_scale


def _scores(q, k, scale: float, offset):
    """``q k^T * scale``; entries more than ``offset`` columns ahead of
    their row are masked (``offset`` None: none is)."""
    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if offset is not None:
        ahead = (lax.broadcasted_iota(jnp.int32, s.shape, 1)
                 - lax.broadcasted_iota(jnp.int32, s.shape, 0))
        s = jnp.where(ahead <= offset, s, MASK_VALUE)
    return s


def _row_tiles(bq: int, bkm: int, block_k: int):
    """The tiles of a major block that is one tile high."""
    return [(0, bq, start, block_k) for start in range(0, bkm, block_k)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                block_k: int, seq: int):
    *lse_ref, m_sc, l_sc, acc_sc = rest
    bq, d = q_ref.shape[2:]
    bkm = k_ref.shape[2]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _start():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    q_scale, s_scale = _split_scale(scale)

    def tile(_, start, offset, q):
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        s = _scores(q, k, s_scale, offset)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _lanes(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1)[:, None]
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * _lanes(alpha, d) + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _causal_regions(
        qi * bq - ki * bkm, seq, bq, bkm, _row_tiles(bq, bkm, block_k),
        lambda _: _scaled(q_ref[0, 0], q_scale), tile)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _store():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / _lanes(l, d)).astype(o_ref.dtype)
        if lse_ref:
            lse_ref[0][0, 0] = _as_row(m_sc[...] + jnp.log(l))


def _backward_rows(q_ref, lse_ref, o_ref, do_ref, rows, q_scale: float):
    """(q, do, di, lse) of a block of rows; ``di`` = sum(o * do) a row,
    the softmax gradient's correction, as a column (it needs no pass of
    its own over o and do in HBM, nor 128 lanes of it written out), and
    the forward's statistic turned from its row into 128 lanes."""
    o, do = o_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=1,
                 keepdims=True)
    return (_scaled(q_ref[0, 0, rows, :], q_scale), do, di,
            _as_column(lse_ref[0, 0, :, rows]))


def _dq_kernel(q_ref, k_ref, v_ref, lse_ref, o_ref, do_ref, dq_ref, dq_sc, *,
               scale: float, block_k: int, seq: int):
    bq = q_ref.shape[2]
    bkm = k_ref.shape[2]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _start():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    q_scale, s_scale = _split_scale(scale)

    def tile(_, start, offset, rows):
        q, do, di, lse = rows
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        s = _scores(q, k, s_scale, offset)
        p = jnp.exp(s - _lanes(lse, block_k))
        dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = (dp - di) * p
        dq_sc[...] += lax.dot(ds.astype(k.dtype), k,
                              preferred_element_type=jnp.float32)

    _causal_regions(
        qi * bq - ki * bkm, seq, bq, bkm, _row_tiles(bq, bkm, block_k),
        lambda _: _backward_rows(q_ref, lse_ref, o_ref, do_ref, slice(None),
                                 q_scale), tile)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _store():
        dq_ref[0, 0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, lse_ref, o_ref, do_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale: float, block_q: int,
                block_k: int, seq: int):
    bqm = q_ref.shape[2]
    bkm = k_ref.shape[2]
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _start():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    # dk = (ds^T q) * scale: what q carries of the scale, dk has.
    q_scale, s_scale = _split_scale(scale)

    def tile(q_start, k_start, offset, rows):
        q, do, di, lse = rows
        cols = pl.ds(k_start, block_k)
        k, v = k_ref[0, 0, cols, :], v_ref[0, 0, cols, :]
        s = _scores(q, k, s_scale, offset)
        p = jnp.exp(s - _lanes(lse, block_k))
        dv_sc[cols, :] += lax.dot(p.T.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = (dp - di) * p
        dk_sc[cols, :] += lax.dot(ds.T.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)

    _causal_regions(
        qi * bqm - ki * bkm, seq, bqm, bkm,
        [(q_start, block_q, k_start, block_k)
         for q_start in range(0, bqm, block_q)
         for k_start in range(0, bkm, block_k)],
        lambda q_start: _backward_rows(
            q_ref, lse_ref, o_ref, do_ref, pl.ds(q_start, block_q), q_scale),
        tile)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _store():
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)
        dk_ref[0, 0] = (dk_sc[...] * s_scale).astype(dk_ref.dtype)


def _check(seq: int, **blocks: int) -> None:
    for name, block in blocks.items():
        if seq % block or block % LANES:
            raise ValueError(f"{name}={block} must be a multiple of {LANES} "
                             f"that divides the sequence ({seq})")


def _row_specs(bq: int, bkm: int, d: int):
    """Block specs of the kernels whose grid is (batch, head, block of
    rows, major block of columns): rows (q, o, do, dq), columns (k, v)
    and a row statistic."""
    def kv_map(bi, hi, qi, ki):
        # A block past the diagonal is not read: the row's next one is.
        return bi, hi, lax.select(ki * bkm <= qi * bq + (bq - 1), ki, 0), 0

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def stat_map(bi, hi, qi, ki):
        return bi, hi, 0, qi

    return (pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bkm, d), kv_map),
            pl.BlockSpec((1, 1, 1, bq), stat_map))


def _call(kernel, scope, grid, in_specs, out_specs, out_shape, scratch,
          flops, operands, interpret):
    byts = sum(x.size * x.dtype.itemsize
               for x in (*operands, *jax.tree.leaves(out_shape)))
    # `scope` names the compiled instruction, and so the device trace's
    # event, of a backward call; the forward's comes from the jitted
    # `flash_attention` around it, as the library's does.
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_SEMANTICS),
            cost_estimate=pl.CostEstimate(
                flops=int(flops), bytes_accessed=int(byts),
                transcendentals=int(flops // (4 * operands[0].shape[-1]))),
            interpret=interpret,
        )(*operands)


def _forward(q, k, v, scale, blocks, interpret, residuals: bool):
    b, h, s, d = q.shape
    bq, bkm, bk = blocks.block_q, blocks.block_k_major, blocks.block_k
    _check(s, block_q=bq, block_k_major=bkm, block_k=bk)
    q_spec, kv_spec, lm_spec = _row_specs(bq, bkm, d)
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if residuals:
        out_specs.append(lm_spec)
        out_shape.append(jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32))
    out = _call(
        functools.partial(_fwd_kernel, scale=scale, block_k=bk, seq=s),
        None, (b, h, s // bq, s // bkm),
        [q_spec, kv_spec, kv_spec], out_specs, out_shape,
        [pltpu.VMEM((bq, LANES), jnp.float32),
         pltpu.VMEM((bq, LANES), jnp.float32),
         pltpu.VMEM((bq, d), jnp.float32)],
        2 * b * h * s * s * d, (q, k, v), interpret)
    return out if residuals else out[0]


def _backward_dq(q, k, v, lse, o, do, scale, blocks, interpret):
    b, h, s, d = q.shape
    bq, bkm, bk = (blocks.block_q_dq, blocks.block_k_major_dq,
                   blocks.block_k_dq)
    _check(s, block_q_dq=bq, block_k_major_dq=bkm, block_k_dq=bk)
    q_spec, kv_spec, lm_spec = _row_specs(bq, bkm, d)
    return _call(
        functools.partial(_dq_kernel, scale=scale, block_k=bk, seq=s),
        "flash_mha_bwd_dq", (b, h, s // bq, s // bkm),
        [q_spec, kv_spec, kv_spec, lm_spec, q_spec, q_spec],
        q_spec, jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((bq, d), jnp.float32)],
        3 * b * h * s * s * d, (q, k, v, lse, o, do), interpret)


def _backward_dkv(q, k, v, lse, o, do, scale, blocks, interpret):
    b, h, s, d = q.shape
    bqm, bq = blocks.block_q_major_dkv, blocks.block_q_dkv
    bkm, bk = blocks.block_k_major_dkv, blocks.block_k_dkv
    _check(s, block_q_major_dkv=bqm, block_q_dkv=bq, block_k_major_dkv=bkm,
           block_k_dkv=bk)

    def q_map(bi, hi, ki, qi):
        # The rows before a block of columns see none of it: the first
        # block of rows that does is read once and waited on.
        return bi, hi, jnp.maximum(qi, (ki * bkm) // bqm), 0

    def stat_map(bi, hi, ki, qi):
        return bi, hi, 0, q_map(bi, hi, ki, qi)[2]

    def kv_map(bi, hi, ki, qi):
        return bi, hi, ki, 0

    q_spec = pl.BlockSpec((1, 1, bqm, d), q_map)
    kv_spec = pl.BlockSpec((1, 1, bkm, d), kv_map)
    lm_spec = pl.BlockSpec((1, 1, 1, bqm), stat_map)
    dkv = jax.ShapeDtypeStruct(k.shape, k.dtype)
    return _call(
        functools.partial(_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                          seq=s),
        "flash_mha_bwd_dkv", (b, h, s // bkm, s // bqm),
        [q_spec, kv_spec, kv_spec, lm_spec, q_spec, q_spec],
        [kv_spec, kv_spec], [dkv, dkv],
        [pltpu.VMEM((bkm, d), jnp.float32),
         pltpu.VMEM((bkm, d), jnp.float32)],
        4 * b * h * s * s * d, (q, k, v, lse, o, do), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_mha(q, k, v, scale, blocks, interpret):
    return _forward(q, k, v, scale, blocks, interpret, residuals=False)


def _flash_mha_fwd(q, k, v, scale, blocks, interpret):
    o, lse = _forward(q, k, v, scale, blocks, interpret, residuals=True)
    # The output is named with a row's heads side by side, [B, S, H·D]
    # (the layout the caller wants it in anyway): kept as the kernel's
    # [B, H, S, D] it would take twice its bytes at head size 64, the
    # chip's tiles being 128 lanes wide. The residuals ARE the named
    # values, so a policy that keeps them feeds the backward kernels
    # from what it kept; with no policy the transposes cancel.
    b, h, s, d = o.shape
    out_name, lse_name = FLASH_SAVED
    kept = checkpoint_name(o.transpose(0, 2, 1, 3).reshape(b, s, h * d),
                           out_name)
    lse = checkpoint_name(lse, lse_name)
    o = kept.reshape(b, s, h, d).transpose(0, 2, 1, 3)
    return o, (q, k, v, o, lse)


def _flash_mha_bwd(scale, blocks, interpret, residuals, do):
    q, k, v, o, lse = residuals
    dk, dv = _backward_dkv(q, k, v, lse, o, do, scale, blocks, interpret)
    dq = _backward_dq(q, k, v, lse, o, do, scale, blocks, interpret)
    return dq, dk, dv


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def flash_attention(q, k, v, scale: float, blocks, interpret: bool = False):
    """softmax(q k^T * scale, causal) v for [B, H, S, D] operands of one
    sequence length; ``blocks`` is the library's `BlockSizes`. (The
    function's name is the forward kernel's in a device trace.)"""
    return _flash_mha(q, k, v, scale, blocks, interpret)

