"""Pallas TPU kernel: a CHUNK of queries over the last ``reach`` rows
before each and its own (the prefill of a family's window layers:
``models/dots3_note.py``'s sliding layers), the scores kept in fast
memory.

  q      [B, T, H, qk]        rotated; ``q_nope`` ++ ``q_rope``
  k      [B, H, reach+T, qk]  the ``reach`` rows before the chunk ++ the
                              chunk's own, expanded to per-head keys
                              ONCE, outside, head-major
  v      [B, H, reach+T, v]   their values
  q_pos  [B, T] int32         the queries' positions
  k_pos  [B, reach+T] int32   the rows' positions; `NO_ROW` where a row
                              holds none (its caller decides: a ring row
                              its slot's owner never wrote)
  out    [B, T, H, v] float32 (written head-major, [B, H, T, v], and
         handed back transposed: the product with ``W_o`` that follows
         reads it where it lies), and ``rows`` / ``first`` [B, T] int32:
         how many rows each query attended to and the lowest position
         among them, counted from the mask the attention RAN UNDER

What it computes is `swa_prefill_attention_reference`, rounded where
that rounds: float32 scores times ``scale``, the mask on the scores
(``k_pos <= q_pos`` and ``k_pos >= q_pos - reach``; `NO_ROW` lies past
every query), ``p`` normalised in float32 and rounded to the rows' type
for the second product, float32 sums. The reference writes a chunk's
``[T / 512, H, 512, reach + 512]`` float32 scores to HBM and reads them
back for each pass of the softmax (537 MB a layer at 64 heads, 2,048
queries and a reach of 512), after gathering each block's span of keys
and values into a copy; here a block's scores live and die in fast
memory and the span is read where it lies.

Grid = (B, T / reach, H), the heads innermost. A grid step holds one
block of ``reach`` queries of one head. Query ``i`` of block ``n`` sits
at row ``reach + n reach + i`` of the keys (the caller's contract:
``k_pos[:, reach:] == q_pos``, consecutive), so the block sees rows
``[n reach, n reach + 2 reach)``: the keys and the values are given
TWICE, with index maps ``n`` and ``n + 1`` over blocks of ``reach``
rows, and no span is copied. The whole span of a query fits, so the
softmax is one exact pass: max, exp, sum, one reciprocal a query.

Within a step the queries go ``q_sub`` at a time (`_q_sub`): queries
``[j q_sub, (j + 1) q_sub)`` can see nothing before row ``j q_sub`` of
the first block or from row ``(j + 1) q_sub`` of the second, so they
multiply ``reach + q_sub`` rows, not ``2 reach``. Those few sub-blocks
are a Python loop (static slices on whole tiles); heads and blocks are
the grid.

The mask is the same for every head of a block: the step of head 0
makes it from the positions, keeps it as the float32 ``0 | NEG_INF`` it
adds to the scores (a score plus -1e30 IS -1e30 in float32) in a
scratch the other heads' steps read, and writes ``rows`` and ``first``
from that same mask.

Off the TPU the jnp reference runs (``interpret=True`` runs the kernel
under the Pallas interpreter, for the CPU tests), and on it where a
width or ``reach`` is not whole 128-lane tiles or ``reach`` does not
divide the chunk (the toy geometries; a window that is not the
published one).
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
NAME = "rtpu_swa_prefill_attention"
NO_ROW = 2 ** 30        # the position of a row that holds none
_NT = (((1,), (1,)), ((), ()))      # a @ b.T

_QUERIES = 512      # queries a block of the reference, where they divide


def _mask(q_pos, k_pos, reach: int):
    """q_pos [.., t, 1], k_pos [.., 1, s] -> [.., t, s] bool."""
    return (k_pos <= q_pos) & (k_pos >= q_pos - reach)


def _read(mask, k_pos):
    """(rows attended to, the lowest position among them), [.., t, 1]."""
    return (jnp.sum(mask.astype(jnp.int32), -1, keepdims=True),
            jnp.min(jnp.where(mask, k_pos, NO_ROW), -1, keepdims=True))


def swa_prefill_attention_reference(q, k, v, q_pos, k_pos, *, reach: int,
                                    scale: float):
    """ONE slot: q [T,H,qk], k [H,reach+T,qk], v [H,reach+T,v], q_pos
    [T], k_pos [reach+T] -> (out [T,H,v] float32, rows [T], first [T]).
    A block of `_QUERIES` queries (the chunk, where they do not divide
    it) reads its own rows and the ``reach`` before its first: the
    scores are [blocks, H, qb, reach + qb], not the chunk's square."""
    t = q.shape[0]
    qb = _QUERIES if t % _QUERIES == 0 else t
    n = t // qb
    take = (jnp.arange(n) * qb)[:, None] + jnp.arange(reach + qb)[None, :]
    k_b, v_b, kp_b = k[:, take], v[:, take], k_pos[take]     # [H,n,span,..]
    q_b, qp_b = q.reshape(n, qb, *q.shape[1:]), q_pos.reshape(n, qb)
    logits = jnp.einsum("nthk,hnsk->nhts", q_b, k_b,
                        preferred_element_type=F32) * scale
    mask = _mask(qp_b[:, :, None], kp_b[:, None, :], reach)      # [n,qb,span]
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("nhts,hnsv->nthv", p.astype(v_b.dtype), v_b,
                     preferred_element_type=F32)
    rows, first = _read(mask, kp_b[:, None, :])
    return (out.reshape(t, *out.shape[2:]), rows.reshape(t),
            first.reshape(t))


def _q_sub(reach: int) -> int:
    """Queries a sub-block of a grid step's ``reach``. On a v5e at 64
    heads, 2,048 queries and a reach of 512, 8 slots a call (PERF.md,
    PR 60), ms a slot: 256 queries (a span of 768 rows) 1.07, 512 (the
    whole 1,024) 1.11, 128 (640) 1.15: less is multiplied as the
    sub-block shrinks, and fewer query rows pass each latched tile of
    keys."""
    return next(s for s in (256, reach) if reach % s == 0)


def _kernel(q_ref, k_lo_ref, k_hi_ref, v_lo_ref, v_hi_ref, qp_ref, kp_lo_ref,
            kp_hi_ref, o_ref, rows_ref, first_ref, bias_ref, *, reach: int,
            q_sub: int, scale: float):
    import jax.experimental.pallas as pl

    # Sub-block j: its queries, its rows of the first block, its rows of
    # the second, and where the first block's end in its scores.
    subs = [(slice(j * q_sub, (j + 1) * q_sub), slice(j * q_sub, reach),
             slice(0, (j + 1) * q_sub), reach - j * q_sub)
            for j in range(reach // q_sub)]

    @pl.when(pl.program_id(2) == 0)
    def _the_blocks_mask():
        for j, (mine, lo, hi, cut) in enumerate(subs):
            q_pos = qp_ref[mine, :]                              # [q_sub, 1]
            kp_lo, kp_hi = kp_lo_ref[:, lo], kp_hi_ref[:, hi]    # [1, ..]
            m_lo = _mask(q_pos, kp_lo, reach)
            m_hi = _mask(q_pos, kp_hi, reach)
            bias_ref[j, :, :cut] = jnp.where(m_lo, 0.0, NEG_INF)
            bias_ref[j, :, cut:] = jnp.where(m_hi, 0.0, NEG_INF)
            (n_lo, first_lo), (n_hi, first_hi) = (_read(m_lo, kp_lo),
                                                  _read(m_hi, kp_hi))
            rows_ref[mine, :] = n_lo + n_hi
            first_ref[mine, :] = jnp.minimum(first_lo, first_hi)

    for j, (mine, lo, hi, cut) in enumerate(subs):
        q = q_ref[mine, :]
        s_lo = lax.dot_general(q, k_lo_ref[lo, :], _NT,
                               preferred_element_type=F32)
        s_hi = lax.dot_general(q, k_hi_ref[hi, :], _NT,
                               preferred_element_type=F32)
        s_lo = s_lo * scale + bias_ref[j, :, :cut]
        s_hi = s_hi * scale + bias_ref[j, :, cut:]
        m = jnp.maximum(jnp.max(s_lo, -1, keepdims=True),
                        jnp.max(s_hi, -1, keepdims=True))
        e_lo, e_hi = jnp.exp(s_lo - m), jnp.exp(s_hi - m)
        # A query reads its own row at least: the sum is 1 or more.
        inv = 1.0 / (jnp.sum(e_lo, -1, keepdims=True)
                     + jnp.sum(e_hi, -1, keepdims=True))
        o_ref[mine, :] = (
            jnp.dot((e_lo * inv).astype(v_lo_ref.dtype), v_lo_ref[lo, :],
                    preferred_element_type=F32)
            + jnp.dot((e_hi * inv).astype(v_hi_ref.dtype), v_hi_ref[hi, :],
                      preferred_element_type=F32))


def takes(t: int, reach: int, qk: int, v_dim: int) -> bool:
    """Whether the kernel takes a chunk of ``t`` queries at this reach
    and these widths: whole tiles, whole blocks."""
    return (reach > 0 and t % reach == 0
            and all(n % LANES == 0 for n in (reach, qk, v_dim)))


@functools.partial(jax.jit, static_argnames=("reach", "scale", "interpret"))
def swa_prefill_attention(q, k, v, q_pos, k_pos, *, reach: int, scale: float,
                          interpret: Optional[bool] = None):
    """q [B,T,H,qk], k [B,H,reach+T,qk], v [B,H,reach+T,v], q_pos [B,T],
    k_pos [B,reach+T] (``k_pos[:, reach:] == q_pos``, consecutive;
    `NO_ROW` where a row holds none) -> (out [B,T,H,v] float32, rows
    [B,T], first [B,T]): the Pallas kernel on the TPU (or under
    ``interpret``) where it `takes` the shapes, the jnp reference
    elsewhere."""
    b, t, heads, qk = q.shape
    v_dim = v.shape[-1]
    on_tpu = jax.default_backend() == "tpu"
    if not ((interpret or on_tpu) and takes(t, reach, qk, v_dim)):
        return jax.vmap(functools.partial(
            swa_prefill_attention_reference, reach=reach, scale=scale))(
                q, k, v, q_pos, k_pos)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q_sub = _q_sub(reach)
    queries = lambda bi, n, h: (bi, n, h)           # of [B, T, H * width]
    column = lambda bi, n, h: (bi, n, 0)            # of [B, T, 1]
    lo_rows = lambda bi, n, h: (bi, h, n, 0)        # of [B, H, reach+T, ..]
    hi_rows = lambda bi, n, h: (bi, h, n + 1, 0)
    rows_of = lambda width, at: pl.BlockSpec((None, None, reach, width), at)
    a_column = pl.BlockSpec((None, reach, 1), column)
    out, rows, first = pl.pallas_call(
        functools.partial(_kernel, reach=reach, q_sub=q_sub, scale=scale),
        grid=(b, t // reach, heads),
        in_specs=[
            pl.BlockSpec((None, reach, qk), queries),
            rows_of(qk, lo_rows), rows_of(qk, hi_rows),
            rows_of(v_dim, lo_rows), rows_of(v_dim, hi_rows),
            a_column,
            pl.BlockSpec((None, 1, reach), lambda bi, n, h: (bi, 0, n)),
            pl.BlockSpec((None, 1, reach), lambda bi, n, h: (bi, 0, n + 1)),
        ],
        out_specs=[rows_of(v_dim, lo_rows), a_column, a_column],
        out_shape=[jax.ShapeDtypeStruct((b, heads, t, v_dim), F32),
                   jax.ShapeDtypeStruct((b, t, 1), jnp.int32),
                   jax.ShapeDtypeStruct((b, t, 1), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((reach // q_sub, q_sub, reach + q_sub), F32)],
        # At a reach of 512, 256 + 128 columns: queries, two blocks of
        # keys and of values and the output, two buffers each, 2.5 MB;
        # the mask 1.25; a sub-block's scores under 1.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=bool(interpret),
        name=NAME,
        metadata={"kernel": NAME},
    )(q.reshape(b, t, heads * qk), k, k, v, v,
      q_pos.astype(jnp.int32)[:, :, None],
      *(k_pos.astype(jnp.int32)[:, None, :],) * 2)
    # Head-major out of the kernel (whole tiles, one stretch of HBM a
    # step), as the product with W_o that follows wants it laid out.
    return out.transpose(0, 2, 1, 3), rows[..., 0], first[..., 0]
