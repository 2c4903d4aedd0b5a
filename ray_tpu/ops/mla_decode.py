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

Grid = (B, S / block_s), the row blocks innermost and sequential with
the online-softmax carry in VMEM scratch. Each valid row is read ONCE:
the block is loaded once and serves both the score product (all Dk
columns) and the value product (its first v_dim columns, a lane-aligned
slice). Blocks past ``lengths[b]`` are not read: the index map parks
them on the slot's last valid block (no fresh copy) and their compute
is skipped.

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


def _mla_kernel(len_ref, layer_ref, q_ref, kv_ref, *rest, block_s: int,
                v_dim: int, scale: float, kept: bool):
    import jax.experimental.pallas as pl

    keep_ref = rest[0] if kept else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]

    b = pl.program_id(0)
    s_idx = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(s_idx * block_s < length)
    def _accumulate():
        q = q_ref[0]                                 # [H, Dk]
        rows = kv_ref[0, 0]                          # [block_s, Dk]
        logits = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, block_s]
        positions = s_idx * block_s + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        valid = positions < length
        if kept:
            valid = valid & (keep_ref[0] > 0)        # [1, block_s]
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        if kept:
            # A block may hold no kept row: its running maximum is still
            # the floor, and exp(floor - floor) is one, not nothing.
            p = jnp.where(valid, p, 0.0)
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        # A slot of length 0 accumulated nothing: 0 / eps = 0.
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "block_s",
                                             "interpret", "name"))
def mla_decode_attention(q, cache, lengths, *, layer, v_dim: int,
                         scale: float, block_s: int = 512,
                         interpret: Optional[bool] = None, keep=None,
                         name: str = "rtpu_mla_decode_attention"):
    """q [B,H,Dk], cache [L,B,S,Dk], lengths [B] int32, ``layer`` a
    traced int32 scalar, ``keep`` [B,S] (optional; > 0: the row is
    attended to) -> [B,H,v_dim]: the Pallas kernel on the TPU (or under
    ``interpret``), the jnp reference elsewhere and where ``block_s``
    does not divide the cache's rows."""
    on_tpu = jax.default_backend() == "tpu"
    n_layers, b, s, dk = cache.shape
    block_s = min(block_s, s)
    if not ((on_tpu or interpret) and s % block_s == 0):
        kv = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
        return mla_decode_attention_reference(q, kv, lengths, v_dim=v_dim,
                                              scale=scale, keep=keep)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h = q.shape[1]

    def _kv_index(bi, si, lens, layer):
        # Blocks past the slot's length park on its last valid one.
        last = jnp.maximum(jax.lax.div(lens[bi] + block_s - 1, block_s) - 1,
                           0)
        return layer[0], bi, jnp.minimum(si, last), 0

    def _q_index(bi, si, lens, layer):
        return bi, 0, 0

    def _keep_index(bi, si, lens, layer):
        return bi, 0, _kv_index(bi, si, lens, layer)[2]

    kept = keep is not None
    in_specs = [pl.BlockSpec((1, h, dk), _q_index),
                pl.BlockSpec((1, 1, block_s, dk), _kv_index)]
    operands = [q, cache]
    if kept:
        in_specs.append(pl.BlockSpec((1, 1, block_s), _keep_index))
        operands.append(keep.astype(jnp.float32).reshape(b, 1, s))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, s // block_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, v_dim), _q_index),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),      # running max
                        pltpu.VMEM((h, 1), jnp.float32),      # running denom
                        pltpu.VMEM((h, v_dim), jnp.float32)],  # numerator
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
