"""Pallas TPU kernel: single-token decode attention over a KV cache.

The serving engine's hot op (serve/llm.py decodes one token per slot per
step): q is ONE query position per sequence attending to a long cache.
The training-shaped flash kernel (ops/attention.py dispatches to the tuned
jax.experimental.pallas.ops.tpu kernel) wants big q blocks; decode has
q_len == 1, so its arithmetic is pure KV streaming — this kernel keeps the
MXU busy by folding the GQA query-head group into the q-block rows and
streams the cache in lane-aligned blocks with the online-softmax carry in
VMEM scratch (the canonical flash pattern from the Pallas guide:
sequential innermost grid dimension + revisited output block).

Layout (grid = (B, KH, S/block_s), innermost sequential on one core):
  q      [B, KH, G, D]     one block (1,1,G,D) per (b,kh)
  k,v    [L, B, KH, S, D]  one block (1,1,1,block_s,D) per (b,kh,s) of
                           layer ``layer`` — the engine's WHOLE cache is
                           the operand and the layer index picks the
                           blocks, so no layer is sliced out of it first
                           (a [B,KH,S,D] cache is the L == 1 case)
  len    [B]               int32, SMEM scalar-prefetch (masks cache tail)
  layer  [1]               int32, SMEM scalar-prefetch
  out    [B, KH, G, D]     written on the LAST s-block

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


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_s: int, scale: float):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Inputs stay in their storage dtype (bf16 on the serving path): the
    # MXU takes bf16 operands with f32 accumulation via
    # preferred_element_type, and the f32 upcasts cost ~1.8x end-to-end
    # (measured 1563us -> 873us on v5e at B8/H32/KH8/S4096/D128).
    q = q_ref[0, 0]                              # [G, D]
    k = k_ref[0, 0, 0]                           # [block_s, D]
    v = v_ref[0, 0, 0]
    length = len_ref[b]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [G, block_s] f32
    positions = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(positions < length, logits, NEG_INF)

    m_prev = m_ref[...]                          # [G, 1] carried max
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                  # [G, block_s] f32
    # Fully-masked block (length == 0 slot): every logit == m_new ==
    # NEG_INF and exp(0) would attend UNIFORMLY to padding — clamp to 0
    # (the standard flash guard; output for an empty slot is then 0/eps).
    p = jnp.where(m_new == NEG_INF, 0.0, p)
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "layout"))
def decode_attention(q, k, v, lengths, *, layer=None,
                     block_s: int = 2048,
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
    whose rows ``block_s`` does not divide is sliced (and padded)
    first, as is the reference's."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = False
    kernel = on_tpu or interpret
    if layer is not None and layout != "bksd":
        raise ValueError("a layered cache is [L,B,KH,S,D]: layout 'bksd'")
    if layer is not None and not (kernel and k.shape[3] % block_s == 0):
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

    b, h, d = q.shape
    if layer is None:
        if layout == "bskd":
            k = k.transpose(0, 2, 1, 3)  # [B,KH,S,D]
            v = v.transpose(0, 2, 1, 3)
        if k.shape[2] % block_s:
            pad = block_s - k.shape[2] % block_s
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k, v, layer = k[None], v[None], 0    # [1,B,KH,S,D]: layer 0 of one
    kh, s = k.shape[2], k.shape[3]
    rep = h // kh
    qg = q.reshape(b, kh, rep, d)

    def _kv_index(bi, ki, si, lens, layer):
        return layer[0], bi, ki, si, 0

    def _q_index(bi, ki, si, lens, layer):
        return bi, ki, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, s // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d), _q_index),
            pl.BlockSpec((1, 1, 1, block_s, d), _kv_index),
            pl.BlockSpec((1, 1, 1, block_s, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),   # running max
            pltpu.VMEM((rep, 1), jnp.float32),   # running denom
            pltpu.VMEM((rep, d), jnp.float32),   # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, d), q.dtype),
        interpret=interpret,
        name="rtpu_decode_attention",
        metadata={"kernel": "rtpu_decode_attention"},
    )(lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k, v)
    return out.reshape(b, h, d)
