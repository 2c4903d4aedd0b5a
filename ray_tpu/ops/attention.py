"""Causal grouped-query attention: dispatcher + portable paths.

`full_causal_attention` dispatches to the Pallas TPU flash kernels of
`ops/flash_attention.py` (forward, dkv, dq; blocks chosen from the head
size and the sequence by `flash_block_sizes`, gate `use_fused_kernel`);
the blockwise online-softmax scan below is the portable path (CPU
tests, ragged shapes), and `ops/ring_attention.py` covers sequence
parallelism over the ``sp`` axis.

Shapes follow [batch, seq, heads, head_dim] throughout ("BSHD").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads to match query heads for GQA: [B,S,K,D] -> [B,S,K*n,D]."""
    if n_rep == 1:
        return k
    b, s, kh, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, d)).reshape(
        b, s, kh * n_rep, d)


def causal_attention(
    q: jnp.ndarray,                 # [B, Sq, H, D]
    k: jnp.ndarray,                 # [B, Sk, KH, D]
    v: jnp.ndarray,                 # [B, Sk, KH, D]
    *,
    q_positions: Optional[jnp.ndarray] = None,   # [B, Sq] global positions
    kv_positions: Optional[jnp.ndarray] = None,  # [B, Sk]
    kv_mask: Optional[jnp.ndarray] = None,       # [B, Sk] valid-kv mask (decode)
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Softmax(QK^T)V with causal masking by *global position*.

    Position-based masking (not index-based) makes the same function serve
    full prefill, chunked prefill, and single-token decode against a KV cache.
    """
    b, sq, h, d = q.shape
    kh = k.shape[2]
    if h != kh:
        rep = h // kh
        k = repeat_kv(k, rep)
        v = repeat_kv(v, rep)
    if scale is None:
        scale = d ** -0.5
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(k.shape[1]), (b, k.shape[1]))

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    causal = q_positions[:, None, :, None] >= kv_positions[:, None, None, :]
    if kv_mask is not None:
        causal = jnp.logical_and(causal, kv_mask[:, None, None, :])
    logits = jnp.where(causal, logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def online_softmax_update(q, k, v, q_pos, k_pos, m, l, o, scale):
    """One flash-style online-softmax accumulation step against a K/V block.

    The single shared implementation for the blockwise scan (below) and the
    ring-attention ppermute loop (`ops/ring_attention.py`). GQA-aware: k/v may
    have fewer heads ([B,Sk,KH,D]); they are expanded here, AFTER any
    inter-chip transfer, so ring hops move only the un-repeated KV bytes.

    q: [B,Sq,H,D]; accumulators m,l: [B,H,Sq] fp32, o: [B,H,Sq,D] fp32.
    """
    h, kh = q.shape[2], k.shape[2]
    if h != kh:
        k = repeat_kv(k, h // kh)
        v = repeat_kv(v, h // kh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def blockwise_attention(
    q: jnp.ndarray,                 # [B, Sq, H, D]
    k: jnp.ndarray,                 # [B, Sk, KH, D]
    v: jnp.ndarray,                 # [B, Sk, KH, D]
    *,
    q_positions: jnp.ndarray,       # [B, Sq]
    kv_positions: jnp.ndarray,      # [B, Sk]
    scale: Optional[float] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    """Flash-style online-softmax attention, scanning KV in blocks.

    Never materializes the [Sq, Sk] score matrix: peak temp is
    [B, H, Sq, block_k]. Portable (CPU tests, TPU fallback when the Pallas
    kernel does not apply); numerics match `causal_attention`.
    """
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if sk % block_k or sk < block_k:
        # Ragged tail: fall back to the dense path.
        return causal_attention(q, k, v, q_positions=q_positions,
                                kv_positions=kv_positions, scale=scale)
    n_blocks = sk // block_k
    kb = k.reshape(b, n_blocks, block_k, kh, d).swapaxes(0, 1)
    vb = v.reshape(b, n_blocks, block_k, kh, d).swapaxes(0, 1)
    pb = kv_positions.reshape(b, n_blocks, block_k).swapaxes(0, 1)

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)

    def step(carry, blk):
        m, l, o = carry
        kc, vc, kp = blk
        m, l, o = online_softmax_update(q, kc, vc, q_positions, kp,
                                        m, l, o, scale)
        return (m, l, o), None

    (m, l, o), _ = lax.scan(
        jax.checkpoint(step), (m0, l0, o0), (kb, vb, pb))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)


def _default_positions(q_positions, kv_positions, b, sq, sk) -> bool:
    """True iff positions are the standard full-sequence arange (the only
    pattern the fused TPU kernel's `causal=True` flag encodes)."""
    if q_positions is None and kv_positions is None:
        return sq == sk
    return False


def use_fused_kernel(on_tpu: bool, standard: bool, sq: int, d: int) -> bool:
    """The fused-flash dispatch gate, exposed for tests: the kernels take
    any head_dim <= 128 or an exact multiple of 128 — Llama-class
    head_dim=64/128 both qualify. A head size under 128 is NOT padded in
    HBM (the trace shows ``bf16[16,16,2048,64]`` operands): it half-fills
    the 128-wide MXU in both products, which is why the kernels' compute
    roofline share at head size 64 cannot pass about 50 %."""
    return (on_tpu and standard and sq >= 256 and sq % 128 == 0
            and (d <= 128 or d % 128 == 0))


# (head size, sequence) whose blocks a sweep on the chip chose: PERF.md
# section 6, PR 38 (TPU v5e, bare kernels, device time from a trace).
SWEPT = frozenset((hd, sq) for hd in (64, 128, 256)
                  for sq in (1024, 2048, 4096))


def flash_block_sizes(hd: int, sq: int):
    """The `BlockSizes` of the three flash kernels (forward, dkv, dq) for
    one head size and sequence length.

    Where the sweep measured: 512 x 512 tiles inside major blocks of the
    whole sequence, to 2,048 rows. The kernels skip a masked TILE, so a
    large major block costs no masked work and saves grid steps (K and V
    of a head stay resident), and 512 is where a tile's fixed work (the
    running max, sum and accumulator rescaled) stops mattering while the
    diagonal still cuts little: at head size 64 and sequence 2,048 the
    forward takes 2.40 ms a call with these blocks, 3.00 with 1,024 for
    every field (12 of 16 tiles computed where the causal half needs 10)
    and 4.24 with 256-wide tiles in a 1,024 major block; the library's
    kernels, which skip by the major block alone, took 3.30 at 1,024 for
    every field (their best) and 7.40 at 256, 128 being their default.
    The same shape of blocks won at head sizes 128 and 256. A major
    block is not longer than 2,048 rows: a kernel's code grows with the
    tiles it unrolls, and 36 of them ran 3.8 times slower than 14.
    Elsewhere: the largest of 1,024 / 512 / 256 / 128 that divides the
    sequence for every field, which is what every shape had before the
    sweep.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    one = next(c for c in (1024, 512, 256, 128) if sq % c == 0)
    if (hd, sq) in SWEPT:
        tile, major = 512, min(sq, 2048)
        # The backward holds more of a major block in VMEM than the
        # forward (q, o, do, a statistic, k, v, dk, dv), and twice the
        # bytes a row at head size 256.
        major_dq = major if hd <= 128 else 1024
        # dkv unrolls rows x columns of tiles, and a sequence of more
        # than one major block needs them a second time, unmasked.
        major_dkv = major_dq if sq <= 2048 else 1024
    else:
        tile = major = major_dq = major_dkv = one
    return BlockSizes(
        block_q=tile, block_k_major=major, block_k=tile, block_b=1,
        block_q_major_dkv=major_dkv, block_q_dkv=tile,
        block_k_major_dkv=major_dkv, block_k_dkv=tile,
        block_q_dq=tile, block_k_major_dq=major_dq, block_k_dq=tile)


# `checkpoint_name`s of what the flash backward kernels read of their
# forward: its output and its row statistic, named where the custom
# VJP's residuals are made (`ops/flash_attention.py`). A `jax.checkpoint`
# policy that keeps them runs no forward kernel in the backward pass
# (`models/llama.py` `_remat_policy`). Here, not beside the kernels, so
# that naming them imports no Pallas.
FLASH_SAVED = ("flash_out", "flash_lse")


def _flash_attention(q, k, v, scale: float):
    """The Pallas flash kernels on [B,S,H|KH,D] operands (GQA heads
    repeated here, so under `shard_map` only un-repeated KV crosses the
    partition boundary)."""
    from ray_tpu.ops.flash_attention import flash_attention

    sq, h, kh = q.shape[1], q.shape[2], k.shape[2]
    if h != kh:
        k = repeat_kv(k, h // kh)
        v = repeat_kv(v, h // kh)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = flash_attention(qt, kt, vt, scale,
                          flash_block_sizes(q.shape[3], sq))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _sharded_flash_attention(q, k, v, scale: float, mesh: Mesh):
    """Mosaic kernels cannot be auto-partitioned: on a multi-device
    mesh the kernel runs per shard under `shard_map`, batch split over
    the data axes and heads over ``tp`` (attention is independent per
    sequence and per head, so no collective is needed)."""
    tp = mesh.shape.get("tp", 1)
    if k.shape[2] % tp:
        # KV heads do not split over tp: repeat first, then every shard
        # owns whole query heads with their own KV copy.
        rep = q.shape[2] // k.shape[2]
        k, v = repeat_kv(k, rep), repeat_kv(v, rep)
    spec = P(("dp", "fsdp"), None, "tp", None)
    return shard_map(
        functools.partial(_flash_attention, scale=scale), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call's output carries no varying-axes type.
        check_vma=False,
    )(q, k, v)


def full_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *,
    q_positions: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Training-path attention dispatcher (full sequence, causal).

    TPU: fused Pallas flash kernel (jax.experimental.pallas.ops.tpu) — no
    [Sq,Sk] materialization, fwd+bwd kernels; pass the ``mesh`` the
    caller's jit partitions over so the kernel is shard_mapped on more
    than one device. Elsewhere / ragged shapes: blockwise online-softmax
    scan, then dense for short sequences.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    on_tpu = jax.default_backend() == "tpu"
    standard = _default_positions(q_positions, kv_positions, b, sq, sk)
    if use_fused_kernel(on_tpu, standard, sq, d):
        if mesh is not None and mesh.size > 1:
            return _sharded_flash_attention(q, k, v, scale, mesh)
        return _flash_attention(q, k, v, scale)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk), (b, sk))
    if sk >= 1024:
        return blockwise_attention(q, k, v, q_positions=q_positions,
                                   kv_positions=kv_positions, scale=scale)
    return causal_attention(q, k, v, q_positions=q_positions,
                            kv_positions=kv_positions, scale=scale)
