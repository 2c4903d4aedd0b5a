"""What the served families' modules share; this module imports no family.

A family imports these INTO ITS OWN NAMESPACE and calls them by the bare
name, never as ``common.route(..)``: the benchmark's controls degrade a
family by replacing names in that family's module (``setattr(family,
"route", fn)``, or a second copy of it with ``_real`` replaced), and a
call that went round the family's own binding would let a known-bad
control pass.
"""

from __future__ import annotations

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.ops import (blockwise_attention, causal_attention,
                         full_causal_attention, mla_decode_attention)

F32 = jnp.float32


def _mm(eq: str, x, w):
    """A product with a weight: the activation rounded to the weight's
    type on the way in (the MXU's operands), accumulated and handed on
    in float32."""
    return jnp.einsum(eq, x.astype(w.dtype), w, preferred_element_type=F32)


def _layer_of(stack, idx):
    """Layer ``idx`` of a stack of layers, sliced where it is used: a
    loop nested in the scan over periods that took its layers as a
    [linear_per_period, ..] slice of the stack would have that slice
    COPIED out for it every period (the chip's trace, PR 33: 6 GB of
    weights a decode step); indexed from the whole stack inside the
    inner loop, each matrix is read by its product where it lies."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), stack)


def _starts_fresh(cache_index):
    """Whether a prefill at ``cache_index`` starts a request: its slot's
    state is then whatever the last request left, and is not read."""
    return cache_index == 0


def _real(t: int, last):
    """(valid [T], tokens that are real) of a bucket of ``t`` whose
    last real token is ``last`` (None: all are)."""
    if last is None:
        return None, t
    return jnp.arange(t) <= last, jnp.asarray(last, jnp.int32) + 1


def _swiglu(n, w_gate, w_up, w_down):
    ff = jax.nn.silu(_mm("td,df->tf", n, w_gate)) * _mm("td,df->tf", n, w_up)
    return _mm("tf,fd->td", ff, w_down)


def _write_rows(cache, layer_idx, lengths, rows):
    """rows [B,KH,D] -> cache[layer_idx, b, :, lengths[b], :] of the
    [L,B,KH,S,D] cache, in place under a loop that carries it.

    A scatter of D-wide rows into the cache seen as [L*B*KH, S, D] (a
    free view): that is the form the chip's compiler updates in place
    in the cache's own layout. Scattered as [KH,D] windows of the 5-D
    array it re-lays the WHOLE cache out, KH inside S, and back around
    every step; one dynamic_update_slice a slot stays in place but
    costs 64 small operations a layer (measured, PERF.md PR 26).
    ``lengths`` is bounded BY CONTRACT like ``_block``'s cache_index:
    the engine parks a done or empty slot's write on a row of its own
    that nothing reads (decode_loop's header), under the cache's
    extent."""
    n_layers, b, kh, s, d = cache.shape
    heads = layer_idx * (b * kh) + jnp.arange(b * kh, dtype=jnp.int32)
    flat = cache.reshape(n_layers * b * kh, s, d)
    flat = flat.at[heads, jnp.repeat(lengths.astype(jnp.int32), kh)].set(
        rows.reshape(b * kh, d).astype(cache.dtype),
        unique_indices=True, indices_are_sorted=True)
    return flat.reshape(cache.shape)


def _write_latent_rows(cache, layer_idx, lengths, rows):
    """rows [B,W] -> cache[layer_idx, b, lengths[b]] of the [L,B,S,W]
    cache: a scatter of W-wide rows into the free view [L*B, S, W], the
    form the chip's compiler updates in place (`_write_rows`, PR
    26). ``lengths`` is bounded by the engine's contract."""
    n_layers, b, s, w = cache.shape
    slots = layer_idx * b + jnp.arange(b, dtype=jnp.int32)
    flat = cache.reshape(n_layers * b, s, w)
    flat = flat.at[slots, lengths.astype(jnp.int32)].set(
        rows.astype(cache.dtype), unique_indices=True,
        indices_are_sorted=True)
    return flat.reshape(cache.shape)


def route(x, router, bias, cfg, precision=None):
    """x [T, d] -> (experts [T, k] int32, gates [T, k] float32): chosen
    on ``s + b``, weighted by ``s``. ``precision`` is the router
    product's (None: the backend's default, which on the TPU rounds a
    float32 router to bf16; a family that states a float32 router asks
    for `lax.Precision.HIGHEST`)."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router,
                                  precision=precision,
                                  preferred_element_type=F32))
    _, experts = lax.top_k(s + bias.astype(F32), cfg.n_experts_per_tok)
    gates = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), gates * cfg.routed_scaling_factor


def _padded(a, width: int):
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, width - a.shape[-1]),))


def _expand(rows, layer, cfg):
    """Cache rows [B,S,W] -> per-head keys and values [B,S,H,
    ``attn_head_dim``]: the up-projections applied, the one shared key
    beside each head's, zeros up to the kernel's head size."""
    c_kv = rows[..., :cfg.kv_lora_rank]
    shared = rows[..., cfg.kv_lora_rank:cfg.cache_row_values]
    k_a = _mm("bsr,rhk->bshk", c_kv, layer["w_uk"]).astype(rows.dtype)
    v = _mm("bsr,rhv->bshv", c_kv, layer["w_uv"]).astype(rows.dtype)
    shared = jnp.broadcast_to(shared[:, :, None, :],
                              k_a.shape[:3] + (cfg.qk_rope_head_dim,))
    return (_padded(jnp.concatenate([k_a, shared], axis=-1),
                    cfg.attn_head_dim), _padded(v, cfg.attn_head_dim))


def mla_prefill_attend(q, rows, layer, kv_l, cache_index, positions, cfg):
    """q [B,T,H,qk], rows [B,T,W] (the tokens' cache rows), kv_l
    [B,S,W] (this layer's rows of the slot) -> (the mixer's output
    [B,T,d] float32, kv_l with the rows written). Expanded MLA, for
    every family whose latent row is ``c~ ++ one shared key`` (`models/kimi_linear.py`'s
    shared key is plain, `models/xing_mhc.py`'s rotated)."""
    q = _padded(q, cfg.attn_head_dim)
    # cache_index + T is bounded by the engine's contract, as in
    # llama._block: the scheduler admits only what fits a slot's rows.
    kv_l = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        kv_l, rows.astype(kv_l.dtype), (0, cache_index, 0))

    def fresh(_):
        k, v = _expand(rows, layer, cfg)
        return full_causal_attention(q, k, v, scale=cfg.attn_scale)

    def through_the_cache(_):
        k, v = _expand(kv_l, layer, cfg)
        s = kv_l.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s), (q.shape[0], s))
        attend = blockwise_attention if s >= 1024 else causal_attention
        return attend(q, k, v, q_positions=positions, kv_positions=kv_pos,
                      scale=cfg.attn_scale).astype(q.dtype)

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    return _mm("bthv,hvd->btd", attn[..., :cfg.v_head_dim],
               layer["w_o"]), kv_l


def mla_decode_attend(q, row, layer, layer_idx, kv, lengths, cfg):
    """q [B,H,qk], row [B,W] (each slot's new cache row), the whole
    [L,B,S,W] array carried -> (the mixer's output [B,d] float32, kv).
    Absorbed MLA: the step attends over the latent rows themselves."""
    kv = _write_latent_rows(kv, layer_idx, lengths, row)
    nope = cfg.qk_nope_head_dim
    q_lat = _mm("bhk,rhk->bhr", q[..., :nope], layer["w_uk"])
    q = _padded(jnp.concatenate([q_lat.astype(q.dtype), q[..., nope:]],
                                axis=-1), cfg.cache_row_dim)     # [B,H,W]
    o_lat = mla_decode_attention(
        q, kv, (lengths + 1).astype(jnp.int32), layer=layer_idx,
        v_dim=cfg.kv_lora_rank, scale=cfg.attn_scale,
        interpret=cfg.interpret_kernels)
    o = _mm("bhr,rhv->bhv", o_lat, layer["w_uv"])
    return _mm("bhv,hvd->bd", o, layer["w_o"]), kv
