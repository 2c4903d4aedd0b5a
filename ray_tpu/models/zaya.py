"""ZAYA1-class decoder (``zaya``): attention inside a compressed latent
with convolutional mixing over time (CCA) whose TWO-TOKEN TAIL lives
beside the K/V rows in the engine's one cache, and large experts of
which a router MLP picks ONE a token, served through the engine's model
seam (``serve/engine/README.md``).

Pre-norm residual blocks, RMSNorm with weight, a final norm, a TIED
head (``logits = x · embedᵀ``). Every layer is attention then experts.
``d`` the hidden size, ``Hq`` query heads and ``Hkv`` KV heads of
``dh``, ``G = Hq / Hkv``; for token ``t``, ``h_t = RMSNorm(x_t)``:

1. ``u_t = h_t W_qk`` (``Hq dh`` query-latent columns, then ``Hkv dh``
   key-latent ones: ``Hq + Hkv`` heads of ``dh``), ``w_t = h_t W_v``
   (``Hkv dh`` columns: a half for this token, a half for the next).
2. ``a_t = w0[:,0] u_{t-1} + w0[:,1] u_t + b0`` (depthwise, causal).
3. ``c_t[g] = a_{t-1}[g] W1[g,0] + a_t[g] W1[g,1] + b1[g]`` (one group
   a head, ``dh -> dh``, causal). Each convolution pads ITS OWN input
   with zeros: ``u_{-1} = 0`` and ``a_{-1} = 0`` (not ``b0``).
4. On the PRE-convolution latents: for query head ``j`` of KV head
   ``i = j // G``, ``m^q_j = (u^q_j + u^k_i) / 2``; ``m^k_i`` the mean
   of its group's ``m^q_j``. ``q = c[queries] + m^q``,
   ``k = c[keys] + m^k``.
5. ``v_t = [w_t[:half] ; w_{t-1}[half:]]`` (``w_{-1} = 0``): at two KV
   heads, head 0 from this token and head 1 from the last.
6. Per head ``q <- sqrt(dh) q/|q|``, ``k <- exp(tau_i) sqrt(dh) k/|k|``
   (``tau`` a float32 scalar a KV head), then rotary at position ``t``
   on the first ``rotary_dim`` columns of every head (`apply_rope` on
   that slice, the rest joined on untouched).
7. Causal GQA ``softmax(q kᵀ / sqrt(dh)) v``; ``x += o W_o``.
8. ``g = RMSNorm(x)``; the router in float32 (its products at the
   highest precision): ``r = g W_d``, ``s = W_3 gelu(W_2 gelu(W_1
   RMSNorm(r)))``, ``p = softmax(s)``, ``e* = argmax(p + b)``,
   ``x += p_{e*} SwiGLU_{e*}(g)``. No capacity: no token is dropped
   however skewed the routing (``ops/grouped_experts.py``, shared with
   ``models/glm_moe_lite.py``); a bucket's padding goes to no expert.

The residual stream is float32 and every product with a bf16 weight
takes its operands in bf16 and accumulates in float32 (as the two
families with per-slot state do): a bf16 stream is rounded twice a
layer, and what that adds up to over 16 layers is what moves a token
across its router's boundary (v5e, PR 40: the largest excess of a
differing choice 0.008 to 0.023 of the scores' spread over nine runs
with a bf16 stream, 0.006 to 0.012 over six with this one). Steps 2 to 6 run in float32 (the two
convolutions and the router's MLP hold float32 weights and multiply at
the highest precision: they are a few ``[T, 128] x [128, 128]`` and
``[T, 256] x [256, 256]`` products); q, k, v are rounded to the cache's
type.

**The cache** is rows AND a tail, slot axis second:

    k, v   [L, B, Hkv, rows, dh]       a row a token, AFTER step 6
    tail   [L, B, 2 (Hq+Hkv) dh + Hkv dh / 2]   float32, no rows

``tail`` holds what the next token's steps 2, 3 and 5 read of the last
one: ``u_{t-1}``, ``a_{t-1}`` and ``w_{t-1}[half:]`` (1280 + 1280 + 128
= 2,688 values = 21 whole lane tiles at the published sizes). It is
per-slot state (`SLOT_STATE_KEYS`) but no recurrence: it depends on two
tokens, not on the whole prefix. A prefill at ``cache_index`` 0 starts
from a ZERO tail (the slot's reset, inside the tick's prefill program:
a stale tail, unlike a stale row, is masked by no length), any other
reads the slot's (a later chunk of a chunked prefill); the tail written
back is that of the LAST REAL token, not of the bucket's padding. A
decode step steps the tail of the LIVE slots only.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import _write_rows
from ray_tpu.ops import (
    apply_rope,
    blockwise_attention,
    causal_attention,
    decode_attention,
    decode_step_rows,
    full_causal_attention,
    rms_norm,
)
from ray_tpu.ops.grouped_experts import (
    gated_sum,
    grouped_swiglu,
    split_expert_stacks,
)

Params = Dict[str, Any]
F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST

# The cache entry that holds one tail a slot and no rows (this module's
# header says what the engine does about it).
SLOT_STATE_KEYS = ("tail",)
# Fetched counter -> the attribute the request's span carries it under.
SPAN_ATTRS = {"moe_prefill_load_max": "experts_max_load",
              "moe_expert_hits": "experts_touched",
              "state_resets": "state_reset"}


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    d_model: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2               # taps of the depthwise convolution
    cca_time1: int = 2               # taps of the grouped one
    rotary_dim: int = 64             # ``partial_rotary_factor`` x head_dim
    rope_theta: float = 5e6
    n_experts: int = 16
    n_experts_per_tok: int = 1
    moe_d_ff: int = 2048
    router_d: int = 256
    max_seq_len: int = 131072
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Run the decode kernel under the Pallas interpreter off the TPU
    # (tests); otherwise the kernel on the TPU, its jnp reference off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise ValueError("the tail holds ONE token back of each "
                             "convolution: cca_time0 = cca_time1 = 2")
        if self.n_experts_per_tok != 1:
            raise ValueError("the router picks one expert a token")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("KV heads must divide the query heads, and "
                             "halve (the value shift)")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim}")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def conv_heads(self) -> int:
        return self.n_heads + self.n_kv_heads

    @property
    def conv_channels(self) -> int:
        return self.conv_heads * self.head_dim

    @property
    def value_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def tail_dim(self) -> int:
        """``u_{t-1}`` ++ ``a_{t-1}`` ++ the shifted half of ``w_{t-1}``."""
        return 2 * self.conv_channels + self.value_dim // 2


# Parameters ---------------------------------------------------------------

def init_params(cfg: ZayaConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one. One stack ``layers`` [L, ..], scanned. Storage conventions
    (the reference undoes them): ``W_q`` and ``W_k`` side by side as
    ``w_qk``, ``W_v1`` and ``W_v2`` as ``w_v``, matrices input-major;
    the grouped convolution ``conv1_w [head, tap, in, out]`` with tap 0
    on the LAST token; the convolutions, ``tau``, the router and its
    bias float32."""
    d, dt, n = cfg.d_model, cfg.dtype, cfg.n_layers
    c, dh, g = cfg.conv_channels, cfg.head_dim, cfg.conv_heads
    e, f, r = cfg.n_experts, cfg.moe_d_ff, cfg.router_d
    keys = iter(jax.random.split(key, 24))

    def norm(shape, fan_in, dtype=dt, scale=1.0):
        return (jax.random.normal(next(keys), shape, F32)
                * scale * fan_in ** -0.5).astype(dtype)

    def centred(shape, fan_in):
        """A router matrix whose columns sum to zero: what all tokens'
        inputs have in common (a GELU's mean) reaches no expert. A
        trained router's load is kept even by what it learned; seeded
        weights have learned nothing, and uncentred they send a third
        of a step's tokens to one expert (v5e, PR 40)."""
        w = norm(shape, fan_in, F32)
        return w - jnp.mean(w, axis=-2, keepdims=True)

    return {
        "embed": norm((cfg.vocab_size, d), d),
        "layers": {
            "ln_attn": jnp.zeros((n, d), dt),
            "w_qk": norm((n, d, c), d),
            "w_v": norm((n, d, cfg.value_dim), d),
            "conv0_w": norm((n, c, 2), 2, F32),
            "conv0_b": norm((n, c), 1, F32, 0.1),
            "conv1_w": norm((n, g, 2, dh, dh), 2 * dh, F32),
            "conv1_b": norm((n, g, dh), 1, F32, 0.1),
            "tau": norm((n, cfg.n_kv_heads), 1, F32, 0.1),
            "w_o": norm((n, cfg.n_heads * dh, d), cfg.n_heads * dh),
            "ln_mlp": jnp.zeros((n, d), dt),
            "router_down": centred((n, d, r), d),
            "ln_router": jnp.zeros((n, r), F32),
            "router_1": centred((n, r, r), r),
            "router_2": centred((n, r, r), r),
            "router_3": centred((n, r, e), r),
            # Small beside the spread of p over the experts (about 0.07
            # with seeded weights), so that the load stays as even as a
            # trained bias keeps it; half the usual gap between the
            # first p and the second, so that choosing on p + b differs
            # from choosing on p on one row in six.
            "router_bias": norm((n, e), 1, F32, 0.004),
            "w_gate": norm((n, e, d, f), d),
            "w_up": norm((n, e, d, f), d),
            "w_down": norm((n, e, f, d), f),
        },
        "ln_out": jnp.zeros((d,), dt),
    }


# Experts ------------------------------------------------------------------

def route(g, layer, cfg: ZayaConfig):
    """g [T, d] float32 (the normed stream) -> (expert [T] int32, gate
    [T] float32, p [T, E]): the router MLP in float32 at the highest
    precision; chosen on ``p + b``, weighted by its own ``p``."""
    dot = lambda x, w: jnp.dot(x, w, precision=_HIGHEST)
    r = rms_norm(dot(g, layer["router_down"]), layer["ln_router"],
                 cfg.norm_eps)
    gelu = lambda x: jax.nn.gelu(x, approximate=False)
    s = dot(gelu(dot(gelu(dot(r, layer["router_1"])), layer["router_2"])),
            layer["router_3"])
    p = jax.nn.softmax(s, axis=-1)
    expert = jnp.argmax(p + layer["router_bias"], axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
    return expert, gate, p


def moe_ffn(x, layer, stacks, layer_idx, cfg: ZayaConfig, valid=None):
    """x [T, d] (the residual stream) -> (y [T, d] to add to it, expert
    [T], load [E], what a check reads of the router: its input and its
    ``p``). ``stacks`` every layer's experts as one run of groups."""
    g = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    expert, gate, p = route(g, layer, cfg)
    y, load = grouped_swiglu(g.astype(cfg.dtype), expert[:, None], stacks,
                             layer_idx, cfg.n_experts, valid)
    y = gated_sum(y, gate[:, None])
    return y, expert, load, {"router_in": g, "router_p": p}


# Attention ----------------------------------------------------------------

def _mm(eq: str, x, w):
    """A product with a bf16 weight: the activation rounded to the
    weight's type on the way in, accumulated and handed on in float32."""
    return jnp.einsum(eq, x.astype(w.dtype), w, preferred_element_type=F32)


def _tail_parts(tail, cfg: ZayaConfig):
    """tail [.., tail_dim] -> (u_prev, a_prev [.., C], w_prev [.., dv/2])."""
    c = cfg.conv_channels
    return tail[..., :c], tail[..., c:2 * c], tail[..., 2 * c:]


def _conv0(u, u_prev, layer):
    """Step 2: the depthwise convolution's output for this token."""
    return (layer["conv0_w"][:, 0] * u_prev + layer["conv0_w"][:, 1] * u
            + layer["conv0_b"])


def _qk_mean(u, cfg: ZayaConfig):
    """Step 4's two means of the PRE-convolution latents u [.., C] ->
    (m^q [.., Hq, dh], m^k [.., Hkv, dh])."""
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = u.shape[:-1]
    uq = u[..., :hq * dh].reshape(lead + (hkv, hq // hkv, dh))
    uk = u[..., hq * dh:].reshape(lead + (hkv, 1, dh))
    mq = 0.5 * (uq + uk)
    return mq.reshape(lead + (hq, dh)), jnp.mean(mq, axis=-2)


def _value(w, w_prev, cfg: ZayaConfig):
    """Step 5: w [.., dv] this token's value latents, w_prev [.., dv/2]
    the last token's second half -> v [.., dv]."""
    return jnp.concatenate([w[..., :cfg.value_dim // 2], w_prev], axis=-1)


def _unit(z, cfg: ZayaConfig):
    """Step 6's ``sqrt(dh) z / |z|`` a head."""
    return z * (lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-30)
                * cfg.head_dim ** 0.5)


def _heads(u, a, a_prev, v, layer, positions, cfg: ZayaConfig):
    """Steps 3 to 6 on [B, C] (a step; the slots are `apply_rope`'s
    sequence axis) or [B, T, C]: u (this token's latents), a and a_prev
    (the first convolution's output for this token and the last), v
    [.., dv] (step 5's two halves joined), positions [B] or [B, T] ->
    q [.., Hq, dh], k, v [.., Hkv, dh] in the cache's type."""
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = u.shape[:-1]
    by_head = lambda z: z.reshape(lead + (cfg.conv_heads, dh))
    w1 = layer["conv1_w"]
    c = (jnp.einsum("...hc,hcd->...hd", by_head(a_prev), w1[:, 0],
                    precision=_HIGHEST)
         + jnp.einsum("...hc,hcd->...hd", by_head(a), w1[:, 1],
                      precision=_HIGHEST)
         + layer["conv1_b"])
    mq, mk = _qk_mean(u, cfg)
    q = _unit(c[..., :hq, :] + mq, cfg)
    k = _unit(c[..., hq:, :] + mk, cfg) * jnp.exp(layer["tau"])[:, None]

    def rotate(z):      # the first rotary_dim columns of every head
        rot = apply_rope(z[..., :cfg.rotary_dim], positions, cfg.rope_theta)
        return jnp.concatenate([rot, z[..., cfg.rotary_dim:]], axis=-1)

    dt = cfg.dtype
    return (rotate(q).astype(dt), rotate(k).astype(dt),
            v.reshape(lead + (hkv, dh)).astype(dt))


def _starts_fresh(cache_index):
    """Whether a prefill at ``cache_index`` starts a request: its slot's
    tail is then whatever the last request left, and is not read."""
    return cache_index == 0


def _prefill_block(x, layer, stacks, layer_idx, ck, cv, tail, cache_index,
                   positions, last, valid, cfg: ZayaConfig):
    """x [B,T,d]; ck, cv [B,Hkv,S,dh] and tail [B,tail_dim]: this
    layer's of the slot -> (x, ck, cv, tail as it stands after the last
    REAL token, expert [B,T], load [E], the router's readings)."""
    b, t, d = x.shape
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    u = _mm("btd,dc->btc", h, layer["w_qk"])
    w = _mm("btd,dc->btc", h, layer["w_v"])
    tail = jnp.where(_starts_fresh(cache_index), 0.0, tail)
    u_first, a_first, w_first = _tail_parts(tail, cfg)
    half = cfg.value_dim // 2
    shifted = lambda first, z: jnp.concatenate([first[:, None], z[:, :-1]],
                                               axis=1)
    # The first convolution's output one token back is its own output
    # shifted, with the tail's ``a`` standing before it.
    a = _conv0(u, shifted(u_first, u), layer)
    v = _value(w, shifted(w_first, w[..., half:]), cfg)
    q, k, v = _heads(u, a, shifted(a_first, a), v, layer, positions, cfg)
    at = (jnp.asarray(t - 1, jnp.int32) if last is None
          else jnp.asarray(last, jnp.int32))
    row = lambda z: lax.dynamic_index_in_dim(z, at, axis=1, keepdims=False)
    tail = jnp.concatenate([row(u), row(a), row(w)[..., half:]], axis=-1)
    # cache_index + T is bounded by the engine's contract, as in
    # llama._block: the scheduler admits only what fits a slot's rows.
    ck = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        ck, k.swapaxes(1, 2).astype(ck.dtype), (0, 0, cache_index, 0))
    cv = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        cv, v.swapaxes(1, 2).astype(cv.dtype), (0, 0, cache_index, 0))

    def fresh(_):
        return full_causal_attention(q, k, v)

    def through_the_cache(_):
        s = ck.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        attend = blockwise_attention if s >= 1024 else causal_attention
        return attend(q, ck.swapaxes(1, 2), cv.swapaxes(1, 2),
                      q_positions=positions,
                      kv_positions=kv_pos).astype(q.dtype)

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    x = x + _mm("btc,cd->btd", attn.reshape(b, t, -1),
                layer["w_o"])
    y, expert, load, seen = moe_ffn(
        x.reshape(b * t, d), layer, stacks, layer_idx, cfg,
        None if valid is None else valid.reshape(-1))
    seen = {name: z.reshape((b, t) + z.shape[1:]) for name, z in seen.items()}
    return (x + y.reshape(b, t, d), ck, cv, tail,
            expert.reshape(b, t), load, seen)


def _decode_block(x, layer, stacks, layer_idx, cache_k, cache_v, tails,
                  lengths, seen_rows, live, cfg: ZayaConfig):
    """x [B,d], one token a slot; the whole K, V and tail arrays
    carried: slot b's new row is written at ``lengths[b]`` (llama's
    scatter), a LIVE slot's tail replaced by this token's, then ONE
    kernel call for all slots over each one's first ``seen_rows[b]``
    rows (``ops/decode_attention.py``, G query heads a KV head)."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    u = _mm("bd,dc->bc", h, layer["w_qk"])
    w = _mm("bd,dc->bc", h, layer["w_v"])
    tail = lax.dynamic_index_in_dim(tails, layer_idx, 0, keepdims=False)
    u_prev, a_prev, w_prev = _tail_parts(tail, cfg)
    half = cfg.value_dim // 2
    a = _conv0(u, u_prev, layer)
    q, k, v = _heads(u, a, a_prev, _value(w, w_prev, cfg), layer, lengths,
                     cfg)
    new_tail = jnp.concatenate([u, a, w[..., half:]], axis=-1)
    tails = lax.dynamic_update_index_in_dim(
        tails, jnp.where(live[:, None], new_tail, tail), layer_idx, 0)
    cache_k = _write_rows(cache_k, layer_idx, lengths, k)
    cache_v = _write_rows(cache_v, layer_idx, lengths, v)
    attn = decode_attention(
        q, cache_k, cache_v, seen_rows, layer=layer_idx, layout="bksd",
        interpret=cfg.interpret_kernels)
    x = x + _mm("bc,cd->bd", attn.reshape(x.shape[0], -1),
                layer["w_o"])
    y, expert, load, seen = moe_ffn(x, layer, stacks, layer_idx, cfg)
    return x + y, cache_k, cache_v, tails, expert, load, seen


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: ZayaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Rows and tail in one cache (this module's header)."""
    dt = dtype or cfg.dtype
    rows = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
            "tail": jnp.zeros((cfg.n_layers, batch, cfg.tail_dim), F32)}


def _head(x, params):
    """x [.., d] -> float32 logits over the tied embedding's rows."""
    return _mm("...d,vd->...v", x, params["embed"])


def _prefill(params, tokens, cache, cache_index, last, cfg: ZayaConfig):
    """-> (x [B,T,d] after the final norm, cache, counters, seen): ONE
    scan over the identical layers. ``cache`` holds one slot's rows and
    tail, so the scan takes its arrays in and stacks them out."""
    b, t = tokens.shape
    cache_index = jnp.asarray(cache_index, jnp.int32)
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    valid = (None if last is None
             else jnp.broadcast_to(jnp.arange(t) <= last, (b, t)))
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    stacks, scanned = split_expert_stacks(params["layers"])

    def body(x, xs):
        layer, idx, ck, cv, tail = xs
        x, ck, cv, tail, expert, load, seen = _prefill_block(
            x, layer, stacks, idx, ck, cv, tail, cache_index, positions,
            last, valid, cfg)
        return x, (ck, cv, tail, expert, load, seen)

    x, (k, v, tail, experts, load, seen) = lax.scan(
        body, x, (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32),
                  cache["k"], cache["v"], cache["tail"]))
    n_real = b * (t if last is None else jnp.asarray(last, jnp.int32) + 1)
    n_real = jnp.asarray(n_real, jnp.int32)
    counters = {
        "moe_prefill_tokens": n_real,
        "moe_prefill_load_max": jnp.sum(jnp.max(load, axis=-1)),
        "moe_prefill_load_mean": (cfg.n_layers / cfg.n_experts
                                  * n_real.astype(F32)),
        "state_resets": b * _starts_fresh(cache_index).astype(jnp.int32)}
    seen = dict(seen, experts=experts[..., None])        # [L,B,T,1]
    return (rms_norm(x, params["ln_out"], cfg.norm_eps),
            {"k": k, "v": v, "tail": tail}, counters, seen)


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: ZayaConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T), read from the slot's tail (zero at ``cache_index``
    0) -> (logits [B,T,V], cache, counters, seen): the functional
    prefill, whole-bucket logits in the cache's type."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        None, cfg)
    return _head(x, params).astype(cfg.dtype), cache, counters, seen


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: ZayaConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding (given to no expert, and past the tail) -> (logits
    [B,V] of row ``last``, cache, counters, seen)."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return _head(row, params), cache, counters, seen


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: ZayaConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters,
    seen). The layer loop CARRIES the three cache arrays: donated, the
    step rewrites a row a layer a slot and a tail a layer a live slot,
    and copies none. A slot that is not live (idle, frozen, or between
    two chunks of its prefill) keeps its tail; its K/V write lands
    where the engine parked it and its attention reads no row; its
    token is routed like any other (static shapes).
    ``moe_expert_hits`` counts, over the layers, the experts that at
    least one of the B tokens chose; ``moe_decode_load_max`` the
    largest group's tokens, summed over the layers; the
    ``decode_attn_*`` counters are llama's."""
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(F32)
    seen_rows, counters = decode_step_rows(lengths, live, cache["k"])
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))
    stacks, scanned = split_expert_stacks(params["layers"])

    def body(carry, xs):
        x, k, v, tails = carry
        layer, idx = xs
        x, k, v, tails, expert, load, seen = _decode_block(
            x, layer, stacks, idx, k, v, tails, lengths, seen_rows, live,
            cfg)
        return (x, k, v, tails), (expert, load, seen)

    (x, k, v, tails), (experts, load, seen) = lax.scan(
        body, (x, cache["k"], cache["v"], cache["tail"]),
        (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    counters = dict(
        counters,
        moe_layer_steps=jnp.int32(cfg.n_layers),
        moe_expert_hits=jnp.sum(load > 0, dtype=jnp.int32),
        moe_decode_load_max=jnp.sum(jnp.max(load, axis=-1)))
    seen = {name: z[:, :, None] for name, z in seen.items()}
    seen["experts"] = experts[:, :, None, None]             # [L,B,1,1]
    return (_head(x, params), {"k": k, "v": v, "tail": tails}, counters,
            seen)


def forward(params: Params, tokens: jnp.ndarray,
            cfg: ZayaConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
