"""Olmo-Hybrid-class decoder (``olmo_hybrid``): gated delta-rule
(linear-attention) layers whose FIXED-SIZE state lives beside the K/V
rows of the full-attention layers in the engine's one cache, served
through the engine's model seam (``serve/engine/README.md``).

The stack is a PERIOD of ``linear_per_period`` linear layers and one
full layer, repeated. Post-norm residual blocks, a final RMSNorm, an
untied head. The residual stream is float32 and every product takes its
operands in the weights' type (bf16) and accumulates in float32: with
seeded weights this model amplifies a relative error 1.1 to 3.5 x a
layer, and a bf16 stream read twice as far from the float32 reference.
``x`` a block's input, d the hidden size:

    h   = x + RMSNorm(Mixer(x))
    out = h + RMSNorm(W_down (silu(W_gate h) * (W_up h)))

*Linear layer* (H heads of dk keys, dv values; the published gated
delta-rule layer). ``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v``; every
channel of the three passes a causal depthwise convolution over time of
width 4 (``y_t = sum_{i<4} c_i u_{t-3+i}``, zeros before the sequence)
and SiLU. Per head ``q_t = q^_t / ||q^_t|| * dk^-1/2``,
``k_t = k^_t / ||k^_t||``; ``beta_t = 2 sigmoid(x W_b)`` (the 2 is
``linear_allow_neg_eigval``); ``g_t = -exp(A_log) softplus(x W_a +
dt_bias)``, ``alpha_t = exp(g_t)``. The state ``S`` in R^{dv x dk},
float32, zero before the sequence:

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t
    y_t = RMSNorm_dv(o_t; w_o_norm) * silu(x W_g)        (per head)
    Mixer(x)_t = concat_h(y_t) W_o

*Full layer*: ``q, k, v = x W_q, x W_k, x W_v``; ``q <- RMSNorm_d(q)``,
``k <- RMSNorm_d(k)`` over the WHOLE projection before the split into
heads; no rotary embedding (the linear layers carry position); causal
softmax attention, scale head_dim^-1/2; ``W_o``.

**The cache** is rows AND state, slot axis second:

    k, v   [full layers, B, H, rows, head_dim]   a row a token
    state  [linear layers, B, H/G, dk, G*dv]     float32, no rows
    conv   [linear layers, B, 3 * C]             float32, the last 3 conv inputs

(``state``: ``ops/gated_delta.py`` says why S^T of G = 2 heads lie side
by side; ``conv`` flat so that its three rows are whole lane tiles and
not 3 sublanes padded to 16). Prefill runs the chunked scan
(`gated_delta.chunk_scan`) from the slot's state, or from ZERO where
``cache_index`` is 0: an admission resets the slot inside the tick's
prefill program, because a stale state, unlike a stale K/V row, is
masked by no length. A bucket's padding steps neither state nor conv
tail (``alpha = 1, beta = 0``; the tail taken at the real length).
Decode steps every LIVE slot's state where it lies
(`gated_delta.gdn_decode`, one Pallas call a linear layer for all
slots); the full layers call ``ops.decode_attention`` as llama does.

`SLOT_STATE_KEYS` tells the engine which cache entries are per-slot
state: the step then steps only the slots of its ``live`` mask (a slot
that is idle or between two chunks of its prefill must not be) and the
engine reuses no prefix (a freed slot holds the state at its LAST token, not at a shared
prefix's end). No `ENGINE_OFFERS`: no optional mechanism serves such a cache.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import (_layer_of, _mm, _real, _starts_fresh,
                                   _write_rows)
from ray_tpu.ops import (
    blockwise_attention,
    causal_attention,
    decode_attention,
    decode_step_rows,
    full_causal_attention,
    gated_delta,
    rms_norm,
)

Params = Dict[str, Any]
F32 = jnp.float32

# Cache entries that hold one state a slot and no rows (this module's
# header says what the engine does about them).
SLOT_STATE_KEYS = ("state", "conv")
# Fetched counter -> the attribute the request's span carries it under.
SPAN_ATTRS = {"state_resets": "state_reset"}


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    n_layers: int = 32
    linear_per_period: int = 3       # linear layers before each full one
    n_heads: int = 30                # full layers: MHA (kv heads = heads)
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_width: int = 4
    allow_neg_eigval: bool = True
    d_ff: int = 11008
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Run the decode kernels under the Pallas interpreter off the TPU
    # (tests); otherwise the kernels on the TPU, their jnp twins off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        if self.n_layers % (self.linear_per_period + 1):
            raise ValueError("n_layers must be whole periods of "
                             f"{self.linear_per_period} linear + 1 full")
        if self.d_model % self.n_heads:
            raise ValueError("n_heads must divide d_model")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        return self.n_layers // (self.linear_per_period + 1)

    @property
    def n_linear_layers(self) -> int:
        return self.n_periods * self.linear_per_period

    @property
    def conv_channels(self) -> int:
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    @property
    def state_group(self) -> int:
        return gated_delta.state_group(self.linear_heads,
                                       self.linear_value_dim)


# Parameters ---------------------------------------------------------------

def init_params(cfg: OlmoHybridConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one; ``A_log`` and ``dt_bias`` as the published layer draws them
    (A uniform in (0, 16], dt log-uniform in [1e-3, 0.1], kept float32).
    Two stacks: ``linear`` [linear layers, ..] and ``full`` [periods,
    ..]; projections split by head, matrices input-major."""
    d, dt, f = cfg.d_model, cfg.dtype, cfg.d_ff
    h, hd = cfg.n_heads, cfg.head_dim
    lh, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    keys = iter(jax.random.split(key, 32))

    def norm(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def mlp(lead):
        return {"ln_mix": jnp.zeros(lead + (d,), dt),
                "w_gate": norm(lead + (d, f), d),
                "w_up": norm(lead + (d, f), d),
                "w_down": norm(lead + (f, d), f),
                "ln_mlp": jnp.zeros(lead + (d,), dt)}

    lin, full = (cfg.n_linear_layers,), (cfg.n_periods,)
    step = jnp.exp(jax.random.uniform(
        next(keys), lin + (lh,), F32, jnp.log(1e-3), jnp.log(0.1)))
    return {
        "embed": norm((cfg.vocab_size, d), d),
        "linear": dict(
            mlp(lin),
            w_q=norm(lin + (d, lh, dk), d), w_k=norm(lin + (d, lh, dk), d),
            w_v=norm(lin + (d, lh, dv), d),
            conv_w=norm(lin + (cfg.conv_channels, cfg.conv_width),
                        cfg.conv_width),
            w_a=norm(lin + (d, lh), d), w_b=norm(lin + (d, lh), d),
            a_log=jnp.log(16.0 * (1.0 - jax.random.uniform(
                next(keys), lin + (lh,), F32))),
            # softplus(dt_bias) = the drawn step.
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            w_g=norm(lin + (d, lh, dv), d),
            ln_o=jnp.zeros(lin + (dv,), dt),
            w_o=norm(lin + (lh, dv, d), lh * dv)),
        "full": dict(
            mlp(full),
            wq=norm(full + (d, h, hd), d), wk=norm(full + (d, h, hd), d),
            wv=norm(full + (d, h, hd), d),
            ln_q=jnp.zeros(full + (d,), dt), ln_k=jnp.zeros(full + (d,), dt),
            wo=norm(full + (h, hd, d), d)),
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm((d, cfg.vocab_size), d),
    }


# The two halves of a block ------------------------------------------------

def _after(x, mixed, layer, cfg: OlmoHybridConfig):
    """``x + RMSNorm(mixed)``, then the SwiGLU half, post-normed too.
    The residual stream is float32 (this module's header)."""
    h = x + rms_norm(mixed, layer["ln_mix"], cfg.norm_eps)
    ff = jax.nn.silu(_mm("btd,df->btf", h, layer["w_gate"])) * _mm(
        "btd,df->btf", h, layer["w_up"])
    y = _mm("btf,fd->btd", ff, layer["w_down"])
    return h + rms_norm(y, layer["ln_mlp"], cfg.norm_eps)


def _linear_projections(x, layer, cfg: OlmoHybridConfig):
    """x [B,T,d] -> (u [B,T,C]: q~ ++ k~ ++ v~ before the convolution,
    g [B,T,H] (log decay), beta [B,T,H], gate [B,T,H,dv])."""
    b, t, _ = x.shape
    u = jnp.concatenate(
        [_mm("btd,dhk->bthk", x, layer[w]).reshape(b, t, -1)
         for w in ("w_q", "w_k", "w_v")], axis=-1)
    g = -jnp.exp(layer["a_log"]) * jax.nn.softplus(
        _mm("btd,dh->bth", x, layer["w_a"]) + layer["dt_bias"])
    beta = jax.nn.sigmoid(_mm("btd,dh->bth", x, layer["w_b"]))
    if cfg.allow_neg_eigval:
        beta = 2.0 * beta
    gate = _mm("btd,dhv->bthv", x, layer["w_g"])
    return u, g, beta, gate


def _heads(y, cfg: OlmoHybridConfig):
    """The convolved, SiLU'd channels [..,C] -> (q, k: unit length, q
    scaled; v), each [..,H,*] float32."""
    h, dk = cfg.linear_heads, cfg.linear_key_dim
    y = jax.nn.silu(y.astype(F32))
    q, k, v = jnp.split(y, [h * dk, 2 * h * dk], axis=-1)
    lead = y.shape[:-1]
    q = gated_delta.l2_normalize(q.reshape(lead + (h, dk))) * dk ** -0.5
    k = gated_delta.l2_normalize(k.reshape(lead + (h, dk)))
    return q, k, v.reshape(lead + (h, -1))


def _linear_out(o, gate, layer, cfg: OlmoHybridConfig):
    """o [..,H,dv] float32 -> the mixer's output [..,d]."""
    y = rms_norm(o, layer["ln_o"], cfg.norm_eps) * jax.nn.silu(gate)
    return _mm("...hv,hvd->...d", y, layer["w_o"])


def _linear_prefill_block(x, layer, state_l, conv_l, cache_index, last,
                          cfg: OlmoHybridConfig):
    """x [B,T,d]; state_l [B,H/G,dk,G*dv], conv_l [B,3C]: the slot's
    -> (x, state_l, conv_l), both as they stand after the last REAL
    token."""
    fresh = _starts_fresh(cache_index)
    valid, n_real = _real(x.shape[1], last)
    u, g, beta, gate = _linear_projections(x, layer, cfg)
    if valid is not None:
        g = jnp.where(valid[None, :, None], g, 0.0)
        beta = jnp.where(valid[None, :, None], beta, 0.0)
    y, tail = gated_delta.causal_conv(
        u, jnp.where(fresh, 0, conv_l), layer["conv_w"], n_real)
    q, k, v = _heads(y, cfg)
    group = cfg.state_group
    state = jnp.where(fresh, 0.0, gated_delta.unpack_state(state_l, group))
    o, state = gated_delta.chunk_scan(q, k, v, g, beta, state)
    x = _after(x, _linear_out(o, gate, layer, cfg), layer, cfg)
    return (x, gated_delta.pack_state(state, group),
            tail.astype(conv_l.dtype))


def _full_qkv(x, layer, cfg: OlmoHybridConfig):
    """x [B,T,d] -> q, k, v [B,T,H,hd]: q and k normed over the whole
    projection, no rotary."""
    b, t, d = x.shape
    shape = (b, t, cfg.n_heads, cfg.head_dim)
    q = _mm("btd,dhk->bthk", x, layer["wq"]).reshape(b, t, d)
    k = _mm("btd,dhk->bthk", x, layer["wk"]).reshape(b, t, d)
    v = _mm("btd,dhk->bthk", x, layer["wv"])
    dt = cfg.dtype      # what the cache holds and the kernels read
    return (rms_norm(q, layer["ln_q"], cfg.norm_eps).reshape(shape).astype(dt),
            rms_norm(k, layer["ln_k"], cfg.norm_eps).reshape(shape).astype(dt),
            v.astype(dt))


def _full_prefill_block(x, layer, ck, cv, cache_index, positions,
                        cfg: OlmoHybridConfig):
    """x [B,T,d]; ck, cv [B,H,S,hd]: the slot's rows of this layer."""
    q, k, v = _full_qkv(x, layer, cfg)
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
        kv_pos = jnp.broadcast_to(jnp.arange(s), (x.shape[0], s))
        attend = blockwise_attention if s >= 1024 else causal_attention
        return attend(q, ck.swapaxes(1, 2), cv.swapaxes(1, 2),
                      q_positions=positions,
                      kv_positions=kv_pos).astype(q.dtype)

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    mixed = _mm("bthk,hkd->btd", attn, layer["wo"])
    return _after(x, mixed, layer, cfg), ck, cv


def _linear_decode_block(x, layer, layer_idx, state, conv, live,
                         cfg: OlmoHybridConfig):
    """x [B,1,d]; the whole ``state`` and ``conv`` arrays carried; a
    slot that is not ``live`` keeps both as they are."""
    u, g, beta, gate = _linear_projections(x, layer, cfg)
    tail = lax.dynamic_index_in_dim(conv, layer_idx, 0, keepdims=False)
    y, new_tail = gated_delta.causal_conv_step(u[:, 0], tail,
                                               layer["conv_w"])
    conv = lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[:, None], new_tail.astype(tail.dtype), tail),
        layer_idx, 0)
    q, k, v = _heads(y, cfg)
    o, state = gated_delta.gdn_decode(
        state, layer_idx, q, k, v,
        jnp.where(live[:, None], g[:, 0], 0.0),
        jnp.where(live[:, None], beta[:, 0], 0.0),
        interpret=cfg.interpret_kernels)
    mixed = _linear_out(o, gate[:, 0], layer, cfg)[:, None]
    return _after(x, mixed, layer, cfg), state, conv


def _full_decode_block(x, layer, layer_idx, cache_k, cache_v, lengths, seen,
                       cfg: OlmoHybridConfig):
    """x [B,1,d]; the whole K and V arrays carried: slot b's new row is
    written at ``lengths[b]`` (llama's scatter), then ONE kernel call
    for all slots over each one's first ``seen[b]`` rows
    (``ops/decode_attention.py``, 30 KV heads, groups of one query
    head)."""
    q, k, v = _full_qkv(x, layer, cfg)
    cache_k = _write_rows(cache_k, layer_idx, lengths, k[:, 0])
    cache_v = _write_rows(cache_v, layer_idx, lengths, v[:, 0])
    attn = decode_attention(
        q[:, 0], cache_k, cache_v, seen, layer=layer_idx, layout="bksd",
        interpret=cfg.interpret_kernels)
    mixed = _mm("bhk,hkd->bd", attn, layer["wo"])[:, None]
    return _after(x, mixed, layer, cfg), cache_k, cache_v


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: OlmoHybridConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Rows and state in one cache (this module's header)."""
    dt = dtype or cfg.dtype
    group = cfg.state_group
    rows = (cfg.n_periods, batch, cfg.n_heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
        "state": jnp.zeros(
            (cfg.n_linear_layers, batch, cfg.linear_heads // group,
             cfg.linear_key_dim, group * cfg.linear_value_dim), F32),
        # float32 like the products it holds (53 MB at the cell's
        # sizes): decode then convolves what prefill convolved.
        "conv": jnp.zeros((cfg.n_linear_layers, batch,
                           (cfg.conv_width - 1) * cfg.conv_channels), F32)}


def _prefill(params, tokens, cache, cache_index, last,
             cfg: OlmoHybridConfig):
    """-> (x [B,T,d] after the final norm, cache, counters): a scan over
    periods, each a loop over its linear layers and then the full one.
    ``cache`` holds ONE slot's rows and state, so the loops scan its
    arrays in and stack them out."""
    b, t = tokens.shape
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    per = cfg.linear_per_period

    def linear(x, xs):
        idx, state_l, conv_l = xs
        x, state_l, conv_l = _linear_prefill_block(
            x, _layer_of(params["linear"], idx), state_l, conv_l,
            cache_index, last, cfg)
        return x, (state_l, conv_l)

    def period(x, xs):
        p, full, state_p, conv_p, ck, cv = xs
        x, (state_p, conv_p) = lax.scan(
            linear, x, (p * per + jnp.arange(per, dtype=jnp.int32),
                        state_p, conv_p))
        x, ck, cv = _full_prefill_block(x, full, ck, cv, cache_index,
                                        positions, cfg)
        return x, (state_p, conv_p, ck, cv)

    def by_period(a):       # [linear layers, ..] -> [periods, per, ..]
        return a.reshape((cfg.n_periods, per) + a.shape[1:])

    x, (state, conv, k, v) = lax.scan(
        period, x, (jnp.arange(cfg.n_periods, dtype=jnp.int32),
                    params["full"], by_period(cache["state"]),
                    by_period(cache["conv"]), cache["k"], cache["v"]))
    cache = {"k": k, "v": v,
             "state": state.reshape(cache["state"].shape),
             "conv": conv.reshape(cache["conv"].shape)}
    counters = {
        "gdn_prefill_tokens": b * _real(t, last)[1],
        "state_resets": b * _starts_fresh(cache_index).astype(jnp.int32)}
    return rms_norm(x, params["ln_out"], cfg.norm_eps), cache, counters


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: OlmoHybridConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T) and scanned from the slot's state (zero at
    ``cache_index`` 0) -> (logits [B,T,V], cache, counters)."""
    x, cache, counters = _prefill(params, tokens, cache,
                                  jnp.asarray(cache_index, jnp.int32),
                                  None, cfg)
    logits = _mm("btd,dv->btv", x, params["lm_head"]).astype(cfg.dtype)
    return logits, cache, counters


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: OlmoHybridConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding that steps no state -> (logits [B,V] of row
    ``last``, cache, counters)."""
    x, cache, counters = _prefill(params, tokens, cache,
                                  jnp.asarray(cache_index, jnp.int32),
                                  last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return _mm("bd,dv->bv", row, params["lm_head"]), cache, counters


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: OlmoHybridConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters).
    The loops CARRY the four cache arrays: donated, the step rewrites
    a row a full layer a slot and a state a linear layer a live slot,
    and copies none. A slot that is not live (idle, frozen, or between
    two chunks of its prefill) keeps its state and conv tail; its K/V
    write lands where the engine parked it and its attention reads no
    row. ``gdn_slot_steps`` counts the states stepped: live slots x
    linear layers; the ``decode_attn_*`` counters are llama's."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    seen, counters = decode_step_rows(lengths, live, cache["k"])
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))
    per = cfg.linear_per_period

    def linear(carry, idx):
        x, state, conv = carry
        return _linear_decode_block(x, _layer_of(params["linear"], idx),
                                    idx, state, conv, live, cfg), None

    def period(carry, xs):
        x, k, v, state, conv = carry
        full, p = xs
        (x, state, conv), _ = lax.scan(
            linear, (x, state, conv),
            p * per + jnp.arange(per, dtype=jnp.int32))
        x, k, v = _full_decode_block(x, full, p, k, v, lengths, seen, cfg)
        return (x, k, v, state, conv), None

    (x, k, v, state, conv), _ = lax.scan(
        period, (x, cache["k"], cache["v"], cache["state"], cache["conv"]),
        (params["full"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    logits = _mm("bd,dv->bv", x[:, 0], params["lm_head"])
    counters["gdn_slot_steps"] = (jnp.sum(live, dtype=jnp.int32)
                                  * cfg.n_linear_layers)
    return logits, {"k": k, "v": v, "state": state, "conv": conv}, counters


def forward(params: Params, tokens: jnp.ndarray,
            cfg: OlmoHybridConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
