"""Granite-4.0-H-class decoder (``granitemoehybrid``, dense or with
routed experts in every block): Mamba-2 layers whose FIXED-SIZE state
lives beside the K/V rows of a few grouped-query attention layers in
the engine's one cache, served through the engine's model seam
(``serve/engine/README.md``).

``layer_kinds`` says which layers are Mamba-2 and which attention, in
any order (the published model: attention at layers 5, 15, 25, 35 of
40); the stack is walked a RUN of one kind at a time (`segments`), each
a scan over its layers. Pre-norm residual blocks with three published
multipliers, a final RMSNorm, the head TIED to the embedding. The
residual stream is float32 and every product takes its operands in the
weights' type (bf16) and accumulates in float32. ``x`` a block's input,
d the hidden size:

    x_0    = embedding_multiplier E[token]
    x      <- x + residual_multiplier Mixer(RMSNorm(x))
    [a; b] = RMSNorm(x) W_in
    x      <- x + residual_multiplier (silu(a) * b) W_out
    logits = RMSNorm(x) E^T / logits_scaling

With ``n_experts`` (Granite-4.0-H-Small: 72, ten a token) the block's
second half is routed experts BESIDE that SwiGLU (the published
"shared" part), in Mamba-2 and attention blocks alike; ``h`` the same
``RMSNorm(x)``:

    l        = h W_r                  float32 logits, operands bf16
    (l_k, e) = top_k(l)               the k largest LOGITS
    g        = softmax(l_k)           over the k chosen, not over all
    moe      = sum_i g_i (silu(h W_gate[e_i]) * (h W_up[e_i])) W_down[e_i]
    x       <- x + residual_multiplier (moe + (silu(a) * b) W_out)

(no bias, no scaling factor, no groups: `route`; ``models/common.route``
is the sigmoid families'). The experts are NOT in the two stacks of
layers: ``params["experts"]`` holds every block's, by the block's
place in ``layer_kinds``, and the grouped products read the whole of it
(``ops/grouped_experts.expert_stacks``: no layer's share sliced out),
closed over by the scans of both kinds of run. ``held = (first,
count)``: one chip's share of an expert-parallel layer, as
``models/kimi_linear.py`` takes it; the router keeps its width, the
softmax runs over all k chosen, and a pair on an absent expert adds
nothing here. Without experts every program of this module is what it
was before they came (``tests/test_granite_hybrid.py`` holds the
jaxprs).

*Mamba-2 layer* (H heads of P, state size N, one group; I = H P):
``[z (I); u (I + 2N); delta (H)] = h W_in``; ``u`` passes a causal
depthwise convolution over time of width 4 WITH A BIAS (zeros before
the sequence) and SiLU, and splits into ``[x (H x P); B (N); C (N)]``;
``dt = softplus(delta + dt_bias)``, ``a = -exp(A_log)``; the state
``S`` in R^{H x P x N}, float32, zero before the sequence
(``ops/mamba2.py``):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t
    Mixer(h)_t = RMSNorm_I(y_t * silu(z_t); w) W_out

(the gate BEFORE the norm, the norm over all I values).

*Attention layer*: ``q = h W_q`` (heads of ``head_dim``), ``k, v`` over
``n_kv_heads``; NO rotary embedding, no q/k norm; causal softmax of
``attention_multiplier q . k`` (the published 1/64 at head size 64, not
``64^-1/2``); ``W_o``. `decode_attention` and the prefill's attention
take the scale as ``head_dim^-1/2`` of what they are handed, so the
query is scaled before them: one mathematics.

**The cache** is rows AND state, slot axis second:

    k, v  [attention layers, B, KH/R, rows, R*head_dim]   a row a token
    ssm   [mamba layers, B, H/G, N, G*P]                  float32, no rows
    conv  [mamba layers, B, 3 * (I + 2N)]                 float32: the last
                                                          3 conv inputs

``k``, ``v``: R KV heads side by side in one row, R the fewest that
fill whole 128-lane tiles (2 at the published head size 64: a row of
one head would be padded to 128 lanes, and `decode_attention` slices
and pads such a layer out of the cache every call). A query head then
attends over its PAIR's rows with zeros in the other head's lanes: its
scores are its own head's, and of the output it keeps its own half.
Nothing in the kernel changes; it reads every row once at full lanes
(its products are twice as wide, which a step bound by memory does not
see). ``ssm``: ``ops/mamba2.py`` says why S^T of G heads lie side by
side. Prefill runs the chunked scan (`mamba2.chunk_scan`) from the
slot's state, or from ZERO where ``cache_index`` is 0: an admission
resets the slot inside the tick's prefill program. A bucket's padding
steps neither state nor conv tail (``dt = 0``; the tail taken at the
real length). Decode steps every LIVE slot's state where it lies
(`mamba2.mamba2_decode`, one Pallas call a Mamba layer for all slots).

`SLOT_STATE_KEYS`, `SPAN_ATTRS`: the engine's
contract for a family with per-slot state (``models/olmo_hybrid.py``);
the expert counters, which only a configuration with experts returns,
are ``models/kimi_linear.py``'s.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, Optional, Tuple

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import (_layer_of, _mm, _real, _starts_fresh,
                                   _write_rows)
# The other state families': a product with bf16 operands and a float32
# sum, a layer of a stack sliced where its products read it, whether a
# prefill starts a request (its slot's state is then not read) and which
# tokens of a bucket are real.
from ray_tpu.ops import (
    blockwise_attention,
    causal_attention,
    decode_attention,
    decode_step_rows,
    full_causal_attention,
    gated_delta,
    mamba2,
    rms_norm,
)
from ray_tpu.ops.decode_attention import LANES
from ray_tpu.ops.grouped_experts import (
    gated_sum,
    grouped_swiglu,
    split_expert_stacks,
)

Params = Dict[str, Any]
F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"

SLOT_STATE_KEYS = ("ssm", "conv")
# Fetched counter -> the attribute the request's span carries it under.
SPAN_ATTRS = {"state_resets": "state_reset",
              "mamba2_prefill_tokens": "mamba2_prefill_tokens",
              "moe_prefill_load_max": "experts_max_load",
              "moe_expert_hits": "experts_touched",
              "moe_pairs_held": "expert_pairs_held"}


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    d_model: int = 2048
    layer_kinds: Tuple[str, ...] = tuple(
        ATTENTION if i % 10 == 5 else MAMBA for i in range(40))
    n_heads: int = 32                # attention layers: query heads
    n_kv_heads: int = 8
    head_dim: int = 64
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    conv_width: int = 4
    mamba_chunk: int = 256
    d_ff: int = 8192                 # the SwiGLU every block has
    # Routed experts beside it in every block (0: none, the dense model).
    n_experts: int = 0               # the router's width
    n_experts_per_tok: int = 0
    d_expert: int = 0
    held: Optional[Tuple[int, int]] = None   # (first, count) held HERE
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    max_seq_len: int = 131072
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Run the decode kernels under the Pallas interpreter off the TPU
    # (tests); otherwise the kernels on the TPU, their jnp twins off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        unknown = set(self.layer_kinds) - {MAMBA, ATTENTION}
        if unknown:
            raise ValueError(f"layer kinds {sorted(unknown)} are not in "
                             "models/granite_hybrid.py")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        first, count = self.held_experts
        if (first < 0 or first + count > self.n_experts
                or self.n_experts_per_tok > self.n_experts
                or bool(self.n_experts) != bool(self.n_experts_per_tok)):
            raise ValueError(
                f"held {self.held} and {self.n_experts_per_tok} a token "
                f"of {self.n_experts} experts")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_kinds.count(MAMBA)

    @property
    def n_attention_layers(self) -> int:
        return self.layer_kinds.count(ATTENTION)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds: all, unless
        ``held`` names a share."""
        return self.held if self.held is not None else (0, self.n_experts)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_state

    @property
    def state_group(self) -> int:
        return mamba2.state_group(self.mamba_heads, self.mamba_head_dim)

    @property
    def kv_pack(self) -> int:
        """KV heads side by side in one cached row (this module's
        header): 1 where a head fills lane tiles by itself or no whole
        number of heads does."""
        r = LANES // self.head_dim if LANES % self.head_dim == 0 else 1
        return r if r > 1 and self.n_kv_heads % r == 0 else 1

    @property
    def segments(self):
        """Runs of one kind in ``layer_kinds``: (kind, the run's first
        layer in its kind's stack, layers)."""
        out, seen = [], {MAMBA: 0, ATTENTION: 0}
        for kind in self.layer_kinds:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return [tuple(run) for run in out]


# Parameters ---------------------------------------------------------------

def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one; ``a_log`` and ``dt_bias`` as the Mamba-2 authors draw them (A
    uniform in [1, 16], dt log-uniform in [1e-3, 0.1], ``dt_bias`` its
    inverse softplus), ``d_skip`` ones, all three float32. Two stacks,
    ``mamba`` [mamba layers, ..] and ``attention`` [attention layers,
    ..], layer i of ``layer_kinds`` being the next of its kind;
    matrices input-major. The published ``in_proj`` of a Mamba layer
    (columns ``z ++ u ++ delta``) is TWO matrices, ``w_in`` (``z ++ u``)
    and ``w_dt`` (``delta``): together they are 66.5 lane tiles wide,
    and the chip's compiler copied the whole stack into a layout of
    its own at the head of every decode chunk (1.26 GB: compiled for a
    described v5e, PR 46).

    With experts, ``experts`` [layers, ..] by the block's place in
    ``layer_kinds``: ``router`` [d, n_experts] and the HELD experts'
    ``w_gate``, ``w_up`` [held, d, d_expert], ``w_down`` [held,
    d_expert, d], drawn after everything else (the dense model's draws
    are what they were)."""
    d, dt, f = cfg.d_model, cfg.dtype, cfg.d_ff
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mh, inner, c = cfg.mamba_heads, cfg.d_inner, cfg.conv_channels
    keys = iter(jax.random.split(key, 32))

    def norm(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def mlp(lead):
        return {"ln_mix": jnp.zeros(lead + (d,), dt),
                "ln_mlp": jnp.zeros(lead + (d,), dt),
                "w_ff_in": norm(lead + (d, 2 * f), d),
                "w_ff_out": norm(lead + (f, d), f)}

    mam, att = (cfg.n_mamba_layers,), (cfg.n_attention_layers,)
    step = jnp.exp(jax.random.uniform(
        next(keys), mam + (mh,), F32, jnp.log(1e-3), jnp.log(0.1)))
    params = {
        "embed": norm((cfg.vocab_size, d), d),
        "mamba": dict(
            mlp(mam),
            w_in=norm(mam + (d, inner + c), d),
            w_dt=norm(mam + (d, mh), d),
            conv_w=norm(mam + (c, cfg.conv_width), cfg.conv_width),
            conv_b=norm(mam + (c,), cfg.conv_width, F32),
            a_log=jnp.log(jax.random.uniform(next(keys), mam + (mh,), F32,
                                             1.0, 16.0)),
            # softplus(dt_bias) = the drawn step.
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            d_skip=jnp.ones(mam + (mh,), F32),
            ln_gate=jnp.zeros(mam + (inner,), dt),
            w_out=norm(mam + (inner, d), inner)),
        "attention": dict(
            mlp(att),
            wq=norm(att + (d, h, hd), d), wk=norm(att + (d, kh, hd), d),
            wv=norm(att + (d, kh, hd), d), wo=norm(att + (h, hd, d), h * hd)),
        "ln_out": jnp.zeros((d,), dt),
    }
    if cfg.n_experts:
        every = (cfg.n_layers,)
        held, fe = every + (cfg.held_experts[1],), cfg.d_expert
        params["experts"] = {
            "router": norm(every + (d, cfg.n_experts), d),
            "w_gate": norm(held + (d, fe), d),
            "w_up": norm(held + (d, fe), d),
            "w_down": norm(held + (fe, d), fe)}
    return params


# The two halves of a block ------------------------------------------------

def route(n, router, cfg: GraniteHybridConfig):
    """n [T, d] -> (experts [T, k] int32, gates [T, k] float32, their
    logits [T, k]): the k largest LOGITS (float32 sums of products in
    the weights' type), and a softmax over those k alone."""
    top, experts = lax.top_k(_mm("td,de->te", n, router),
                             cfg.n_experts_per_tok)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1), top


def moe_ffn(n, router, stacks, layer_idx, cfg: GraniteHybridConfig,
            valid=None):
    """n [T, d] float32 (the normed stream) -> (y [T, d] float32,
    {experts [T, k], load [held], logits [T, k]: the chosen experts'}):
    block ``layer_idx``'s routed experts, dropless; a pair on an expert
    this chip does not hold adds nothing, and its gate stays in the
    softmax."""
    experts, gates, top = route(n, router, cfg)
    y, load = grouped_swiglu(
        n.astype(cfg.dtype), experts, stacks, layer_idx, cfg.n_experts,
        valid, held=cfg.held, interpret=cfg.interpret_kernels or None)
    return (gated_sum(y, gates),
            {"experts": experts, "load": load, "logits": top})


def _after(x, mixed, layer, cfg: GraniteHybridConfig, moe=None, valid=None):
    """The mixer's branch added, then the feed-forward half, both
    scaled by ``residual_multiplier``: the SwiGLU, and beside it the
    routed experts where ``moe`` = (router, expert stacks, the block's
    place) gives them (``valid`` [..]: the tokens that reach an
    expert). The residual stream is float32. -> (x, None | {experts,
    logits [..,k], load [held]})."""
    x = x + cfg.residual_multiplier * mixed
    n = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    ab = _mm("...d,df->...f", n, layer["w_ff_in"])
    a, b = jnp.split(ab, 2, axis=-1)
    y = _mm("...f,fd->...d", jax.nn.silu(a) * b, layer["w_ff_out"])
    if moe is None:
        return x + cfg.residual_multiplier * y, None
    routed, about = moe_ffn(n.reshape(-1, n.shape[-1]), *moe, cfg,
                            None if valid is None else valid.reshape(-1))
    a_token = lambda a: a.reshape(y.shape[:-1] + (-1,))
    return (x + cfg.residual_multiplier * (routed.reshape(y.shape) + y),
            dict(about, experts=a_token(about["experts"]),
                 logits=a_token(about["logits"])))


def _mamba_in(x, layer, cfg: GraniteHybridConfig):
    """x [..,d] -> (z [..,I], u [..,I+2N] before the convolution, dt
    [..,H] after the softplus)."""
    h = rms_norm(x, layer["ln_mix"], cfg.norm_eps)
    z, u = jnp.split(_mm("...d,dc->...c", h, layer["w_in"]), [cfg.d_inner],
                     axis=-1)
    delta = _mm("...d,dh->...h", h, layer["w_dt"])
    return z, u, jax.nn.softplus(delta + layer["dt_bias"])


def _conv_out(y, layer, cfg: GraniteHybridConfig):
    """The convolved channels [..,I+2N] -> (x [..,H,P], B, C [..,N]),
    float32: the bias, SiLU and the split."""
    y = jax.nn.silu(y.astype(F32) + layer["conv_b"])
    x, bm, cm = jnp.split(
        y, [cfg.d_inner, cfg.d_inner + cfg.mamba_state], axis=-1)
    return (x.reshape(x.shape[:-1] + (cfg.mamba_heads, cfg.mamba_head_dim)),
            bm, cm)


def _mamba_out(y, x, z, layer, cfg: GraniteHybridConfig):
    """y, x [..,H,P] float32, z [..,I] -> the mixer's output [..,d]: the
    skip, the gate, THEN the norm over all I values."""
    y = y + layer["d_skip"][:, None] * x
    y = y.reshape(z.shape) * jax.nn.silu(z)
    return _mm("...i,id->...d", rms_norm(y, layer["ln_gate"], cfg.norm_eps),
               layer["w_out"])


def _mamba_prefill_block(x, layer, ssm_l, conv_l, cache_index, last,
                         cfg: GraniteHybridConfig, moe=None, routed=None):
    """x [B,T,d]; ssm_l [B,H/G,N,G*P], conv_l [B,3C]: the slot's ->
    (x, ssm_l, conv_l, what `_after` says of the experts), both as they
    stand after the last REAL token."""
    fresh = _starts_fresh(cache_index)
    valid, n_real = _real(x.shape[1], last)
    z, u, dt = _mamba_in(x, layer, cfg)
    if valid is not None:
        dt = jnp.where(valid[None, :, None], dt, 0.0)
    y, tail = gated_delta.causal_conv(
        u, jnp.where(fresh, 0, conv_l), layer["conv_w"], n_real)
    xs, bm, cm = _conv_out(y, layer, cfg)
    group = cfg.state_group
    state = jnp.where(fresh, 0.0, mamba2.unpack_state(ssm_l, group))
    y, state = mamba2.chunk_scan(xs, dt, -jnp.exp(layer["a_log"]), bm, cm,
                                 state, chunk=cfg.mamba_chunk)
    x, about = _after(x, _mamba_out(y, xs, z, layer, cfg), layer, cfg, moe,
                      routed)
    return (x, mamba2.pack_state(state, group), tail.astype(conv_l.dtype),
            about)


def _mamba_decode_block(x, layer, layer_idx, ssm, conv, live,
                        cfg: GraniteHybridConfig, moe=None):
    """x [B,d]; the whole ``ssm`` and ``conv`` arrays carried; a slot
    that is not ``live`` keeps both as they are."""
    z, u, dt = _mamba_in(x, layer, cfg)
    tail = lax.dynamic_index_in_dim(conv, layer_idx, 0, keepdims=False)
    y, new_tail = gated_delta.causal_conv_step(u, tail, layer["conv_w"])
    conv = lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[:, None], new_tail.astype(tail.dtype), tail),
        layer_idx, 0)
    xs, bm, cm = _conv_out(y, layer, cfg)
    y, ssm = mamba2.mamba2_decode(
        ssm, layer_idx, xs, jnp.where(live[:, None], dt, 0.0),
        -jnp.exp(layer["a_log"]), bm, cm, interpret=cfg.interpret_kernels)
    x, about = _after(x, _mamba_out(y, xs, z, layer, cfg), layer, cfg, moe)
    return x, ssm, conv, about


def _qkv(x, layer, scale: float, cfg: GraniteHybridConfig):
    """x [..,d] -> q [..,H,hd] times ``scale`` (float32 until then: one
    rounding), k, v [..,KH,hd], in the type the cache holds."""
    h = rms_norm(x, layer["ln_mix"], cfg.norm_eps)
    dt = cfg.dtype
    return ((_mm("...d,dhk->...hk", h, layer["wq"]) * scale).astype(dt),
            _mm("...d,dhk->...hk", h, layer["wk"]).astype(dt),
            _mm("...d,dhk->...hk", h, layer["wv"]).astype(dt))


def _packed(rows, cfg: GraniteHybridConfig):
    """k or v [..,KH,hd] -> [..,KH/R,R*hd]: neighbours side by side."""
    r = cfg.kv_pack
    return rows.reshape(rows.shape[:-2] + (cfg.n_kv_heads // r,
                                           r * cfg.head_dim))


def _attention_prefill_block(x, layer, ck, cv, cache_index, positions,
                             cfg: GraniteHybridConfig, moe=None, routed=None):
    """x [B,T,d]; ck, cv [B,KH/R,S,R*hd]: the slot's rows of this
    layer."""
    # The attention functions scale by head_dim^-1/2 unless told.
    q, k, v = _qkv(x, layer, 1.0, cfg)
    # cache_index + T is bounded by the engine's contract, as in
    # llama._block: the scheduler admits only what fits a slot's rows.
    ck = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        ck, _packed(k, cfg).swapaxes(1, 2).astype(ck.dtype),
        (0, 0, cache_index, 0))
    cv = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        cv, _packed(v, cfg).swapaxes(1, 2).astype(cv.dtype),
        (0, 0, cache_index, 0))
    scale = cfg.attention_multiplier

    def fresh(_):
        return full_causal_attention(q, k, v, scale=scale)

    def through_the_cache(_):
        b, _, s, _ = ck.shape
        rows = lambda c: c.swapaxes(1, 2).reshape(
            b, s, cfg.n_kv_heads, cfg.head_dim)
        kv_pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        attend = blockwise_attention if s >= 1024 else causal_attention
        return attend(q, rows(ck), rows(cv), q_positions=positions,
                      kv_positions=kv_pos, scale=scale).astype(q.dtype)

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    mixed = _mm("bthk,hkd->btd", attn, layer["wo"])
    x, about = _after(x, mixed, layer, cfg, moe, routed)
    return x, ck, cv, about


def _attention_decode_block(x, layer, layer_idx, cache_k, cache_v, lengths,
                            seen, cfg: GraniteHybridConfig, moe=None):
    """x [B,d]; the whole K and V arrays carried: slot b's new row is
    written at ``lengths[b]`` (llama's scatter), then ONE kernel call
    for all slots over each one's first ``seen[b]`` rows. The query of
    a head stands in its own head's lanes of the packed row, zeros in
    its neighbours'; the kernel divides by the root of the row's WIDTH,
    so the query carries ``attention_multiplier`` times that root."""
    r, hd = cfg.kv_pack, cfg.head_dim
    b = x.shape[0]
    q, k, v = _qkv(x, layer, cfg.attention_multiplier * (r * hd) ** 0.5, cfg)
    cache_k = _write_rows(cache_k, layer_idx, lengths, _packed(k, cfg))
    cache_v = _write_rows(cache_v, layer_idx, lengths, _packed(v, cfg))
    # [B, rows, R, query heads of a KV head, hd] x [R, R]: head e of a
    # row keeps lanes [e hd, (e+1) hd).
    own = jnp.eye(r, dtype=q.dtype)
    q = q.reshape(b, cfg.n_kv_heads // r, r, -1, hd)
    q = (q[:, :, :, :, None, :] * own[:, None, :, None]).reshape(
        b, cfg.n_heads, r * hd)
    attn = decode_attention(
        q, cache_k, cache_v, seen, layer=layer_idx, layout="bksd",
        interpret=cfg.interpret_kernels)
    attn = attn.reshape(b, cfg.n_kv_heads // r, r, -1, r, hd)
    attn = jnp.einsum("bjegfk,ef->bjegk", attn, own).reshape(
        b, cfg.n_heads, hd)
    mixed = _mm("bhk,hkd->bd", attn, layer["wo"])
    x, about = _after(x, mixed, layer, cfg, moe)
    return x, cache_k, cache_v, about


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: GraniteHybridConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """Rows and state in one cache (this module's header)."""
    dt = dtype or cfg.dtype
    r, group = cfg.kv_pack, cfg.state_group
    rows = (cfg.n_attention_layers, batch, cfg.n_kv_heads // r, max_len,
            r * cfg.head_dim)
    return {
        "k": jnp.zeros(rows, dt), "v": jnp.zeros(rows, dt),
        "ssm": jnp.zeros(
            (cfg.n_mamba_layers, batch, cfg.mamba_heads // group,
             cfg.mamba_state, group * cfg.mamba_head_dim), F32),
        # float32 like the products it holds: decode then convolves
        # what prefill convolved.
        "conv": jnp.zeros((cfg.n_mamba_layers, batch,
                           (cfg.conv_width - 1) * cfg.conv_channels), F32)}


def _embed(params, tokens, cfg: GraniteHybridConfig):
    return (jnp.take(params["embed"], tokens, axis=0).astype(F32)
            * cfg.embedding_multiplier)


def _head(x, params, eq: str, cfg: GraniteHybridConfig):
    """The tied head: ``eq`` contracts d with the embedding's rows."""
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    return _mm(eq, x, params["embed"]) / cfg.logits_scaling


def _scan_runs(params, carry, mamba, attention, cfg: GraniteHybridConfig):
    """``carry`` through every run of ``cfg.segments``, each a scan of
    ``mamba(carry, idx, moe)`` or ``attention(carry, idx, moe)`` over the
    run's layers (``idx`` in its kind's stack) -> (carry, what the
    blocks say of their experts, [layers, ..] in the order of
    ``layer_kinds``; None without experts). ``moe`` is what `_after`
    takes: the block's router, the expert stacks (closed over whole by
    every run), the block's place among all layers."""
    # ONE body a kind, whatever the number of its runs: a scan traces a
    # body it has met once.
    if not cfg.n_experts:
        bodies = {MAMBA: lambda c, idx: mamba(c, idx, None),
                  ATTENTION: lambda c, idx: attention(c, idx, None)}
        for kind, first, n in cfg.segments:
            carry, _ = lax.scan(bodies[kind], carry,
                                first + jnp.arange(n, dtype=jnp.int32))
        return carry, None
    stacks, scanned = split_expert_stacks(params["experts"])

    def routed(body):
        def block(c, xs):
            idx, at = xs
            return body(c, idx, (_layer_of(scanned, at)["router"], stacks, at))
        return block

    bodies = {MAMBA: routed(mamba), ATTENTION: routed(attention)}
    about, place = [], 0
    for kind, first, n in cfg.segments:
        run = jnp.arange(n, dtype=jnp.int32)
        carry, out = lax.scan(bodies[kind], carry, (first + run, place + run))
        about.append(out)
        place += n
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *about)


def _prefill(params, tokens, cache, cache_index, last,
             cfg: GraniteHybridConfig):
    """-> (x [B,T,d] before the final norm, cache, counters[, seen]): a
    scan over the layers of each run of one kind, the cache's arrays
    carried and each layer's slice rewritten. ``cache`` holds ONE
    slot's rows and state. With experts the counters gain
    ``models/kimi_linear.py``'s ``moe_*`` and ``seen`` = {``experts``,
    ``logits`` [layers, B, T, k]: the chosen and their router logits}
    follows."""
    b, t = tokens.shape
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    # The bucket's real tokens [B,T]: padding reaches no expert.
    valid = _real(t, last)[0] if cfg.n_experts else None
    routed = None if valid is None else jnp.broadcast_to(valid, (b, t))
    at = lambda a, i: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    put = lambda a, row, i: lax.dynamic_update_index_in_dim(a, row, i, 0)

    def mamba(carry, idx, moe):
        x, k, v, ssm, conv = carry
        x, ssm_l, conv_l, about = _mamba_prefill_block(
            x, _layer_of(params["mamba"], idx), at(ssm, idx), at(conv, idx),
            cache_index, last, cfg, moe, routed)
        return (x, k, v, put(ssm, ssm_l, idx), put(conv, conv_l, idx)), about

    def attention(carry, idx, moe):
        x, k, v, ssm, conv = carry
        x, ck, cv, about = _attention_prefill_block(
            x, _layer_of(params["attention"], idx), at(k, idx), at(v, idx),
            cache_index, positions, cfg, moe, routed)
        return (x, put(k, ck, idx), put(v, cv, idx), ssm, conv), about

    carry = (_embed(params, tokens, cfg), cache["k"], cache["v"],
             cache["ssm"], cache["conv"])
    (x, k, v, ssm, conv), moe = _scan_runs(params, carry, mamba, attention,
                                           cfg)
    cache = {"k": k, "v": v, "ssm": ssm, "conv": conv}
    counters = {
        "mamba2_prefill_tokens": b * _real(t, last)[1],
        "state_resets": b * _starts_fresh(cache_index).astype(jnp.int32)}
    if moe is None:
        return x, cache, counters
    n_real = jnp.asarray(counters["mamba2_prefill_tokens"], jnp.int32)
    counters.update({
        "moe_prefill_tokens": n_real,
        "moe_prefill_load_max": jnp.sum(jnp.max(moe["load"], axis=-1)),
        # What an even router gives each of its experts, held or not.
        "moe_prefill_load_mean": (
            cfg.n_layers * cfg.n_experts_per_tok / cfg.n_experts
            * n_real.astype(F32)),
        "moe_prefill_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32),
        "moe_pairs_routed": cfg.n_layers * cfg.n_experts_per_tok * n_real,
        "moe_pairs_held": jnp.sum(moe["load"]).astype(jnp.int32)})
    return x, cache, counters, {"experts": moe["experts"],
                                "logits": moe["logits"]}


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: GraniteHybridConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T) and scanned from the slot's state (zero at
    ``cache_index`` 0) -> (logits [B,T,V], cache, counters[, seen])."""
    x, *rest = _prefill(params, tokens, cache,
                        jnp.asarray(cache_index, jnp.int32), None, cfg)
    return (_head(x, params, "btd,vd->btv", cfg).astype(cfg.dtype), *rest)


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: GraniteHybridConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding that steps no state and reaches no expert ->
    (logits [B,V] of row ``last``, cache, counters[, seen])."""
    x, *rest = _prefill(params, tokens, cache,
                        jnp.asarray(cache_index, jnp.int32), last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return (_head(row, params, "bd,vd->bv", cfg), *rest)


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: GraniteHybridConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters[,
    seen]).
    The scans CARRY the four cache arrays: donated, the step rewrites a
    row an attention layer a slot and a state a Mamba layer a live
    slot, and copies none. A slot that is not live (idle, frozen, or
    between two chunks of its prefill) keeps its state and conv tail;
    its K/V write lands where the engine parked it and its attention
    reads no row. ``mamba2_slot_steps`` counts the states stepped: live
    slots x Mamba layers; the ``decode_attn_*`` counters are llama's.
    With experts, over EVERY slot's token (a frozen slot's is routed
    like any other: static shapes) ``moe_layer_steps``,
    ``moe_expert_hits`` (held experts that a pair fell on, summed over
    the layers), ``moe_pairs_routed``, ``moe_pairs_held``, and ``seen``
    = {``experts``, ``logits`` [layers, B, 1, k]}."""
    x = _embed(params, tokens, cfg)[:, 0]
    seen, counters = decode_step_rows(lengths, live, cache["k"])
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))

    def mamba(carry, idx, moe):
        x, k, v, ssm, conv = carry
        x, ssm, conv, about = _mamba_decode_block(
            x, _layer_of(params["mamba"], idx), idx, ssm, conv, live, cfg,
            moe)
        return (x, k, v, ssm, conv), about

    def attention(carry, idx, moe):
        x, k, v, ssm, conv = carry
        x, k, v, about = _attention_decode_block(
            x, _layer_of(params["attention"], idx), idx, k, v, lengths,
            seen, cfg, moe)
        return (x, k, v, ssm, conv), about

    carry = (x, cache["k"], cache["v"], cache["ssm"], cache["conv"])
    (x, k, v, ssm, conv), moe = _scan_runs(params, carry, mamba, attention,
                                           cfg)
    counters["mamba2_slot_steps"] = (jnp.sum(live, dtype=jnp.int32)
                                     * cfg.n_mamba_layers)
    out = (_head(x, params, "bd,vd->bv", cfg),
           {"k": k, "v": v, "ssm": ssm, "conv": conv}, counters)
    if moe is None:
        return out
    counters.update({
        "moe_layer_steps": jnp.int32(cfg.n_layers),
        "moe_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32),
        "moe_pairs_routed": jnp.int32(cfg.n_layers * x.shape[0]
                                      * cfg.n_experts_per_tok),
        "moe_pairs_held": jnp.sum(moe["load"]).astype(jnp.int32)})
    return out + ({"experts": moe["experts"][:, :, None],
                   "logits": moe["logits"][:, :, None]},)


def forward(params: Params, tokens: jnp.ndarray,
            cfg: GraniteHybridConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
