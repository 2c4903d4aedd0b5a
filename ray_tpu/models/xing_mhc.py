"""Xing4.0-class decoder (``xing4_0``) as ONE CHIP'S SHARE of an
expert-parallel replica, served through the engine's model seam
(``serve/engine/README.md``): latent attention under YaRN and
sigmoid-routed experts, every sub-layer inside a residual of FOUR
STREAMS (mHC, ``ops/mhc.py``).

**The residual.** Between layers a token carries ``X`` in R^{n x C}
(``n`` = ``hc_mult`` = 4), float32; ``X_0`` is the token's embedding
repeated ``n`` times, and after the last layer the ``n`` rows are
summed, normed and read by the untied head. Each layer has two
sub-layers ``F`` (attention; then the dense SwiGLU in the first
``n_dense_layers`` layers, the expert layer after), each with its own
pre-norm gain and its own mHC parameters:

    x, maps = mhc_pre(X)              # H_pre X, and the three maps
    y       = F(RMSNorm_g(x))
    X'      = mhc_post(X, y, maps)    # H_res X + H_post^T y

so the layer scans CARRY ``[B, T, n C]`` (the streams side by side in
the lanes: ``ops/mhc.py`` says why flat) where every other family
carries ``[B, T, C]``; a sub-layer's two mixes are the Pallas kernels
``rtpu_mhc_pre`` and ``rtpu_mhc_post`` over B x T rows (a decode
step's slots and a prefill bucket's tokens alike), their ``jnp`` twins
off the TPU.

**Attention** is DeepSeek-V3's MLA: ``q = W_uq RMSNorm(W_dq h)`` split
``nope | rope`` a head; ``[c | k_r] = W_dkv h``, ``c~ = RMSNorm(c)``,
one rotary key for all heads; ``[k_nope | v] = c~ W_ukv`` a head. The
rotary frequencies are YaRN's (`ops.rotary.YarnScaling`: the fast ones
kept, the slow ones divided by ``factor``, a ramp between) and the
softmax scale is ``qk^-1/2`` x ``mscale^2``. The cache row, the
expanded prefill and the absorbed decode are `models/kimi_linear.py`'s
(`mla_prefill_attend`, `mla_decode_attend`: a row is ``c~ ++ one
shared key``, here rotated; qk 192 beside v 128), the row written as
`glm_moe_lite` writes it.

**Experts**: `common.route` (sigmoid + correction bias, gates
normalised over all the chosen and scaled, float32 at the chip's
highest precision) ranks all ``n_experts``; this chip multiplies the
pairs that fall on the experts it HOLDS (``held_experts``,
`kimi_linear.moe_ffn`) and adds one shared expert; what the absent
experts would add is left out. The vocabulary may be a slice. Every
product takes bf16 operands and accumulates in float32; the streams,
the maps and the Sinkhorn passes are float32.

**The cache** is ``{"kv": [L, B, S, 640]}``, slot axis second: a latent
row a token a layer (512 + 64 values padded to whole 128-lane tiles),
`glm_moe_lite`'s. It holds no state, so the engine's prefix cache
works as for that family.

What the engine's seam asks: `init_params`, `init_kv_cache`,
`forward_with_cache`, `forward_last_with_cache` (the tick's prefill:
one row of logits), `decode_step_with_cache`; each returns ``(logits,
cache, counters, seen)``: ``counters`` ride the fetch the tick makes
anyway (summed from call to call, but for those named in
`COUNTER_MAXES`, of which the largest is kept), ``seen`` (each token's
chosen experts; the FIRST sub-layer's maps, whose input no rounding has
touched; at the row the head reads, EVERY sub-layer's write-back, what
it read and what it handed on, and the last sub-layer's streams beside
what the final norm read of them) is what a check against a reference
reads, returned by the check's programs only.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, Optional, Tuple

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.models.common import (_layer_of, _mm, _real, _swiglu,
                                   mla_decode_attend, mla_prefill_attend)
from ray_tpu.models.kimi_linear import moe_ffn
from ray_tpu.ops import apply_rope, mhc, mla_step_rows, rms_norm
from ray_tpu.ops.grouped_experts import split_expert_stacks
from ray_tpu.ops.rotary import YarnScaling

Params = Dict[str, Any]
F32 = jnp.float32

# Fetched counter -> the attribute under which the request's span
# (``engine.prefill``, ``engine.decode_chunk``) carries it.
SPAN_ATTRS = {"mhc_prefill_rows": "mhc_rows",
              "mhc_step_rows": "mhc_rows",
              "moe_prefill_load_max": "experts_max_load",
              "moe_expert_hits": "experts_touched",
              "moe_pairs_held": "expert_pairs_held"}
# Counters of which the engine keeps the LARGEST (over a chunk's steps,
# over the calls of its life), where it sums every other.
COUNTER_MAXES = ("mhc_sinkhorn_err_max",)
# A layer's mHC parameters (``mhc_phi``, ``mhc_alpha``, ``mhc_bias``)
# each have a leading axis of 2: the attention sub-layer's, then the
# feed-forward's.
ATTN, FFN = 0, 1


@dataclasses.dataclass(frozen=True)
class XingMhcConfig:
    vocab_size: int = 131072
    d_model: int = 3584
    n_layers: int = 40
    n_dense_layers: int = 2              # ``first_k_dense_replace``
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 9216                     # the dense layers' SwiGLU
    moe_d_ff: int = 1024                 # one expert's (and the shared one's)
    n_experts: int = 64                  # the router's width, published
    held_experts: Tuple[int, int] = (0, 64)    # (first, count) held HERE
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    max_seq_len: int = 262144
    rope_theta: float = 1e4
    rope_scaling: Optional[YarnScaling] = YarnScaling(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    norm_eps: float = 1e-6
    mhc: mhc.MhcSpec = mhc.MhcSpec()
    dtype: Any = jnp.bfloat16
    # Run the kernels (the two mixes, the latent decode attention) under
    # the Pallas interpreter off the TPU (tests); otherwise the kernels
    # on the TPU, their jnp twins off it.
    interpret_kernels: bool = False

    def __post_init__(self):
        if not 0 < self.n_dense_layers < self.n_layers:
            raise ValueError("need at least one dense and one expert layer")
        first, count = self.held_experts
        if not (0 <= first and 0 < count
                and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} of "
                             f"{self.n_experts}")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_head_dim(self) -> int:
        """The head size the expanded path runs its one attention kernel
        at (`kimi_linear`'s: keys and values zero-padded to it)."""
        qk = self.qk_head_dim
        return qk if qk <= 128 else -(-qk // 128) * 128

    @property
    def cache_row_values(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        return -(-self.cache_row_values // 128) * 128

    @property
    def attn_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        return scale * (self.rope_scaling.softmax_mscale
                        if self.rope_scaling else 1.0)

    def rotate(self, x, positions):
        """x [B,T,H,rope] at ``positions`` [B,T], by the scaled
        frequencies."""
        if self.rope_scaling is None:
            return apply_rope(x, positions, self.rope_theta)
        y = apply_rope(x, positions, self.rope_theta,
                       self.rope_scaling.frequencies(x.shape[-1],
                                                     self.rope_theta))
        return y * self.rope_scaling.rotation_mscale


# Parameters ---------------------------------------------------------------

def init_params(cfg: XingMhcConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one. The dense and the expert layers are two stacks (each scanned);
    projections split by head, matrices input-major, ``kv_b_proj`` kept
    as its key half ``w_uk`` and its value half ``w_uv``; the router,
    its bias and every mHC parameter float32.

    The mHC parameters are drawn so that the maps MOVE with the token
    and are far from the fixed point a trained model starts at
    (``ops/mhc.py`` has the layout): ``Phi`` fan-in scaled, so ``u
    Phi`` is about N(0, 1) a column; the three scalars uniform in [0.5,
    1.5]; ``b_pre``, ``b_post`` N(0, 0.5^2); ``B_res`` = 1.5 I + N(0,
    1): the logits of ``H_res`` spread over some +-3, a matrix that
    ONE Sinkhorn pass leaves several per cent from doubly stochastic
    and twenty bring to it, whose diagonal leads without being the
    identity."""
    d, h, dt = cfg.d_model, cfg.n_heads, cfg.dtype
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    n, m = cfg.mhc.n, cfg.mhc.n_maps
    e, f, fs = (cfg.held_experts[1], cfg.moe_d_ff,
                cfg.moe_d_ff * cfg.n_shared_experts)
    keys = iter(jax.random.split(key, 64))

    def norm(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def block(layers):
        eye = jnp.concatenate([jnp.zeros(2 * n), 1.5 * jnp.eye(n).ravel()])
        spread = jnp.concatenate([jnp.full(2 * n, 0.5), jnp.ones(n * n)])
        return {
            "ln_attn": jnp.zeros((layers, d), dt),
            "w_dq": norm((layers, d, rq), d),
            "ln_q": jnp.zeros((layers, rq), dt),
            "w_uq": norm((layers, rq, h, cfg.qk_head_dim), rq),
            "w_dkv": norm((layers, d, cfg.cache_row_values), d),
            "ln_kv": jnp.zeros((layers, rkv), dt),
            "w_uk": norm((layers, rkv, h, cfg.qk_nope_head_dim), rkv),
            "w_uv": norm((layers, rkv, h, cfg.v_head_dim), rkv),
            "w_o": norm((layers, h, cfg.v_head_dim, d), h * cfg.v_head_dim),
            "ln_mlp": jnp.zeros((layers, d), dt),
            "mhc_phi": norm((layers, 2, m, n * d), n * d, F32),
            "mhc_alpha": jax.random.uniform(next(keys), (layers, 2, 3), F32,
                                            0.5, 1.5),
            "mhc_bias": eye + spread * jax.random.normal(
                next(keys), (layers, 2, m), F32),
        }

    nd, nm = cfg.n_dense_layers, cfg.n_moe_layers
    return {
        "embed": norm((cfg.vocab_size, d), d),
        "dense": dict(block(nd),
                      w_gate=norm((nd, d, cfg.d_ff), d),
                      w_up=norm((nd, d, cfg.d_ff), d),
                      w_down=norm((nd, cfg.d_ff, d), cfg.d_ff)),
        "moe": dict(block(nm),
                    router=norm((nm, d, cfg.n_experts), d, F32),
                    # `glm_moe_lite.init_params` has the reason for 0.02.
                    router_bias=0.02 * jax.random.normal(
                        next(keys), (nm, cfg.n_experts), F32),
                    w_gate=norm((nm, e, d, f), d),
                    w_up=norm((nm, e, d, f), d),
                    w_down=norm((nm, e, f, d), f),
                    ws_gate=norm((nm, d, fs), d),
                    ws_up=norm((nm, d, fs), d),
                    ws_down=norm((nm, fs, d), fs)),
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm((d, cfg.vocab_size), d),
    }


# A sub-layer inside the streams ---------------------------------------------

def _mixed(streams, layer, sub: int, f, cfg: XingMhcConfig, probe):
    """One sub-layer ``f`` (the normed input [B,T,C] float32 -> (y
    [B,T,C], whatever it reports)) inside the streams [B,T,n C] ->
    (streams, the report, the maps [B*T, 128] it ran under, and of row
    ``probe`` of T what the write-back read and what it HANDED ON:
    ``y`` [B,C] as the mix reads it, ``maps`` [B, 2n + n^2], ``after``
    [B,n C])."""
    b, t, wide = streams.shape
    c = wide // cfg.mhc.n
    rows = streams.reshape(b * t, wide)
    x, maps = mhc.mhc_pre(
        rows, layer["mhc_phi"][sub], layer["mhc_alpha"][sub],
        layer["mhc_bias"][sub], spec=cfg.mhc, interpret=cfg.interpret_kernels)
    y, about = f(x.reshape(b, t, c))
    streams = mhc.mhc_post(rows, y.reshape(b * t, c), maps, spec=cfg.mhc,
                           interpret=cfg.interpret_kernels
                           ).reshape(b, t, wide)
    row = lambda a: lax.dynamic_index_in_dim(a.reshape(b, t, -1), probe,
                                             axis=1, keepdims=False)
    return streams, about, maps, {
        "y": row(y.astype(F32)), "maps": row(maps[:, :cfg.mhc.n_maps]),
        "after": row(streams)}


def _queries_and_row(h, layer, positions, cfg: XingMhcConfig):
    """h [B,T,C] (normed) -> q [B,T,H,qk] (its rope part rotated) and
    row [B,T,W]: the token's cache row ``c~ ++ k_rope ++ 0``, both in
    the cache's type."""
    c_q = rms_norm(_mm("btd,dr->btr", h, layer["w_dq"]), layer["ln_q"],
                   cfg.norm_eps)
    q = _mm("btr,rhk->bthk", c_q, layer["w_uq"])
    nope, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.concatenate([q[..., :nope],
                         cfg.rotate(q[..., nope:], positions)], axis=-1)
    ckr = _mm("btd,dr->btr", h, layer["w_dkv"])
    c_kv = rms_norm(ckr[..., :rkv], layer["ln_kv"], cfg.norm_eps)
    k_rope = cfg.rotate(ckr[..., None, rkv:], positions)[..., 0, :]
    pad = jnp.zeros(ckr.shape[:-1] + (cfg.cache_row_dim
                                      - cfg.cache_row_values,), F32)
    row = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
    return q.astype(cfg.dtype), row.astype(cfg.dtype)


def _ffn(layer, moe, valid, cfg: XingMhcConfig):
    """The feed-forward sub-layer as `_mixed` takes it: the dense
    SwiGLU, or the expert layer where ``moe`` = (the experts' stacks,
    this layer's index among the expert layers) is given."""
    def f(x):
        b, t, c = x.shape
        n = rms_norm(x, layer["ln_mlp"], cfg.norm_eps).reshape(b * t, c)
        if moe is None:
            y = _swiglu(n, layer["w_gate"], layer["w_up"], layer["w_down"])
            return y.reshape(b, t, c), None
        y, experts, load = moe_ffn(
            n, layer, *moe, cfg, None if valid is None else valid.reshape(-1))
        return (y.reshape(b, t, c),
                {"experts": experts.reshape(b, t, -1), "load": load})
    return f


def _report(about, maps_attn, maps_ffn, mix_attn, mix_ffn,
            cfg: XingMhcConfig):
    """What a layer's scan step hands out: the expert layer's report,
    the layer's largest Sinkhorn error and its two write-backs at the
    probed row (`_mixed`; each [2, B, ..]: attention, feed-forward); of
    a dense layer, the attention sub-layer's maps besides."""
    n = cfg.mhc.n
    out = {"err": jnp.maximum(mhc.sinkhorn_error(maps_attn, n),
                              mhc.sinkhorn_error(maps_ffn, n)),
           "mix": jax.tree.map(lambda a, f: jnp.stack([a, f]),
                               mix_attn, mix_ffn)}
    if about is None:
        return dict(out, maps=maps_attn[:, :cfg.mhc.n_maps])
    return dict(about, **out)


def _mixes(first, dense, moe):
    """``seen["mhc_mixes"]``: the streams that entered the first
    sub-layer at the probed row (``first`` [B, n C]) and every
    sub-layer's write-back there in the model's order (``y`` [L, 2, B,
    C], ``maps`` [L, 2, B, 2n + n^2], ``after`` [L, 2, B, n C]), from
    which a check computes ``H_res X + H_post^T y`` itself and holds
    what was handed on to it."""
    return dict(jax.tree.map(lambda d, m: jnp.concatenate([d, m]),
                             dense["mix"], moe["mix"]), first=first)


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: XingMhcConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """The latent cache: ONE array [layers, slots, rows, W]
    (`glm_moe_lite`'s)."""
    shape = (cfg.n_layers, batch, max_len, cfg.cache_row_dim)
    return {"kv": jnp.zeros(shape, dtype or cfg.dtype)}


def _scan_layers(params, carry, block, cfg: XingMhcConfig):
    """The dense stack's layers, then the expert stack's, each a scan;
    ``block(moe?)`` makes the body over (layer index, index in the
    stack). -> (carry, the dense layers' reports stacked, the expert
    layers')."""
    nd = cfg.n_dense_layers
    ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    carry, dense = lax.scan(block(False), carry, (ids[:nd], ids[:nd]))
    carry, moe = lax.scan(block(True), carry,
                          (ids[nd:], ids[:cfg.n_moe_layers]))
    return carry, dense, moe


def _embedded(params, tokens, cfg: XingMhcConfig):
    """tokens [B,T] -> X_0 [B,T,n C] float32: the embedding in every
    stream."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    return jnp.tile(x, (1, 1, cfg.mhc.n))


def _apart(streams, n: int):
    """[.., n C] -> [.., n, C]."""
    return streams.reshape(streams.shape[:-1] + (n, -1))


def _streams_out(streams, n: int):
    """[B,T,n C] -> [B,T,C]: the streams summed, what the final norm
    reads."""
    return jnp.sum(_apart(streams, n), axis=-2)


def _prefill(params, tokens, cache, cache_index, last, cfg: XingMhcConfig):
    """-> (x [B,T,C] after the streams' sum and the final norm, cache,
    counters, seen). The cache's one array is carried and each layer's
    slice rewritten. ``cache`` holds ONE slot's rows (or, off the
    engine, a batch's)."""
    b, t = tokens.shape
    cache_index = jnp.asarray(cache_index, jnp.int32)
    positions = cache_index + jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32), (b, t))
    valid, n_real = _real(t, last)
    routed = None if valid is None else jnp.broadcast_to(valid, (b, t))
    stacks, scanned = split_expert_stacks(params["moe"])
    at = lambda a, i: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    put = lambda a, row, i: lax.dynamic_update_index_in_dim(a, row, i, 0)
    end = t - 1 if last is None else last       # the row the head reads
    row = lambda a: lax.dynamic_index_in_dim(a, end, axis=1, keepdims=False)

    def block(moe: bool):
        def body(carry, xs):
            streams, kv = carry
            idx, s_idx = xs
            layer = _layer_of(scanned if moe else params["dense"], s_idx)

            def attention(x):
                h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
                q, rows = _queries_and_row(h, layer, positions, cfg)
                return mla_prefill_attend(q, rows, layer, at(kv, idx),
                                          cache_index, positions, cfg)

            streams, kv_l, maps_a, mix_a = _mixed(
                streams, layer, ATTN, attention, cfg, end)
            streams, about, maps_f, mix_f = _mixed(
                streams, layer, FFN,
                _ffn(layer, (stacks, s_idx) if moe else None, routed, cfg),
                cfg, end)
            return ((streams, put(kv, kv_l, idx)),
                    _report(about, maps_a, maps_f, mix_a, mix_f, cfg))
        return body

    first = _embedded(params, tokens, cfg)
    (streams, kv), dense, moe = _scan_layers(
        params, (first, cache["kv"]), block, cfg)
    n_real = jnp.asarray(b * n_real, jnp.int32)
    counters = {
        "mhc_prefill_rows": 2 * cfg.n_layers * n_real,
        "mhc_sinkhorn_err_max": jnp.maximum(jnp.max(dense["err"]),
                                            jnp.max(moe["err"])),
        "moe_prefill_tokens": n_real,
        "moe_prefill_load_max": jnp.sum(jnp.max(moe["load"], axis=-1)),
        # What an even router gives each of its experts, held or not.
        "moe_prefill_load_mean": (
            cfg.n_moe_layers * cfg.n_experts_per_tok / cfg.n_experts
            * n_real.astype(F32)),
        "moe_prefill_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32),
        "moe_pairs_routed": cfg.n_moe_layers * cfg.n_experts_per_tok * n_real,
        "moe_pairs_held": jnp.sum(moe["load"]).astype(jnp.int32)}
    read = _streams_out(streams, cfg.mhc.n)
    return (rms_norm(read, params["ln_out"], cfg.norm_eps), {"kv": kv},
            counters,
            {"experts": moe["experts"],                    # [Lm,B,T,k]
             "mhc_maps": dense["maps"][0].reshape(b, t, -1),
             "mhc_mixes": _mixes(row(first), dense, moe),
             "mhc_end": {"streams": _apart(row(streams), cfg.mhc.n),
                         "read": row(read)}})


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: XingMhcConfig):
    """tokens [B,T], all real, written at rows [cache_index,
    cache_index+T) -> (logits [B,T,V], cache, counters, seen): the
    functional prefill, whole-bucket logits, every token routed.
    ``seen``: ``experts`` [expert layers, B, T, k]; ``mhc_maps`` [B, T,
    2n + n^2], the first sub-layer's H_pre ++ H_post ++ vec(H_res);
    ``mhc_mixes`` (`_mixes`: every sub-layer's write-back at the last
    row); ``mhc_end``: the last row's ``streams`` [B, n, C] after the last
    layer and ``read`` [B, C], what the final norm read of them."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        None, cfg)
    logits = _mm("btd,dv->btv", x, params["lm_head"]).astype(cfg.dtype)
    return logits, cache, counters, seen


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: XingMhcConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding (given to no expert) -> (logits [B,V] of row
    ``last``, cache, counters, seen)."""
    x, cache, counters, seen = _prefill(params, tokens, cache, cache_index,
                                        last, cfg)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return _mm("bd,dv->bv", row, params["lm_head"]), cache, counters, seen


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: XingMhcConfig,
                           live=None):
    """One decode step for every slot: tokens [B,1], lengths [B],
    ``live`` [B] bool (None: all) -> (logits [B,V], cache, counters,
    seen). The scans CARRY the cache: donated, the step rewrites one
    latent row a layer a slot and copies none. A slot that is not live
    is stepped like any other (static shapes); its row lands where the
    engine parked it.

    Counters: ``mhc_step_rows`` (LIVE slots x sub-layers: the rows whose
    mixes somebody reads), ``mhc_sinkhorn_err_max`` (the largest |row
    or column sum - 1| of any H_res in this call; `COUNTER_MAXES`: the
    engine keeps the largest of them)
    and the routed counters as `kimi_linear` gives them, over every
    slot's token; ``mla_decode_rows`` and ``mla_decode_rows_streamed``
    as `glm_moe_lite` counts them."""
    b = tokens.shape[0]
    live = (jnp.ones(lengths.shape, bool) if live is None
            else live.astype(bool))
    stacks, scanned = split_expert_stacks(params["moe"])
    positions = lengths[:, None]

    def block(moe: bool):
        def body(carry, xs):
            streams, kv = carry
            idx, s_idx = xs
            layer = _layer_of(scanned if moe else params["dense"], s_idx)

            def attention(x):
                h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
                q, rows = _queries_and_row(h, layer, positions, cfg)
                o, new = mla_decode_attend(q[:, 0], rows[:, 0], layer, idx,
                                           kv, lengths, cfg)
                return o[:, None], new

            streams, kv, maps_a, mix_a = _mixed(streams, layer, ATTN,
                                                attention, cfg, 0)
            streams, about, maps_f, mix_f = _mixed(
                streams, layer, FFN,
                _ffn(layer, (stacks, s_idx) if moe else None, None, cfg), cfg,
                0)
            return (streams, kv), _report(about, maps_a, maps_f, mix_a,
                                          mix_f, cfg)
        return body

    first = _embedded(params, tokens, cfg)
    (streams, kv), dense, moe = _scan_layers(
        params, (first, cache["kv"]), block, cfg)
    read = _streams_out(streams, cfg.mhc.n)
    x = rms_norm(read, params["ln_out"], cfg.norm_eps)
    logits = _mm("bd,dv->bv", x[:, 0], params["lm_head"])
    counters = {
        "mhc_step_rows": 2 * cfg.n_layers * jnp.sum(live, dtype=jnp.int32),
        "mhc_sinkhorn_err_max": jnp.maximum(jnp.max(dense["err"]),
                                            jnp.max(moe["err"])),
        "moe_layer_steps": jnp.int32(cfg.n_moe_layers),
        "moe_expert_hits": jnp.sum(moe["load"] > 0, dtype=jnp.int32),
        "moe_pairs_routed": jnp.int32(cfg.n_moe_layers * b
                                      * cfg.n_experts_per_tok),
        "moe_pairs_held": jnp.sum(moe["load"]).astype(jnp.int32),
        **mla_step_rows(lengths, kv, cfg.n_heads)}
    return (logits, {"kv": kv}, counters,
            {"experts": moe["experts"],                    # [Lm,B,1,k]
             "mhc_maps": dense["maps"][0].reshape(b, 1, -1),
             "mhc_mixes": _mixes(first[:, 0], dense, moe),
             "mhc_end": {"streams": _apart(streams[:, 0], cfg.mhc.n),
                         "read": read[:, 0]}})


def forward(params: Params, tokens: jnp.ndarray,
            cfg: XingMhcConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    return forward_with_cache(params, tokens, init_kv_cache(cfg, b, t), 0,
                              cfg)[0]
