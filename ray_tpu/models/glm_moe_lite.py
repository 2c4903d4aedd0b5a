"""GLM-4.7-Flash-class decoder (``glm4_moe_lite``): multi-head latent
attention (MLA) over a LATENT cache and dropless sigmoid-routed experts,
served through the engine's model seam (``serve/engine/README.md``).

Pre-norm residual blocks, RMSNorm, a final norm and an untied head. The
first ``n_dense_layers`` blocks carry a dense SwiGLU, the rest a routed
expert layer with one shared expert.

**MLA, two orders of one mathematics.** Per token the cache holds
``c_kv`` (the latent after its norm, ``kv_lora_rank`` values) and
``k_rope`` (ONE rotary key for all heads, after RoPE):

- *prefill, expanded*: ``[k_nope_h | v_h] = c_kv W_ukv`` gives every
  head its keys and values; attention is ordinary causal attention with
  head size ``qk_nope + qk_rope`` (the flash kernel on the TPU). A
  prefill at ``cache_index`` 0 attends to its own tokens; any other
  (prefix reuse, a check that reads back through the cache) expands the
  slot's cached rows instead.
- *decode, absorbed*: ``q~_h = q_nope_h W_uk,h^T`` and
  ``o_h = (Σ p c_kv) W_uv,h``, so the step attends over the latent rows
  themselves: multi-query attention with one key of
  ``kv_lora_rank + qk_rope`` columns whose first ``kv_lora_rank`` are
  also the value (``ops/mla_decode.py``, each row read once).

**The cache** is ``{"kv": [L, B, S, W]}``, slot axis second. ``W`` is
the row padded up to a multiple of 128 lanes (576 -> 640 at the
published sizes: 1,280 B a token a layer in bf16 beside the published
1,152). Left at 576 the chip's compiler keeps the array with S minor
and the kernel, which wants the row minor, re-lays the whole cache out
on every call (compiled for a described v5e, PR 29); the pad columns
are zero in the cache and in the query.

**Experts, without drops.** ``s = sigmoid(x W_r)`` in float32; the
``n_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the
router's correction bias; one group, so no group limit), weighted by
the UNBIASED scores ``g = scale * s / (Σ s + 1e-20)``. The chosen
(token, expert) pairs are sorted by expert and multiplied group by group
with ``jax.lax.ragged_dot`` (the chip's compiler has a grouped-matmul
kernel for it; elsewhere it is a masked dense product, fine at test
sizes): no capacity, so no pair is dropped however skewed the routing.
Padding tokens of a prefill bucket are given to no expert.

What the engine's seam asks of this module: `init_params`,
`init_kv_cache`, `forward_with_cache`, `decode_step_with_cache`; and,
because a ``[1, bucket, 154880]`` logits array is 0.3 to 1.27 GB,
`forward_last_with_cache` for the tick's prefill (one row of logits).
Each of the three returns ``(logits, cache, counters, seen)``:
``counters`` are scalars that ride the fetch the tick makes anyway,
``seen`` (each token's chosen experts) is what a check against a
reference reads, returned by the functional programs only.
`ENGINE_REFUSES` names what the latent cache cannot do yet, and
`SPAN_ATTRS` the counters that the engine's request spans carry.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict

import jax
from jax import lax
import jax.numpy as jnp

from ray_tpu.ops import (
    apply_rope,
    blockwise_attention,
    causal_attention,
    full_causal_attention,
    mla_decode_attention,
    rms_norm,
)

Params = Dict[str, Any]
F32 = jnp.float32

# Engine options this family's cache cannot serve yet, each with its
# reason; `InferenceEngine` refuses them at construction.
ENGINE_REFUSES = {
    "quantize": "models/quant.py quantizes llama's weight tree only",
    "paged_decode": "ops/paged_decode.py reads K and V pages of one "
                    "width; the latent cache is one array whose value is "
                    "part of its key",
    "spec_draft_len": "verify_chunk vmaps forward_with_cache over llama's "
                      "{k, v} cache",
    "role": "export_page/install_page carry k_page and v_page",
    "kv_fleet": "kv_fleet.pack_page carries k_page and v_page",
}
# Fetched counter -> the attribute under which the request's span
# (``engine.prefill``, ``engine.decode_chunk``) carries it.
SPAN_ATTRS = {"moe_prefill_load_max": "experts_max_load",
              "moe_expert_hits": "experts_touched"}


@dataclasses.dataclass(frozen=True)
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    d_model: int = 2048
    n_layers: int = 47
    n_dense_layers: int = 1          # ``first_k_dense_replace``
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240                # the dense layers' SwiGLU
    moe_d_ff: int = 1536             # one expert's (and the shared one's)
    n_experts: int = 64
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    max_seq_len: int = 202752
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Run the decode kernel under the Pallas interpreter off the TPU
    # (tests); otherwise the kernel on the TPU, its jnp reference off it.
    interpret_decode_kernel: bool = False

    def __post_init__(self):
        if self.qk_head_dim != self.v_head_dim:
            raise ValueError(
                "the expanded path runs one attention kernel over keys and "
                f"values of one head size: qk {self.qk_head_dim} != v "
                f"{self.v_head_dim}")
        if not 0 < self.n_dense_layers < self.n_layers:
            raise ValueError("need at least one dense and one expert layer")

    @property
    def model(self):
        """The module the serving engine asks for this family's cache,
        prefill and decode step."""
        return sys.modules[__name__]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def cache_row_values(self) -> int:
        """What a token's cache row means: latent + shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        """What it occupies: padded to whole 128-lane tiles."""
        return -(-self.cache_row_values // 128) * 128

    @property
    def attn_scale(self) -> float:
        return self.qk_head_dim ** -0.5


# Parameters ---------------------------------------------------------------

def init_params(cfg: GlmMoeLiteConfig, key: jax.Array) -> Params:
    """Random normal, fan-in scaled, norm gains stored as offsets from
    one. The dense and the expert layers are two stacks (each scanned).
    Storage conventions (the reference undoes them): projections split
    by head, ``kv_b_proj`` kept as its key half ``w_uk`` and its value
    half ``w_uv``, matrices stored input-major."""
    d, h, dt = cfg.d_model, cfg.n_heads, cfg.dtype
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, hv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    e, f, fs = (cfg.n_experts, cfg.moe_d_ff,
                cfg.moe_d_ff * cfg.n_shared_experts)
    keys = iter(jax.random.split(key, 32))

    def norm(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(dtype)

    def attn(n):
        return {
            "ln_attn": jnp.zeros((n, d), dt),
            "w_dq": norm((n, d, rq), d),
            "ln_q": jnp.zeros((n, rq), dt),
            "w_uq": norm((n, rq, h, nope + rope), rq),
            "w_dkv": norm((n, d, rkv + rope), d),
            "ln_kv": jnp.zeros((n, rkv), dt),
            "w_uk": norm((n, rkv, h, nope), rkv),
            "w_uv": norm((n, rkv, h, hv), rkv),
            "w_o": norm((n, h, hv, d), h * hv),
            "ln_mlp": jnp.zeros((n, d), dt),
        }

    nd, nm = cfg.n_dense_layers, cfg.n_moe_layers
    return {
        "embed": norm((cfg.vocab_size, d), d),
        "dense": dict(attn(nd),
                      w_gate=norm((nd, d, cfg.d_ff), d),
                      w_up=norm((nd, d, cfg.d_ff), d),
                      w_down=norm((nd, cfg.d_ff, d), cfg.d_ff)),
        "moe": dict(attn(nm),
                    router=norm((nm, d, e), d),
                    # Small beside the scores' spread (sigmoid of about
                    # N(0,1): 0.8 over 64 experts), so that the load
                    # stays even as a trained bias keeps it; as large
                    # as the gap between the k-th score and the next,
                    # so that selecting on s + b differs from on s.
                    router_bias=0.02 * jax.random.normal(next(keys),
                                                         (nm, e), F32),
                    w_gate=norm((nm, e, d, f), d),
                    w_up=norm((nm, e, d, f), d),
                    w_down=norm((nm, e, f, d), f),
                    ws_gate=norm((nm, d, fs), d),
                    ws_up=norm((nm, d, fs), d),
                    ws_down=norm((nm, fs, d), fs)),
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm((d, cfg.vocab_size), d),
    }


# Experts ------------------------------------------------------------------

def route(x, router, bias, cfg: GlmMoeLiteConfig):
    """x [T, d] -> (experts [T, k] int32, gates [T, k] float32): chosen
    on ``s + b``, weighted by ``s``."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router,
                                  preferred_element_type=F32))
    _, experts = lax.top_k(s + bias.astype(F32), cfg.n_experts_per_tok)
    gates = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), gates * cfg.routed_scaling_factor


def _swiglu(x, w_gate, w_up, w_down):
    gate = jnp.einsum("td,df->tf", x, w_gate)
    up = jnp.einsum("td,df->tf", x, w_up)
    return jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up, w_down)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def expert_stacks(moe: Params) -> Params:
    """The expert layers' matrices as ONE run of groups, [Lm * E, ..]: a
    free view of the stacked parameters. The grouped product takes the
    whole of it and finds a layer's experts by their group sizes (all
    other groups are empty), so no layer's 1.2 GB is sliced out of the
    stack first: a slice feeding a kernel is a copy, and at decode those
    copies took more of the step than everything else in it (v5e
    trace, PR 29). The layer scans close over this, and scan the rest."""
    return {k: moe[k].reshape((-1,) + moe[k].shape[2:])
            for k in EXPERT_STACKS}


def moe_ffn(x, layer, stacks, layer_idx, cfg: GlmMoeLiteConfig, valid=None):
    """x [T, d] -> (y [T, d], experts [T, k], load [E]); ``layer`` holds
    the router and the shared expert of expert layer ``layer_idx``,
    ``stacks`` every expert layer's experts (`expert_stacks`).

    Dropless: the T*k chosen pairs are sorted by expert and each group
    multiplied by its expert's matrices; ``load[e]`` is the group's
    size. ``valid`` [T] (a prefill bucket's real tokens) keeps padding
    out of every group: such pairs sort last, past the groups' total,
    and their rows are zeroed."""
    t, d = x.shape
    k, e = cfg.n_experts_per_tok, cfg.n_experts
    experts, gates = route(x, layer["router"], layer["router_bias"], cfg)
    flat = experts.reshape(t * k)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)
    n_groups = stacks["w_gate"].shape[0]
    # layer_idx < n_groups / e by construction (the scan's own index).
    sizes = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        jnp.zeros((n_groups,), jnp.int32), load, (layer_idx * e,))
    xs = jnp.take(x, order // k, axis=0)                     # [T*k, d]
    hidden = (jax.nn.silu(lax.ragged_dot(xs, stacks["w_gate"], sizes))
              * lax.ragged_dot(xs, stacks["w_up"], sizes))
    ys = lax.ragged_dot(hidden, stacks["w_down"], sizes)     # [T*k, d]
    if valid is not None:
        ys = jnp.where((jnp.take(flat, order) < e)[:, None], ys, 0)
    back = jnp.argsort(order)                # pair i sits at row back[i]
    y = jnp.take(ys, back, axis=0).reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", y.astype(F32), gates).astype(x.dtype)
    shared = _swiglu(x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    return y + shared, experts, load


def _ffn(h, layer, moe, cfg, valid=None):
    """The block's second half on h [B, T, d]: the dense SwiGLU, or the
    expert layer where ``moe`` = (the experts' stacks, this layer's
    index among the expert layers) is given."""
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    if moe is None:
        y = _swiglu(flat, layer["w_gate"], layer["w_up"], layer["w_down"])
        return y.reshape(b, t, d), None, None
    y, experts, load = moe_ffn(flat, layer, *moe, cfg,
                               None if valid is None else valid.reshape(-1))
    return y.reshape(b, t, d), experts.reshape(b, t, -1), load


# Attention ----------------------------------------------------------------

def _queries_and_row(h, layer, positions, cfg: GlmMoeLiteConfig):
    """h [B,T,d] (normed) -> q_nope [B,T,H,nope], q_rope [B,T,H,rope]
    (rotated), row [B,T,W]: the token's cache row ``c_kv ++ k_rope ++ 0``."""
    c_q = rms_norm(jnp.einsum("btd,dr->btr", h, layer["w_dq"]),
                   layer["ln_q"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bthk", c_q, layer["w_uq"])
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    ckr = jnp.einsum("btd,dr->btr", h, layer["w_dkv"])
    c_kv = rms_norm(ckr[..., :cfg.kv_lora_rank], layer["ln_kv"],
                    cfg.norm_eps)
    k_rope = apply_rope(ckr[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]
    pad = jnp.zeros(ckr.shape[:-1] + (cfg.cache_row_dim
                                      - cfg.cache_row_values,), ckr.dtype)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope, pad], axis=-1)


def _expand(rows, layer, cfg: GlmMoeLiteConfig):
    """Cache rows [B,S,W] -> per-head keys [B,S,H,qk] and values
    [B,S,H,v]: the up-projections applied, the one rotary key shared."""
    c_kv = rows[..., :cfg.kv_lora_rank]
    k_rope = rows[..., cfg.kv_lora_rank:cfg.cache_row_values]
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, layer["w_uk"])
    v = jnp.einsum("bsr,rhv->bshv", c_kv, layer["w_uv"])
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              k_nope.shape[:3] + (cfg.qk_rope_head_dim,))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _prefill_block(x, layer, moe, cache_l, cache_index, positions, valid,
                   cfg: GlmMoeLiteConfig):
    """x [B,T,d], cache_l [B,S,W] (this layer's rows of the slot) ->
    (x, cache_l, experts | None, load | None). Expanded MLA."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    q_nope, q_rope, rows = _queries_and_row(h, layer, positions, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # cache_index + T is bounded by the engine's contract, as in
    # llama._block: the scheduler admits only what fits a slot's rows.
    cache_l = lax.dynamic_update_slice(  # rtpu-lint: disable=unclamped-dynamic-update-slice
        cache_l, rows.astype(cache_l.dtype), (0, cache_index, 0))

    def fresh(_):
        k, v = _expand(rows, layer, cfg)
        return full_causal_attention(q, k, v, scale=cfg.attn_scale)

    def through_the_cache(_):
        k, v = _expand(cache_l, layer, cfg)
        s = cache_l.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(s), (x.shape[0], s))
        attend = blockwise_attention if s >= 1024 else causal_attention
        return attend(q, k, v, q_positions=positions, kv_positions=kv_pos,
                      scale=cfg.attn_scale).astype(q.dtype)

    attn = lax.cond(cache_index == 0, fresh, through_the_cache, None)
    x = x + jnp.einsum("bthv,hvd->btd", attn, layer["w_o"]).astype(x.dtype)
    h = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    y, experts, load = _ffn(h, layer, moe, cfg, valid)
    return x + y.astype(x.dtype), cache_l, experts, load


def _write_rows(cache, layer_idx, lengths, rows):
    """rows [B,W] -> cache[layer_idx, b, lengths[b]] of the [L,B,S,W]
    cache: a scatter of W-wide rows into the free view [L*B, S, W], the
    form the chip's compiler updates in place (llama._write_rows, PR
    26). ``lengths`` is bounded by the engine's contract."""
    n_layers, b, s, w = cache.shape
    slots = layer_idx * b + jnp.arange(b, dtype=jnp.int32)
    flat = cache.reshape(n_layers * b, s, w)
    flat = flat.at[slots, lengths.astype(jnp.int32)].set(
        rows.astype(cache.dtype), unique_indices=True,
        indices_are_sorted=True)
    return flat.reshape(cache.shape)


def _decode_block(x, layer, moe, layer_idx, cache, lengths,
                  cfg: GlmMoeLiteConfig):
    """x [B,1,d], the whole [L,B,S,W] cache carried -> (x, cache,
    experts | None, load | None). Absorbed MLA: the step attends over
    the latent rows."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    q_nope, q_rope, rows = _queries_and_row(h, layer, lengths[:, None], cfg)
    cache = _write_rows(cache, layer_idx, lengths, rows[:, 0])
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], layer["w_uk"])
    pad = jnp.zeros(q_lat.shape[:2] + (cfg.cache_row_dim
                                       - cfg.cache_row_values,), q_lat.dtype)
    q = jnp.concatenate([q_lat, q_rope[:, 0], pad], axis=-1)     # [B,H,W]
    o_lat = mla_decode_attention(
        q, cache, (lengths + 1).astype(jnp.int32), layer=layer_idx,
        v_dim=cfg.kv_lora_rank, scale=cfg.attn_scale,
        interpret=cfg.interpret_decode_kernel)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, layer["w_uv"])
    x = x + jnp.einsum("bhv,hvd->bd", o, layer["w_o"])[:, None].astype(x.dtype)
    h = rms_norm(x, layer["ln_mlp"], cfg.norm_eps)
    y, experts, load = _ffn(h, layer, moe, cfg)
    return x + y.astype(x.dtype), cache, experts, load


def _split(moe: Params):
    """The expert layers' parameters as (what the grouped products read
    whole, what the layer scan slices a layer at a time)."""
    return expert_stacks(moe), {k: v for k, v in moe.items()
                                if k not in EXPERT_STACKS}


# The engine's seam --------------------------------------------------------

def init_kv_cache(cfg: GlmMoeLiteConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jnp.ndarray]:
    """The latent cache: ONE array [layers, slots, rows, W], no separate
    V (this module's header)."""
    shape = (cfg.n_layers, batch, max_len, cfg.cache_row_dim)
    return {"kv": jnp.zeros(shape, dtype or cfg.dtype)}


def _prefill(params, tokens, cache, cache_index, cfg, valid):
    """-> (x [B,T,d] after the final norm, cache, experts [Lm,B,T,k],
    load [Lm,E]). Two scans: the dense stack, then the expert stack."""
    b, t = tokens.shape
    positions = cache_index + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    nd = cfg.n_dense_layers

    stacks, scanned = _split(params["moe"])

    def body(x, xs):
        layer, rows, idx = xs
        x, rows, experts, load = _prefill_block(
            x, layer, None if idx is None else (stacks, idx), rows,
            cache_index, positions, valid, cfg)
        return x, (rows, experts, load)

    x, (dense_rows, _, _) = lax.scan(
        body, x, (params["dense"], cache["kv"][:nd], None))
    x, (moe_rows, experts, load) = lax.scan(
        body, x, (scanned, cache["kv"][nd:],
                  jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    return x, {"kv": jnp.concatenate([dense_rows, moe_rows])}, experts, load


def _prefill_counters(load, n_real, cfg: GlmMoeLiteConfig):
    """load [Lm, E] of a prefill's ``n_real`` routed tokens -> what the
    tick sums: the fullest expert's tokens over the expert layers, and
    what an even spread would give each."""
    n_real = jnp.asarray(n_real, jnp.int32)
    return {
        "moe_prefill_tokens": n_real,
        "moe_prefill_load_max": jnp.sum(jnp.max(load, axis=-1)),
        "moe_prefill_load_mean": (
            cfg.n_moe_layers * cfg.n_experts_per_tok / cfg.n_experts
            * n_real.astype(F32)),
    }


def forward_with_cache(params: Params, tokens: jnp.ndarray,
                       cache: Dict[str, jnp.ndarray], cache_index,
                       cfg: GlmMoeLiteConfig):
    """tokens [B,T] written at rows [cache_index, cache_index+T) ->
    (logits [B,T,V], cache, counters, {"experts": [Lm,B,T,k]}): the
    functional prefill, whole-bucket logits, every token routed."""
    b, t = tokens.shape
    x, cache, experts, load = _prefill(params, tokens, cache, cache_index,
                                       cfg, None)
    logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
    return (logits, cache, _prefill_counters(load, b * t, cfg),
            {"experts": experts})


def forward_last_with_cache(params: Params, tokens: jnp.ndarray,
                            cache: Dict[str, jnp.ndarray], cache_index,
                            last, cfg: GlmMoeLiteConfig):
    """The tick's prefill: ``tokens[:, :last+1]`` are real, the rest
    bucket padding (given to no expert). -> (logits [B,V] of row
    ``last``, cache, counters, {"experts": [Lm,B,T,k]}): one vocabulary
    row, not the bucket's."""
    b, t = tokens.shape
    valid = jnp.broadcast_to(jnp.arange(t) <= last, (b, t))
    x, cache, experts, load = _prefill(params, tokens, cache, cache_index,
                                       cfg, valid)
    row = lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    logits = jnp.einsum("bd,dv->bv", row, params["lm_head"])
    n_real = b * (jnp.asarray(last, jnp.int32) + 1)
    return (logits, cache, _prefill_counters(load, n_real, cfg),
            {"experts": experts})


def decode_step_with_cache(params: Params, tokens: jnp.ndarray,
                           cache: Dict[str, jnp.ndarray],
                           lengths: jnp.ndarray, cfg: GlmMoeLiteConfig):
    """One decode step for every slot: tokens [B,1], lengths [B] ->
    (logits [B,V], cache, counters, {"experts": [Lm,B,1,k]}). The layer
    loops CARRY the cache (llama.decode_step_with_cache): donated, the
    step rewrites one row a layer a slot and copies none.
    ``moe_expert_hits`` counts, over the expert layers, the experts that
    at least one of the B tokens chose (a frozen slot's token is
    computed like any other: static shapes); ``mla_decode_rows`` the
    cache rows a layer's attention is asked to read, Σ (lengths + 1)
    over the slots (an idle slot is parked at length 0: one row)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    nd = cfg.n_dense_layers

    stacks, scanned = _split(params["moe"])

    def body(carry, xs):
        x, kv = carry
        layer, idx, moe_idx = xs
        x, kv, experts, load = _decode_block(
            x, layer, None if moe_idx is None else (stacks, moe_idx), idx, kv,
            lengths, cfg)
        hits = (None if load is None
                else jnp.sum(load > 0, dtype=jnp.int32))
        return (x, kv), (experts, hits)

    (x, kv), _ = lax.scan(
        body, (x, cache["kv"]),
        (params["dense"], jnp.arange(nd, dtype=jnp.int32), None))
    (x, kv), (experts, hits) = lax.scan(
        body, (x, kv),
        (scanned, jnp.arange(nd, cfg.n_layers, dtype=jnp.int32),
         jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["ln_out"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"])
    counters = {"moe_layer_steps": jnp.int32(cfg.n_moe_layers),
                "moe_expert_hits": jnp.sum(hits),
                "mla_decode_rows": jnp.sum(lengths.astype(jnp.int32) + 1)}
    return logits, {"kv": kv}, counters, {"experts": experts}


def forward(params: Params, tokens: jnp.ndarray,
            cfg: GlmMoeLiteConfig) -> jnp.ndarray:
    """Full causal forward, no cache kept: tokens [B,T] -> logits."""
    b, t = tokens.shape
    cache = init_kv_cache(cfg, b, t)
    return forward_with_cache(params, tokens, cache, 0, cfg)[0]
