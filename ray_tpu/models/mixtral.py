"""Mixtral-class sparse-MoE decoder: expert parallelism over the ``ep`` axis.

It DROPS tokens over its fixed capacity and has no serving path (no `forward_with_cache`); the dropless, served expert layer is `models/glm_moe_lite.py`'s.

TPU-first MoE (GShard/Switch pattern — static shapes, one-hot dispatch
einsums that run on the MXU): top-k routing with a fixed per-expert
capacity; overflow tokens fall through the residual (standard drop
behavior). Expert weights carry a leading ``experts`` dim sharded over
``ep`` (see `parallel/mesh.py` DEFAULT_RULES), so the dispatch/combine
einsums partition expert compute across the mesh with XLA-inserted
collectives. Attention + norms reuse the Llama block machinery
(`models/llama.py`); reference era equivalent: Ray orchestrates external
MoE models, it has none of this natively (SURVEY §2.4 EP row).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
from jax import lax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import llama
from ray_tpu.ops import apply_rope, rms_norm
from ray_tpu.parallel.mesh import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.02
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # `llama._remat_policy`'s names. Llama's default is "attention"; no
    # chip run has measured it here, so this one stays.
    remat_policy: str = "nothing"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def capacity(self, tokens: int) -> int:
        per = self.top_k * tokens / self.n_experts * self.capacity_factor
        return max(self.top_k, int(-(-per // 1)))  # ceil, >= top_k

    def param_count(self) -> int:
        d, f, v, l, e = (self.d_model, self.d_ff, self.vocab_size,
                         self.n_layers, self.n_experts)
        attn = d * self.n_heads * self.head_dim * 2 \
            + d * self.n_kv_heads * self.head_dim * 2
        moe = e * 3 * d * f + d * e
        return v * d + l * (attn + moe + 2 * d) + d + d * v

    def active_param_count(self) -> int:
        """Params touched per token (top_k experts) — the MoE speed story."""
        d, f, l = self.d_model, self.d_ff, self.n_layers
        attn = d * self.n_heads * self.head_dim * 2 \
            + d * self.n_kv_heads * self.head_dim * 2
        return self.vocab_size * d * 2 + l * (
            attn + self.top_k * 3 * d * f + d * self.n_experts + 2 * d)


MIXTRAL_8X7B = MixtralConfig()


def tiny_moe_config(**kw) -> MixtralConfig:
    base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=64, n_experts=4, top_k=2,
                max_seq_len=64, dtype=jnp.float32, remat=False)
    base.update(kw)
    return MixtralConfig(**base)


# ------------------------------------------------------------------ params

def param_logical_axes(cfg: MixtralConfig) -> Params:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "ln_attn": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "ln_moe": ("layers", "embed"),
            "w_router": ("layers", "embed", "experts"),
            "w_gate": ("layers", "experts", "embed", "mlp"),
            "w_up": ("layers", "experts", "embed", "mlp"),
            "w_down": ("layers", "experts", "mlp", "embed"),
        },
        "ln_out": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(cfg: MixtralConfig, key: jax.Array) -> Params:
    d, hd, h, kh, f, v, l, e = (cfg.d_model, cfg.head_dim, cfg.n_heads,
                                cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size,
                                cfg.n_layers, cfg.n_experts)
    keys = jax.random.split(key, 10)
    dt = cfg.dtype

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    return {
        "embed": norm(keys[0], (v, d), d),
        "blocks": {
            "ln_attn": jnp.zeros((l, d), dt),
            "wq": norm(keys[1], (l, d, h, hd), d),
            "wk": norm(keys[2], (l, d, kh, hd), d),
            "wv": norm(keys[3], (l, d, kh, hd), d),
            "wo": norm(keys[4], (l, h, hd, d), h * hd),
            "ln_moe": jnp.zeros((l, d), dt),
            "w_router": norm(keys[5], (l, d, e), d),
            "w_gate": norm(keys[6], (l, e, d, f), d),
            "w_up": norm(keys[7], (l, e, d, f), d),
            "w_down": norm(keys[8], (l, e, f, d), f),
        },
        "ln_out": jnp.zeros((d,), dt),
        "lm_head": norm(keys[9], (d, v), d),
    }


# ------------------------------------------------------------------ MoE ffn

def moe_ffn(x: jnp.ndarray, layer: Params, cfg: MixtralConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k capacity-dispatched expert FFN on x [B,S,D].

    Returns (out [B,S,D], aux_loss scalar). Dispatch/combine are one-hot
    einsums (MXU-friendly; GShard §3): tokens over capacity fall through
    with zero contribution (their residual path still carries them).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = cfg.capacity(t)
    xt = x.reshape(t, d)

    router_logits = jnp.einsum(
        "td,de->te", xt, layer["w_router"],
        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)          # [T,E] fp32
    gate_vals, gate_idx = lax.top_k(probs, k)               # [T,k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)   # renormalize

    # Load-balancing aux loss (Switch eq. 4): mean prob * mean assignment.
    me = jnp.mean(probs, axis=0)                            # [E]
    assign1 = jax.nn.one_hot(gate_idx[:, 0], e)             # top-1 counts
    ce = jnp.mean(assign1, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    # Capacity assignment: position of each (token, slot) within its
    # expert's buffer, counted in token order over all k slots.
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [T,k,E]
    flat = onehot.transpose(1, 0, 2).reshape(k * t, e)       # slot-major
    pos_in_e = jnp.cumsum(flat, axis=0) - flat               # [k*T,E]
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(k, t).T
    pos = pos.astype(jnp.int32)                              # [T,k]
    keep = pos < cap                                         # overflow drop
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # Dispatch tensor [T,E,C] — combines expert choice AND buffer slot.
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)     # [T,k,C]
    dispatch = jnp.einsum("tke,tkc->tec", onehot,
                          pos_oh * keep[..., None])          # 0/1
    combine = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh,
                         gate_vals.astype(jnp.float32))

    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           xt.astype(jnp.float32)).astype(cfg.dtype)
    expert_in = constrain(expert_in, ("experts", None, None))

    def ffn(w_gate, w_up, w_down, h):                        # [C,D] per e
        act = jax.nn.silu(h @ w_gate) * (h @ w_up)
        return act @ w_down

    expert_out = jax.vmap(ffn)(layer["w_gate"], layer["w_up"],
                               layer["w_down"], expert_in)   # [E,C,D]
    expert_out = constrain(expert_out, ("experts", None, None))
    out = jnp.einsum("tec,ecd->td", combine,
                     expert_out.astype(jnp.float32))
    return out.reshape(b, s, d).astype(x.dtype), aux


# ------------------------------------------------------------------ forward

def _moe_block(x, layer, positions, cfg: MixtralConfig,
               mesh: Optional[Mesh]):
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", h, layer["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", h, layer["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    kk = apply_rope(kk, positions, cfg.rope_theta)
    from ray_tpu.ops import full_causal_attention

    attn = full_causal_attention(q, kk, vv, mesh=mesh)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, layer["wo"]).astype(x.dtype)

    h = rms_norm(x, layer["ln_moe"], cfg.norm_eps)
    moe_out, aux = moe_ffn(h, layer, cfg)
    return x + moe_out, aux


def forward_hidden(params: Params, tokens: jnp.ndarray, cfg: MixtralConfig,
                   *, mesh: Optional[Mesh] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tokens [B,S] -> (hidden [B,S,D], total router aux loss)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    table = constrain(params["embed"], ("vocab", None))
    x = jnp.take(table, tokens, axis=0).astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", None))

    def body(carry, layer):
        x, aux = carry
        y, a = _moe_block(x, layer, positions, cfg, mesh)
        return (y, aux + a), None

    body_fn = body
    if cfg.remat:
        # _remat_policy only reads .remat_policy — shared across models.
        body_fn = jax.checkpoint(body, policy=llama._remat_policy(cfg))
    (x, aux), _ = lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                           params["blocks"])
    return rms_norm(x, params["ln_out"], cfg.norm_eps), aux


def loss_fn(params: Params, tokens: jnp.ndarray, cfg: MixtralConfig,
            *, mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, Dict]:
    hidden, aux = forward_hidden(params, tokens, cfg, mesh=mesh)
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    valid = jnp.broadcast_to(
        (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :], (b, s))
    logits = jnp.einsum("bsd,dv->bsv", hidden,
                        params["lm_head"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    ce = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}
