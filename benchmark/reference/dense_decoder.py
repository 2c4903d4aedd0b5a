"""The plain reference: a dense decoder-only transformer's forward pass
and next-token loss in straightforward float32 ``jax.numpy``.

Written from the published description of the Llama/Mistral/SmolLM2
family of blocks, independent of ``ray_tpu/models/llama.py``:

    h   = x + Attn(RMSNorm(x))          RMSNorm(x) = x / rms(x) * g
    out = h + W_down (silu(W_gate n) * (W_up n)),  n = RMSNorm(h)

with rotary embeddings in the rotate-half form on q and k
(inv_freq_i = theta^(-2i/head_dim), the pair (i, i + head_dim/2)
rotated by position * inv_freq_i), grouped-query attention (each
key/value head serves n_heads / n_kv_heads query heads), a causal
softmax(q k^T / sqrt(head_dim)) v, a final RMSNorm and an output head
that is its own matrix or the transposed embedding table when tied.

No kernels, no cache, no batching tricks, no scan: a Python loop over
layers, each layer's bf16 weights widened to float32 on the way in.
Every matrix multiplication runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes.

It reads the SYSTEM's parameter tree (so both sides hold the same
numbers). Two things about that tree are the system's conventions, not
the published ones, and are undone here:

- norm gains are stored as an offset from one (``g = 1 + stored``);
- projections are stored split by head: ``wq [L, D, H, hd]``,
  ``wk, wv [L, D, KH, hd]``, ``wo [L, H, hd, D]``; the MLP as
  ``w_gate, w_up [L, D, F]``, ``w_down [L, F, D]``; ``embed [V, D]``,
  ``lm_head [D, V]``.

``cfg`` is a configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, stored_gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + stored_gain.astype(F32))


def _rotate_half(x, positions, theta):
    """x [B, T, H, hd]; positions [B, T]."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, :, None, None] * inv_freq   # [B,T,1,hd/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(x, w, *, eps, theta):
    """One block on x [B, T, D] float32; ``w`` is one layer's weights."""
    with jax.default_matmul_precision("highest"):
        b, t, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        wq, wk, wv, wo = (w[k].astype(F32) for k in ("wq", "wk", "wv", "wo"))
        n = _rms_norm(x, w["ln_attn"], eps)
        q = _rotate_half(jnp.einsum("btd,dhk->bthk", n, wq), positions, theta)
        k = _rotate_half(jnp.einsum("btd,dhk->bthk", n, wk), positions, theta)
        v = jnp.einsum("btd,dhk->bthk", n, wv)
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(
            jnp.asarray(q.shape[-1], F32))
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        attn = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(scores, axis=-1), v)
        x = x + jnp.einsum("bqhk,hkd->bqd", attn, wo)
        n = _rms_norm(x, w["ln_mlp"], eps)
        gate = jnp.einsum("btd,df->btf", n, w["w_gate"].astype(F32))
        up = jnp.einsum("btd,df->btf", n, w["w_up"].astype(F32))
        return x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                              w["w_down"].astype(F32))


def hidden(params, tokens, cfg):
    """tokens [B, T] -> the last block's output [B, T, D], before the
    final norm."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.tree.map(lambda a: a[i], params["blocks"])
        x = _layer(x, layer, eps=cfg["rms_norm_eps"],
                   theta=float(cfg["rope_theta"]))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, ln_out, table, *, eps, tied):
    with jax.default_matmul_precision("highest"):
        n = _rms_norm(x, ln_out, eps)
        w = table.astype(F32)
        return n @ (w.T if tied else w)


def logits_at(params, tokens, rows, cfg):
    """Float32 logits [len(rows), V] at the (sequence, position) pairs
    ``rows``, from a full causal forward pass over tokens [B, T]."""
    x = hidden(params, tokens, cfg)
    b, t = zip(*rows)
    picked = x[jnp.asarray(b), jnp.asarray(t)]
    tied = cfg["tie_word_embeddings"]
    return _head(picked, params["ln_out"],
                 params["embed"] if tied else params["lm_head"],
                 eps=cfg["rms_norm_eps"], tied=tied)


def loss(params, tokens, cfg):
    """Mean next-token cross entropy over tokens [B, T]: position t
    predicts token t+1, the last position predicts nothing."""
    x = hidden(params, tokens, cfg)
    tied = cfg["tie_word_embeddings"]
    logits = _head(x[:, :-1], params["ln_out"],
                   params["embed"] if tied else params["lm_head"],
                   eps=cfg["rms_norm_eps"], tied=tied)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
